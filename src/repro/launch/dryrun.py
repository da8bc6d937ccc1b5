import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- multi-pod dry-run entrypoint -------------------------------------------
# The two lines above MUST run before any jax import: jax locks the device
# count on first backend init.  512 host devices stand in for 2 TPU v5e pods.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
#       --shape train_4k --mesh single
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
#
# Per cell: lower + compile against the production mesh, print
# memory_analysis() (fits-in-HBM proof) and cost_analysis(), run the
# trip-count-aware HLO cost walker (launch/hlo_cost.py), and append a JSON
# record under benchmarks/results/dryrun/.
# -----------------------------------------------------------------------------

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch import hlo_cost, mesh as mesh_lib
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.cells import build_cell, lower_cell

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

# TPU v5e per-chip peaks (mesh.py)
PEAK = {"flops": mesh_lib.PEAK_FLOPS_BF16, "hbm": mesh_lib.HBM_BW,
        "ici": mesh_lib.ICI_BW_PER_LINK}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             rules: Optional[Dict[str, Any]] = None,
             n_micro: Optional[int] = None,
             tag: str = "baseline",
             cfg_overrides: Optional[Dict[str, Any]] = None,
             verbose: bool = True) -> Dict[str, Any]:
    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "chips": n_chips, "tag": tag,
                           "status": "ok",
                           "cfg_overrides": {k: str(v) for k, v in
                                             (cfg_overrides or {}).items()}}
    try:
        t0 = time.time()
        cell = build_cell(arch, shape_name, mesh, rules=rules, n_micro=n_micro,
                          cfg_overrides=cfg_overrides)
        lowered = lower_cell(cell)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed", "transcendentals")}

        t2 = time.time()
        hlo = compiled.as_text()
        cost = hlo_cost.HloCostModel(hlo).entry_cost()
        rec["walk_s"] = round(time.time() - t2, 2)
        rec["hlo_cost"] = cost.as_dict()

        meta = cell.meta
        rec["meta"] = meta
        # --- roofline terms (seconds per step, per chip) ---------------------
        compute_s = cost.flops / PEAK["flops"]
        memory_s = cost.hbm_bytes / PEAK["hbm"]
        # ICI: per-chip wire bytes / per-chip link bandwidth.  A 2-D torus
        # axis has ~3 usable links per direction pair; use 3 links aggregate.
        coll_s = cost.collective_bytes / (3 * PEAK["ici"])
        model_flops_step = (meta["flops_factor"] * meta["active_params"]
                            * meta["tokens_per_step"])
        model_flops_chip = model_flops_step / n_chips
        rec["roofline"] = {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": max(
                (("compute", compute_s), ("memory", memory_s),
                 ("collective", coll_s)), key=lambda kv: kv[1])[0],
            "model_flops_per_chip": model_flops_chip,
            "useful_flops_ratio": (model_flops_chip / cost.flops
                                   if cost.flops else 0.0),
            "step_time_bound_s": max(compute_s, memory_s, coll_s),
            "mfu_bound": model_flops_chip / PEAK["flops"]
                         / max(compute_s, memory_s, coll_s)
                         if max(compute_s, memory_s, coll_s) > 0 else 0.0,
        }
        if verbose:
            m = rec["memory"]
            r = rec["roofline"]
            print(f"[{arch} × {shape_name} × {mesh_kind}] OK  "
                  f"compile={rec['compile_s']}s  "
                  f"mem/chip={m['peak_bytes']/2**30:.2f}GiB  "
                  f"compute={r['compute_s']*1e3:.1f}ms "
                  f"memory={r['memory_s']*1e3:.1f}ms "
                  f"coll={r['collective_s']*1e3:.1f}ms "
                  f"dominant={r['dominant']} mfu_bound={r['mfu_bound']:.2%}")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_kind}] FAIL  {rec['error']}")
    return rec


def save_record(rec: Dict[str, Any], out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    if rec.get("tag", "baseline") != "baseline":
        name += f"__{rec['tag']}"
    path = os.path.join(out_dir, name + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def applicable_cells():
    for arch in list_archs():
        cfg = get_config(arch)
        for shape_name in cfg.applicable_shapes():
            yield arch, shape_name


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every applicable (arch × shape)")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--rules", default=None,
                    help='JSON rule overrides, e.g. \'{"seq_sp": null}\'')
    ap.add_argument("--cfg", default=None,
                    help='JSON ModelConfig overrides, e.g. '
                         '\'{"wkv_impl": "chunked"}\'')
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--out-dir", default=os.path.normpath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()

    rules = json.loads(args.rules) if args.rules else None
    cfg_overrides = json.loads(args.cfg) if args.cfg else None
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = list(applicable_cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape_name in cells:
        for mk in meshes:
            name = f"{arch}__{shape_name}__{mk}"
            if args.tag != "baseline":
                name += f"__{args.tag}"
            path = os.path.join(args.out_dir, name + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        print(f"[{arch} × {shape_name} × {mk}] cached OK")
                        continue
            rec = run_cell(arch, shape_name, mk, rules=rules,
                           n_micro=args.n_micro, tag=args.tag,
                           cfg_overrides=cfg_overrides)
            save_record(rec, args.out_dir)
            n_fail += rec["status"] != "ok"
    print(f"done: {len(cells) * len(meshes)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
