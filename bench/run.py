"""Chip benchmark of the delivery path: one cell of ``BENCHMARK.json``, run
once.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1

A cell names a configuration and a traffic mix, and the harness finds
everything by name:

* ``bench/configs/<config>.json`` holds the configuration as it is run,
  and ``bench/configs/<config>.py`` beside it makes its artifacts from the
  seed (``make(cfg, seed, spans)`` returns a source whose ``next()`` gives
  the next version and whose ``reference(version)`` gives the bytes the
  registry must hold);
* ``bench/traffic/<traffic>.json`` holds the mix as data, which the one
  loop below reads: ``setup_versions`` published in set-up; ``fleets``,
  groups of pulling clients (``clients`` over ``processes``, their
  ``pipeline_depth`` and ``connections``, and ``state``: ``seeded`` with
  the newest set-up version, or ``empty``); and ``round``, the steps the
  window repeats until it closes.  A step is ``{"op": "publish"}``, the
  next version committed and pushed, or ``{"op": "pull", "fleet": name}``
  with ``mode`` (``upgrade`` keeps the clients' state, ``cold`` starts
  them empty), ``arrival`` (``{"kind": "wave"}``, all due at once, or
  ``{"kind": "poisson", "per_s": r, "burst": b}``) and ``lineage``
  (``"each"`` in turn, or ``{"zipf": a}`` drawn per client); each pull is
  timed from when it was due.  ``background`` lists open-loop streams: a
  fleet of its own pulls at ``arrival`` (Poisson) through the whole
  window, ``per_s`` times the window's seconds pulls over its clients,
  each started when due whatever is still running, so a queue can grow;
* ``bench/metrics/<metric>.py`` reads one metric from the run
  (``read(run)``; ``None`` when the run has nothing to read).

One process holds the chip and publishes: ``ImageClient.commit`` with the
device boundary scan, then ``push`` over ``MuxSocketTransport``.  The
registry (``bench/server_proc.py``) and the pulling fleets
(``bench/fleet_proc.py``) are child processes that never import JAX.

Set-up runs from process start to the window: JAX and the chip, the
compile cache, the artifacts of version 0, the registry, the versions the
mix publishes in set-up (which compiles or loads every scan shape, since
every version of a configuration has the same lengths), and the fleet.
The window then runs rounds until ``--seconds`` have passed and ends with
the round it started; the clock stops while the benchmark makes the next
version's bytes.  ``--trace 1`` records the window
with the JAX profiler and prints the per-layer metrics instead of the
end-to-end ones.  After the window the device's peak memory is read, the
program's state is freed, and a sample of the window's versions, drawn
from the seed, is compared with the plain reference (``reference.py``):
every cut offset, fingerprint and CDMT root on the publisher, what the
registry holds and reads back, and the recipe and root of each client
that pulled a sampled version and, for a sample of those clients, the
bytes it materialises.  A fleet that holds none of the sampled versions
is checked on the newest version it pulled.  Each number compared is
printed with its limit as the last lines of standard error and under
``checks`` in the result, the last line of standard output.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is counted from here

import argparse                   # noqa: E402
import contextlib                 # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import random                     # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402
import tempfile                   # noqa: E402
import traceback                  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np                # noqa: E402

from proc import artifact_digest, recipe_digest  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
REFERENCE_THREADS = 8
RECENT_VERSIONS = 4     # versions kept for checking a fleet's newest pull


def load_module(path: str):
    """Import the file at ``path`` under a name of its own."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names, found by name."""

    def __init__(self, spec: dict, workload: str, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        w = cells[workload]
        config = {c["name"]: c for c in spec["configs"]}[w["config"]]
        bench = os.path.join(root, "bench")
        self.name = workload
        self.chips = int(w["chips"])
        with open(os.path.join(root, config["file"])) as f:
            self.cfg = json.load(f)
        self.generator = os.path.splitext(
            os.path.join(root, config["file"]))[0] + ".py"
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            self.traffic = json.load(f)

        # a metric without "workloads" is every cell's; a per-layer one is
        # then every cell's that reports the end-to-end metric it moves
        self.end_to_end = [m for m in spec["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (workload in m["workloads"] if "workloads" in m
                              else m["moves"] in names)]
        self.readers = {m["name"]: os.path.join(bench, "metrics",
                                                m["name"] + ".py")
                        for m in self.end_to_end + self.per_layer}


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return Cell(json.load(f), workload, root)


class Spans:
    """Host-clock totals of the benchmark's own spans around the calls into
    each layer; each span is also written into a profiler trace when one is
    recording.  ``paused`` spans are time the window's clock leaves out."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.reset()

    def reset(self) -> None:
        self.totals: Dict[str, float] = {}
        self.paused_s = 0.0

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        try:
            with self._annotation(name):
                yield
        finally:
            dt = time.perf_counter() - t
            self.totals[name] = self.totals.get(name, 0.0) + dt

    @contextlib.contextmanager
    def paused(self, name: str):
        t = time.perf_counter()
        try:
            with self.span(name):
                yield
        finally:
            self.paused_s += time.perf_counter() - t


class CompileCounter:
    """Counts XLA compilations (executables built, persistent-cache hits
    included) and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Reservoir:
    """A uniform sample of ``k`` items of a stream, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng, self.n = k, random.Random(seed), 0
        self.items: list = []

    def offer(self, item) -> None:
        if self.n < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.n + 1)
            if j < self.k:
                self.items[j] = item
        self.n += 1


class Run:
    """What the metric readers read."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_s = 0.0
        self.spans: Dict[str, float] = {}     # window totals by span name
        self.published_bytes = 0              # artifact bytes committed+pushed
        self.push_wire = 0
        self.push_raw = 0
        self.scan_bytes = 0
        self.pulls: List[dict] = []           # seconds/wire/raw per pull
        self.waves: List[float] = []          # slowest pull of each wave
        self.chunks: List[int] = []           # chunks of each commit
        self.pull_parts: Dict[str, float] = {}   # obs span sums (traced)
        self.trace = None                     # trace_reduce.Trace (traced)
        self.device_kind = ""


def arrivals(spec: dict, n: int, rng: random.Random) -> List[float]:
    """Seconds after a pull step starts at which each of its ``n`` pulls is
    due, in client order: ``wave`` all at once; ``poisson`` at ``per_s``
    on average, in bursts of ``burst`` due together, clients in a seeded
    order."""
    kind = spec.get("kind", "wave")
    if kind == "wave":
        return [0.0] * n
    if kind != "poisson":
        raise ValueError(f"unknown arrival kind {kind!r}")
    burst = int(spec.get("burst", 1))
    t, times = 0.0, []
    for i in range(n):
        if i % burst == 0 and i:
            t += rng.expovariate(spec["per_s"] / burst)
        times.append(t)
    order = list(range(n))
    rng.shuffle(order)
    return [times[order[i]] for i in range(n)]


def pick_lineages(spec, lineages: List[str], n: int,
                  rng: random.Random) -> List[List[str]]:
    """The lineage each client pulls, for each pull of the step: ``each``
    is one pull of every client per lineage, in turn; ``{"zipf": a}`` one
    pull in which each client draws a lineage with weight 1 / rank**a."""
    if spec == "each":
        return [[lineage] * n for lineage in lineages]
    weights = [1.0 / (r + 1) ** spec["zipf"] for r in range(len(lineages))]
    return [rng.choices(lineages, weights, k=n)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             impl: str = "pallas") -> dict:
    """Set up, measure and check one run; return the result line."""
    import jax
    from proc import Child, Fleet
    from reference import Format
    from repro.delivery import ImageClient, MuxSocketTransport
    from repro.kernels import ops

    cfg, mix = cell.cfg, cell.traffic
    run = Run()
    devices = jax.devices()
    run.device_kind = devices[0].device_kind
    counter = CompileCounter()
    spans = Spans()
    rng = random.Random(seed)
    setup = {"start": time.perf_counter() - T0}
    mark = time.perf_counter()

    def lap(name):
        nonlocal mark
        now = time.perf_counter()
        setup[name] = now - mark
        mark = now

    source = load_module(cell.generator).make(cfg, seed, spans)
    lap("source")
    children: List[Child] = []
    fleets: Dict[str, Fleet] = {}
    transport = None
    registry_dir = None
    try:
        if cfg["registry"]["durability"] != "journal":
            raise ValueError(f"unknown durability "
                             f"{cfg['registry']['durability']!r}")
        registry_dir = tempfile.mkdtemp(prefix="bench-registry-")
        server = Child("server_proc.py", "--workers",
                       str(cfg["registry"]["workers"]),
                       "--directory", registry_dir)
        children.append(server)
        transport = MuxSocketTransport(tuple(server.hello["address"]))
        lap("registry")
        device_scan = ops.device_scan(impl)

        def scan(data, params):
            with spans.span("bench.scan"):
                cuts = device_scan(data, params)
            run.scan_bytes += len(data)
            return cuts

        publisher = ImageClient(transport, scan=scan)
        failed, attempted, errors = 0, 0, []
        latest = None                        # the newest version published
        lineages: List[str] = []
        # what each client of each fleet holds (lineage -> tags); the order
        # of every tag published and the newest versions, for the check
        held: Dict[str, List[Dict[str, set]]] = {}
        order: Dict[str, int] = {}
        recent: Dict[str, object] = {}
        fleets_named = bool(mix.get("fleets"))

        def publish(step=None) -> None:
            nonlocal latest, lineages, attempted
            version = source.next()
            lineages = [lineage for lineage, _ in version.artifacts]
            attempted += len(version.artifacts)
            for lineage, data in version.artifacts:
                with spans.span("bench.commit"):
                    recipe = publisher.commit(lineage, version.tag, data)
                run.chunks.append(len(recipe.fps))
                with spans.span("bench.push"):
                    report = publisher.push(lineage, version.tag)
                run.published_bytes += len(data)
                run.push_wire += report.total_wire_bytes
                run.push_raw += report.raw_bytes
            version.artifacts = [(lineage, len(data))
                                 for lineage, data in version.artifacts]
            latest = version
            reservoir.offer(version)
            order[version.tag] = len(order)
            recent[version.tag] = version
            while len(recent) > (RECENT_VERSIONS if fleets_named else 0):
                del recent[min(recent, key=order.get)]

        def pull(step) -> None:
            name = step["fleet"]
            fleet = fleets[name]
            mode = step.get("mode", "upgrade")
            for chosen in pick_lineages(step.get("lineage", "each"),
                                        lineages, fleet.clients, rng):
                offsets = arrivals(step.get("arrival", {}), fleet.clients,
                                   rng)
                with spans.span("bench.pull"):
                    got = fleet.pull(chosen, mode, offsets)
                # every client should now hold the version just published
                got["tags"] = [latest.tag] * fleet.clients
                record(name, list(range(fleet.clients)), chosen, mode, got)

        def record(name, who, chosen, mode, got) -> None:
            """Count a fleet's pulls (client ``who[k]`` pulled
            ``chosen[k]``, in the order they were due) and what each client
            now holds."""
            nonlocal attempted, failed
            attempted += len(got["seconds"])
            failed += len(got["errors"])
            errors.extend(got["errors"][:3])
            run.waves.append(max((s for s in got["seconds"]
                                  if s is not None), default=0.0))
            run.pulls += [{"seconds": s, "wire": w, "raw": r}
                          for s, w, r in zip(got["seconds"], got["wire"],
                                             got["raw"]) if s is not None]
            for k, v in got["spans"].items():
                run.pull_parts[k] = run.pull_parts.get(k, 0.0) + v
            for i, lineage, tag, s in zip(who, chosen, got["tags"],
                                          got["seconds"]):
                if mode == "cold":
                    held[name][i] = {}
                if s is not None and tag not in order:
                    failed += 1
                    errors.append(f"client {i} reported pulling {tag!r}")
                elif s is not None:
                    held[name][i].setdefault(lineage, set()).add(tag)

        steps = {"publish": publish, "pull": pull}
        busy = {s["fleet"] for s in mix.get("background", [])}
        if busy & {s.get("fleet") for s in mix["round"]}:
            raise ValueError("a fleet that streams in the background "
                             "cannot also pull in the rounds")
        reservoir = Reservoir(0, seed)      # set-up's versions are not sampled
        for _ in range(mix["setup_versions"]):
            publish()
        lap("publish")
        for name, spec in mix.get("fleets", {}).items():
            fleets[name] = Fleet(children, transport.address, spec, trace)
            held[name] = [{} for _ in range(spec["clients"])]
            if spec.get("state", "seeded") == "seeded":
                for lineage in lineages:
                    fleets[name].seed(lineage, latest.tag)
                    for h in held[name]:
                        h[lineage] = {latest.tag}
        if fleets:
            lap("fleet")

        reservoir = Reservoir(cfg["check"]["versions"], seed)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles = counter.count
        attempted = 0
        run.setup_s = time.perf_counter() - T0
        spans.reset()
        run.scan_bytes = 0
        run.published_bytes = run.push_wire = run.push_raw = 0
        run.chunks = []
        start = time.perf_counter()
        streams = []
        for step in mix.get("background", []):
            # open-loop pulls over the whole window: a count fixed by the
            # rate and the window, spread over the fleet's clients
            fleet = fleets[step["fleet"]]
            n = max(1, round(step["arrival"]["per_s"] * seconds))
            who = [k % fleet.clients for k in range(n)]
            spec = step.get("lineage", "each")
            chosen = ([lineages[k % len(lineages)] for k in range(n)]
                      if spec == "each" else
                      pick_lineages(spec, lineages, n, rng)[0])
            mode = step.get("mode", "upgrade")
            fleet.start_stream(who, chosen, mode,
                               arrivals(step["arrival"], n, rng))
            streams.append((step["fleet"], who, chosen, mode))
        with spans.span("bench.window"):
            while time.perf_counter() - start - spans.paused_s < seconds:
                try:
                    for step in mix["round"]:
                        steps[step["op"]](step)
                except Exception as e:    # a failed operation, not a crash
                    traceback.print_exc()
                    failed += 1
                    errors.append(f"{step['op']}: {type(e).__name__}: {e}")
                    break
            for name, who, chosen, mode in streams:
                with spans.span("bench.stream"):
                    got = fleets[name].finish_stream()
                record(name, who, chosen, mode, got)
        run.window_s = time.perf_counter() - start - spans.paused_s
        run.spans = dict(spans.totals)
        window_compiles = counter.count - compiles
        if trace:
            jax.profiler.stop_trace()
            from trace_reduce import Trace
            run.trace = Trace.load(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)

        # The versions compared: the seed's sample of the window's, and for
        # a fleet that holds none of those, the newest version it holds.
        checked = list(reservoir.items)
        unchecked = 0
        for name in fleets:
            tags = {t for h in held[name] for ts in h.values() for t in ts}
            if tags and not tags & {v.tag for v in checked}:
                newest = [t for t in sorted(tags, key=order.get)
                          if t in recent][-1:]
                checked += [recent[t] for t in newest]
                unchecked += not newest
        # The program's answers for them, then its state is freed before
        # the reference runs.
        keys = [(lineage, v.tag) for v in checked
                for lineage, _ in v.artifacts]
        program = {k: (publisher.store.recipes[f"{k[0]}:{k[1]}"],
                       publisher.index_for_tag(*k).root) for k in keys}
        registry, clients = {}, {}
        for lineage in lineages:
            tags = [t for lin, t in keys if lin == lineage]
            for tag, got in server.request("check", lineage=lineage,
                                           tags=tags).items():
                registry[(lineage, tag)] = got
            # each client that should hold a tag is compared; a sample of
            # them, drawn from the seed, also materialises it
            for name, fleet in fleets.items():
                for tag in tags:
                    holders = [i for i, h in enumerate(held[name])
                               if tag in h.get(lineage, ())]
                    if not holders:
                        continue
                    sample = sorted(rng.sample(holders, min(
                        mix.get("check_clients", 0), len(holders))))
                    got = fleet.check(lineage, [tag], sample)[tag]
                    got["recipes"] = [got["recipes"][i] for i in holders]
                    got["roots"] = [got["roots"][i] for i in holders]
                    clients.setdefault((lineage, tag), []).append(got)
    finally:
        if transport is not None:
            transport.close()
        for child in reversed(children):
            child.stop()
        if registry_dir is not None:
            shutil.rmtree(registry_dir, ignore_errors=True)
    del publisher
    source.free()

    check_start = time.perf_counter()
    counts = compare(Format(cfg["format"]), source, checked, program,
                     registry, clients if fleets else None)
    check_s = time.perf_counter() - check_start
    counts["ops_failed"] = failed
    if unchecked:
        counts["fleets_unchecked"] = unchecked
    checks = {name: {"value": value, "limit": 0}
              for name, value in counts.items()}
    correct = checked and all(c["value"] <= c["limit"]
                              for c in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(cell.readers[m["name"]]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace.device_ops()],
            "idle_gaps": [list(x) for x in run.trace.idle_gaps()]}
    result["setup_parts"] = setup
    result["window_spans"] = run.spans
    result["versions_checked"] = len(checked)
    result["reference_s"] = check_s
    result["window_compiles"] = window_compiles
    result["pulls"] = len(run.pulls)
    result["waves_s"] = run.waves
    result["chunks"] = run.chunks
    result["errors"] = errors[:5]
    result["checks"] = checks
    return result


def compare(fmt, source, sampled, program, registry, clients) -> dict:
    """Count what differs from the reference over the sampled versions:
    the publisher's cut offsets, fingerprints and CDMT roots, what the
    registry holds and reads back, and (with a fleet) every client's recipe
    and root and the sampled clients' bytes."""
    counts = dict.fromkeys(["cuts_differ", "fps_differ", "roots_differ",
                            "registry_differ"], 0)
    if clients is not None:
        counts.update(dict.fromkeys(["client_recipes_differ",
                                     "client_roots_differ",
                                     "client_bytes_differ"], 0))
    with ThreadPoolExecutor(REFERENCE_THREADS) as pool:
        for version in sampled:
            for lineage, data in source.reference(version):
                sizes, fps = fmt.recipe(data, pool)
                root = fmt.cdmt_root(fps).hex()
                digest = artifact_digest([data])
                key = (lineage, version.tag)
                recipe, got_root = program[key]
                counts["cuts_differ"] += len(
                    set(np.cumsum(recipe.sizes).tolist())
                    ^ set(np.cumsum(sizes).tolist()))
                counts["fps_differ"] += sum(
                    a != b for a, b in zip(recipe.fps, fps)) + abs(
                    len(recipe.fps) - len(fps))
                counts["roots_differ"] += got_root is None \
                    or got_root.hex() != root
                reg = registry[key]
                counts["registry_differ"] += (
                    "missing" in reg
                    or reg["sizes"] != sizes or reg["root"] != root
                    or reg["fps"] != [fp.hex() for fp in fps]
                    or reg["digest"] != digest)
                want = recipe_digest(fps, sizes)
                for got in (clients or {}).get(key, []):
                    counts["client_recipes_differ"] += sum(
                        r != want for r in got["recipes"])
                    counts["client_roots_differ"] += sum(
                        r != root for r in got["roots"])
                    counts["client_bytes_differ"] += sum(
                        d != digest for d in got["digests"])
    return counts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    cell = load_cell(args.workload)
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"bench: the program under test is not here ({e})",
              file=sys.stderr)
        return 2
    configure_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print("set-up seconds: " + ", ".join(
        f"{k} {v:.3f}" for k, v in result["setup_parts"].items()),
        file=sys.stderr)
    print(f"pulls completed in the window: {result['pulls']}; compilations "
          f"in the window: {result['window_compiles']}; versions checked: "
          f"{result['versions_checked']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
