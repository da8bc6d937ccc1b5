"""Training launcher.

Reduced-config CPU run (examples/CI):
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 200 --batch 8 --seq 128

Production (per-host process on a real cluster; here the mesh falls back to
the local device set):
    python -m repro.launch.train --arch qwen2-72b --steps 10000 ...

The launcher wires together: config → model → data pipeline → fault-tolerant
Trainer (CDMT-dedup checkpoints to a registry directory) and resumes
automatically from the latest checkpoint on restart.
"""

from __future__ import annotations

import argparse
import time

from repro.checkpoint import CheckpointConfig
from repro.configs.base import get_config, list_archs
from repro.core.registry import Registry
from repro.data import DataConfig
from repro.launch.compile_cache import configure_compile_cache
from repro.models.api import Model
from repro.optim import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.runtime.train_step import TrainConfig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default=None,
                    help="registry directory (persistent across restarts)")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    configure_compile_cache()

    model = Model(get_config(args.arch, reduced=args.reduced))
    print(f"arch={args.arch} reduced={args.reduced} "
          f"params={model.param_count():,}")

    data = DataConfig(vocab=model.cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch, n_hosts=1, seed=args.seed)
    cfg = TrainerConfig(
        total_steps=args.steps,
        ckpt=CheckpointConfig(lineage=f"{args.arch}",
                              every_steps=args.ckpt_every,
                              async_push=args.async_ckpt),
        train=TrainConfig(n_micro=args.n_micro,
                          adamw=AdamWConfig(lr=args.lr),
                          warmup_steps=max(1, args.steps // 20),
                          total_steps=args.steps),
    )
    registry = Registry(directory=args.ckpt_dir)
    trainer = Trainer(model, data, cfg, registry=registry)

    t0 = time.time()

    def log(step, m):
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
                  f"{m['step_s']*1e3:.0f} ms/step")

    state = trainer.run(on_step=log)
    wall = time.time() - t0
    s = trainer.ckpt.wire_summary()
    print(f"done: {args.steps} steps in {wall:.1f}s")
    print(f"checkpoints: {s['checkpoints']}  wire {s['wire_bytes']/2**20:.1f} "
          f"MiB vs raw {s['raw_bytes']/2**20:.1f} MiB "
          f"(savings {s['savings']:.1%})")
    return state


if __name__ == "__main__":
    main()
