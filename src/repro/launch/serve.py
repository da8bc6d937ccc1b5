"""Serving launcher: pull weights via CDMT, serve batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --requests 16 --new-tokens 8
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config, list_archs
from repro.launch.compile_cache import configure_compile_cache
from repro.models.api import Model
from repro.serving import Request, ServeConfig, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    model = Model(get_config(args.arch, reduced=args.reduced))
    params = model.init_params(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(model, params,
                           ServeConfig(batch_size=args.batch,
                                       max_len=args.prompt_len + args.new_tokens
                                       + model.cfg.decode_margin))
    rng = np.random.default_rng(args.seed)
    reqs = [Request(id=i,
                    prompt=rng.integers(0, model.cfg.vocab,
                                        size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    metrics = engine.serve(reqs)
    print(f"served {metrics['requests']} requests in {metrics['wall_s']:.2f}s "
          f"→ {metrics['tokens_per_s']:.1f} new tokens/s")
    print("sample output:", reqs[0].output[:8])


if __name__ == "__main__":
    main()
