"""Registry process of a benchmark run: ``AsyncRegistryServer`` over
``RegistryServer(Registry(directory))``, on a loopback port.

    python bench/server_proc.py --workers N --directory DIR

The registry is durable, as a deployed one is: each push's chunks are
fsynced and its commit journaled in ``DIR`` before it is acknowledged.

Its first line gives the address.  ``check`` answers, for each tag, what
the registry holds: the recipe (sizes and fingerprints), the CDMT root, and
a digest of the artifact read back from its chunks; or that it holds no
such tag.
"""

from __future__ import annotations

import argparse

from proc import artifact_digest, serve
from repro.core.errors import DeliveryError
from repro.core.registry import Registry
from repro.delivery import AsyncRegistryServer, RegistryServer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, required=True)
    ap.add_argument("--directory", required=True)
    args = ap.parse_args()
    registry = Registry(args.directory)
    server = AsyncRegistryServer(RegistryServer(registry), workers=args.workers)

    def check(lineage: str, tags: list) -> dict:
        out = {}
        for tag in tags:
            try:
                recipe = registry.recipe_for(lineage, tag)
            except DeliveryError as e:      # read as a wrong answer
                out[tag] = {"missing": str(e)}
                continue
            chunks = registry.serve_chunks(recipe.fps)
            out[tag] = {"sizes": recipe.sizes,
                        "fps": [fp.hex() for fp in recipe.fps],
                        "root": registry.index_for_tag(lineage, tag).root.hex(),
                        "digest": artifact_digest(chunks[fp]
                                                  for fp in recipe.fps)}
        return out

    try:
        serve({"address": list(server.address)}, {"check": check})
    finally:
        server.stop()
        registry.close()


if __name__ == "__main__":
    main()
