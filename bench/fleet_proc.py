"""Pulling fleet of a benchmark run: ``ImageClient``s over
``MuxSocketTransport``, one transport each, in one process.

    python bench/fleet_proc.py --address HOST:PORT --clients N
        --pipeline-depth D --connections C --trace 0|1

* ``seed``: one client pulls a tag cold; the others are given its state by
  reference (the same chunk objects, recipe and CDMT), as if each had pulled
  it too.
* ``pull``: client ``i`` upgrades ``lineages[i]`` to its head at
  ``due + offsets[i]`` on the wall clock of the machine, and its pull is
  timed from then.  ``mode`` ``"upgrade"`` keeps what the client holds;
  ``"cold"`` gives the client fresh, empty state first (before any pull is
  due), as a newly provisioned node has.  With ``--trace 1`` the clients
  share one ``repro.obs`` Tracer, and the reply also holds the summed time
  of the pull spans' parts.
* ``stream``: open-loop pulls for a whole window: pull ``k`` is due at
  ``due + offsets[k]`` and upgrades ``lineages[k]`` on client
  ``who[k]``; each starts in a thread of its own when due, whatever is
  still running, and waits for its client if an earlier pull holds it.
  ``mode`` is as for ``pull``, applied to the client as its pull starts.
* ``check``: for each tag, every client's recipe and CDMT root, and the
  digest of the artifact that the sampled clients materialise.
"""

from __future__ import annotations

import argparse
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from proc import artifact_digest, recipe_digest, serve
from repro.delivery import ImageClient, MuxSocketTransport
from repro.obs import Tracer
from trace_reduce import union


def span_parts(roots) -> dict:
    """Summed seconds of each part of the ``pull`` span trees."""
    parts = {"pull": 0.0, "plan": 0.0, "fetch": 0.0, "ingest": 0.0}
    for root in roots:
        if root.name != "pull":
            continue
        parts["pull"] += root.duration
        for child in root.children:
            if child.name == "plan_pull":
                parts["plan"] += child.duration
            elif child.name == "execute":
                parts["fetch"] += sum(b - a for a, b in union(
                    (c.t0, c.t1) for c in child.children
                    if c.name == "fetch_batch"))
                parts["ingest"] += sum(c.duration for c in child.children
                                       if c.name == "ingest")
    return parts


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--address", required=True)
    ap.add_argument("--clients", type=int, required=True)
    ap.add_argument("--pipeline-depth", type=int, required=True)
    ap.add_argument("--connections", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    host, port = args.address.rsplit(":", 1)
    address = (host, int(port))
    tracer = Tracer(enabled=bool(args.trace), capacity=1 << 16)
    transports = {}

    def client(**kw) -> ImageClient:
        transport = MuxSocketTransport(address, connections=args.connections)
        c = ImageClient(transport, pipeline_depth=args.pipeline_depth,
                        tracer=tracer, **kw)
        transports[id(c)] = transport
        return c

    def fresh(i: int) -> None:
        transports.pop(id(clients[i])).close()
        clients[i] = client()

    clients = [client() for _ in range(args.clients)]

    def seed(lineage: str, tag: str) -> dict:
        t0 = time.perf_counter()
        first = clients[0]
        report = first.pull(lineage, tag)
        key = f"{lineage}:{tag}"
        recipe = first.store.recipes[key]
        chunks = {fp: first.store.chunks.get(fp) for fp in set(recipe.fps)}
        for c in clients[1:]:
            c.indexes[lineage] = first.indexes[lineage]
            c.tag_trees[key] = first.tag_trees[key]
            c.store.ingest_chunks(key, recipe.fps, chunks, recipe.sizes,
                                  verify=False)
        tracer.take()
        return {"seconds": time.perf_counter() - t0,
                "wire": report.total_wire_bytes}

    def pull(lineages: list, mode: str, offsets: list, due: float) -> dict:
        n = len(clients)
        if mode == "cold":
            for i in range(n):
                fresh(i)
        elif mode != "upgrade":
            raise ValueError(f"unknown pull mode {mode!r}")
        seconds = [None] * n
        wire = [0] * n
        raw = [0] * n
        tags = [None] * n
        errors = []
        lock = threading.Lock()

        def one(i):
            start = due + offsets[i]
            wait = start - time.time()
            if wait > 0:
                time.sleep(wait)
            try:
                report = clients[i].upgrade(lineages[i])
            except Exception as e:  # counted as a failed pull
                with lock:
                    errors.append(f"client {i}: {type(e).__name__}: {e}")
                return
            seconds[i] = time.time() - start
            wire[i] = report.total_wire_bytes
            raw[i] = report.raw_bytes
            tags[i] = report.tag

        with ThreadPoolExecutor(max_workers=n) as pool:
            list(pool.map(one, range(n)))
        return {"seconds": seconds, "wire": wire, "raw": raw, "tags": tags,
                "errors": errors, "spans": span_parts(tracer.take())}

    def stream(who: list, lineages: list, mode: str, offsets: list,
               due: float) -> dict:
        if mode not in ("upgrade", "cold"):
            raise ValueError(f"unknown pull mode {mode!r}")
        n = len(who)
        locks = [threading.Lock() for _ in clients]
        seconds = [None] * n
        wire = [0] * n
        raw = [0] * n
        tags = [None] * n
        errors = []
        lock = threading.Lock()

        def one(k, start):
            try:
                with locks[who[k]]:
                    if mode == "cold":
                        fresh(who[k])
                    report = clients[who[k]].upgrade(lineages[k])
            except Exception as e:  # counted as a failed pull
                with lock:
                    errors.append(f"client {who[k]}: {type(e).__name__}: {e}")
                return
            seconds[k] = time.time() - start
            wire[k] = report.total_wire_bytes
            raw[k] = report.raw_bytes
            tags[k] = report.tag

        threads = []
        for k in sorted(range(n), key=lambda k: offsets[k]):
            start = due + offsets[k]
            wait = start - time.time()
            if wait > 0:
                time.sleep(wait)
            threads.append(threading.Thread(target=one, args=(k, start)))
            threads[-1].start()
        for t in threads:
            t.join()
        return {"seconds": seconds, "wire": wire, "raw": raw, "tags": tags,
                "errors": errors, "spans": span_parts(tracer.take())}

    def check(lineage: str, tags: list, sample: list) -> dict:
        out = {}
        for tag in tags:
            key = f"{lineage}:{tag}"
            recipes, roots, digests = [], [], {}
            for c in clients:
                r = c.store.recipes.get(key)
                tree = c.tag_trees.get(key)
                recipes.append(None if r is None
                               else recipe_digest(r.fps, r.sizes))
                roots.append(None if tree is None or tree.root is None
                             else tree.root.hex())
            for i in sample:
                try:
                    data = clients[i].materialize(lineage, tag)
                except Exception as e:  # a missing version reads as wrong
                    digests[str(i)] = f"{type(e).__name__}: {e}"
                    continue
                digests[str(i)] = artifact_digest([data])
            out[tag] = {"recipes": recipes, "roots": roots,
                        "digests": digests}
        return out

    try:
        serve({"clients": args.clients},
              {"seed": seed, "pull": pull, "stream": stream,
               "check": check})
    finally:
        for t in transports.values():
            t.close()


if __name__ == "__main__":
    main()
