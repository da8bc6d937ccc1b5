"""Child processes of a benchmark run, and the line protocol between them.

The registry and the pulling fleet run in processes of their own, which
never import JAX, so that they share no interpreter lock with the publisher
(as separate machines would not) and never touch the chip.  The parent
writes one JSON object per line to a child's standard input and reads one
JSON reply per line from its standard output; ``{"op": "stop"}`` or the end
of the input ends the child.  A child's errors go to the run's standard
error.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
import traceback
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STOP_TIMEOUT = 30.0


def artifact_digest(parts) -> str:
    """One digest of an artifact's bytes, given in order in any pieces."""
    h = hashlib.blake2b(digest_size=32)
    for part in parts:
        h.update(part)
    return h.hexdigest()


def recipe_digest(fps, sizes) -> str:
    """One digest of a recipe's fingerprints and chunk sizes."""
    h = hashlib.blake2b(digest_size=16)
    for fp, size in zip(fps, sizes):
        h.update(fp + int(size).to_bytes(8, "little"))
    h.update(len(fps).to_bytes(8, "little"))
    return h.hexdigest()


class ChildError(RuntimeError):
    pass


class Child:
    """A child process speaking the line protocol."""

    def __init__(self, script: str, *args: str):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(ROOT, "src"), HERE]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        self.name = script
        self.p = subprocess.Popen(
            [sys.executable, os.path.join(HERE, script), *args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True, bufsize=1)
        try:
            self.hello = self._read()
        except BaseException:
            self.p.kill()
            self.p.wait()
            raise

    def _read(self) -> dict:
        line = self.p.stdout.readline()
        if not line:
            raise ChildError(f"{self.name} exited (code {self.p.poll()}) "
                             f"without answering")
        reply = json.loads(line)
        if "error" in reply:
            raise ChildError(f"{self.name}: {reply['error']}")
        return reply

    def send(self, op: str, **kw) -> None:
        self.p.stdin.write(json.dumps({"op": op, **kw}) + "\n")
        self.p.stdin.flush()

    def request(self, op: str, **kw) -> dict:
        self.send(op, **kw)
        return self._read()

    def stop(self) -> None:
        """Ask the child to stop, wait for it, and kill it if it hangs."""
        if self.p.poll() is None:
            try:
                self.p.stdin.write(json.dumps({"op": "stop"}) + "\n")
                self.p.stdin.close()
            except OSError:
                pass
            try:
                self.p.wait(timeout=STOP_TIMEOUT)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()
        self.p.stdout.close()


class Fleet:
    """One group of the pulling fleet, as a mix's ``fleets`` entry names
    it: ``clients`` clients split evenly over ``processes`` children
    running ``fleet_proc.py``, which act as one.  Each child is appended to
    ``children`` as it starts, so the caller stops every one that started."""

    def __init__(self, children: List[Child], address, spec: dict,
                 trace: bool):
        clients, processes = spec["clients"], spec["processes"]
        if clients % processes:
            raise ValueError(f"{clients} clients do not split evenly over "
                             f"{processes} processes")
        self.clients = clients
        self.per = clients // processes
        self.procs: List[Child] = []
        for _ in range(processes):
            children.append(Child(
                "fleet_proc.py", "--address", "%s:%d" % tuple(address),
                "--clients", str(self.per),
                "--pipeline-depth", str(spec["pipeline_depth"]),
                "--connections", str(spec["connections"]),
                "--trace", str(int(trace))))
            self.procs.append(children[-1])

    def _all(self, op: str, each=None, **kw) -> List[dict]:
        """Send ``op`` to every child first, then read every reply."""
        for i, child in enumerate(self.procs):
            child.send(op, **kw, **(each(i) if each else {}))
        return [child._read() for child in self.procs]

    def _part(self, values: list, i: int) -> list:
        return values[i * self.per:(i + 1) * self.per]

    def seed(self, lineage: str, tag: str) -> None:
        self._all("seed", lineage=lineage, tag=tag)

    def pull(self, lineages: list, mode: str, offsets: list) -> dict:
        """Client ``i`` upgrades ``lineages[i]``, due ``offsets[i]`` seconds
        after now; lists are in client order."""
        replies = self._all("pull", mode=mode, due=time.time(),
                            each=lambda i: {
                                "lineages": self._part(lineages, i),
                                "offsets": self._part(offsets, i)})
        out = {k: [x for r in replies for x in r[k]]
               for k in ("seconds", "wire", "raw", "tags", "errors")}
        out["spans"] = {k: sum(r["spans"][k] for r in replies)
                        for k in replies[0]["spans"]}
        return out

    def start_stream(self, who: list, lineages: list, mode: str,
                     offsets: list) -> None:
        """Start open-loop pulls: pull ``k`` on client ``who[k]`` of
        ``lineages[k]``, due ``offsets[k]`` seconds after now.  The caller
        reads the outcome with ``finish_stream``."""
        due = time.time()
        self._stream = []
        for i, child in enumerate(self.procs):
            mine = [k for k, c in enumerate(who) if c // self.per == i]
            self._stream.append(mine)
            child.send("stream", mode=mode, due=due,
                       who=[who[k] - i * self.per for k in mine],
                       lineages=[lineages[k] for k in mine],
                       offsets=[offsets[k] for k in mine])

    def finish_stream(self) -> dict:
        """Wait for the started stream; lists are in pull order."""
        replies = [child._read() for child in self.procs]
        n = sum(len(m) for m in self._stream)
        out = {k: [None] * n for k in ("seconds", "wire", "raw", "tags")}
        for mine, r in zip(self._stream, replies):
            for j, k in enumerate(mine):
                for key in out:
                    out[key][k] = r[key][j]
        out["errors"] = [e for r in replies for e in r["errors"]]
        out["spans"] = {k: sum(r["spans"][k] for r in replies)
                        for k in replies[0]["spans"]}
        return out

    def check(self, lineage: str, tags: list, sample: list) -> dict:
        replies = self._all("check", lineage=lineage, tags=tags, each=lambda i: {
            "sample": [c - i * self.per for c in sample
                       if c // self.per == i]})
        return {tag: {"recipes": [x for r in replies for x in r[tag]["recipes"]],
                      "roots": [x for r in replies for x in r[tag]["roots"]],
                      "digests": [d for r in replies
                                  for d in r[tag]["digests"].values()]}
                for tag in tags}


def serve(hello: dict, handlers: Dict[str, Callable[..., dict]]) -> None:
    """The child's side: print ``hello``, then answer requests until told
    to stop or until the input ends."""
    def say(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    say(hello)
    for line in sys.stdin:
        req = json.loads(line)
        op = req.pop("op")
        if op == "stop":
            break
        try:
            say(handlers[op](**req))
        except Exception as e:  # reported to the parent, which fails the run
            traceback.print_exc()
            say({"error": f"{op}: {type(e).__name__}: {e}"})
