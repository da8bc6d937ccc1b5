"""Plain reference of the delivery format, written from its definition.

It imports nothing of the program under test.  Every constant comes from
the ``format`` block of a configuration file:

* chunking: the gear rolling hash ``h_i = (2 h_{i-1} + G[b_i]) mod 2^32``
  over a 256-entry table ``G`` drawn by ``numpy.random.default_rng(seed)``;
  a cut may follow byte ``i`` when the low ``mask_bits`` of ``h_i`` are
  zero; the first such cut at least ``min_size`` after the chunk start is
  taken, else the chunk ends ``max_size`` after its start (or at the end);
* chunk identifier: blake2b of the chunk with a ``digest_size``-byte digest;
* CDMT (Algorithm 1 of arXiv:2104.02158): each level groups its children
  in order; once a group holds ``window`` or more children, it closes when
  the last byte of blake2b over its last ``window`` children has its low
  ``rule_bits`` zero, or when it reaches ``max_fanout``; a node's id is
  blake2b over its children's ids; levels repeat until one node is left.
"""

from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

import numpy as np

HASH_BITS = 32          # the gear register: bytes older than 32 drop out
WINDOW = 4 << 20        # bytes per worker task; keeps host memory bounded


class Format:
    """The constants of one configuration's ``format`` block."""

    def __init__(self, spec: dict):
        cdc, fp, tree = spec["cdc"], spec["fingerprint"], spec["cdmt"]
        if cdc["rolling_hash"] != "gear" or fp["hash"] != "blake2b":
            raise ValueError(f"reference knows gear + blake2b, got {spec}")
        self.table = np.random.default_rng(cdc["gear_table_seed"]).integers(
            0, 2**32, size=256, dtype=np.uint32)
        self.mask = np.uint32((1 << cdc["mask_bits"]) - 1)
        self.min_size = int(cdc["min_size"])
        self.max_size = int(cdc["max_size"])
        self.digest = int(fp["digest_size"])
        self.window = int(tree["window"])
        self.rule_mask = (1 << int(tree["rule_bits"])) - 1
        self.max_fanout = int(tree["max_fanout"])

    # ------------------------------------------------------------ chunking

    def gear_hash(self, buf: np.ndarray) -> np.ndarray:
        """h_i for every byte of ``buf`` (as if the stream began there).
        Five doubling steps: after the step of shift s, h_i holds the sum of
        G[b_{i-k}] << k for k < 2s."""
        h = self.table[buf]
        s = 1
        while s < HASH_BITS:
            h[s:] += h[:-s] << np.uint32(s)
            s *= 2
        return h

    def candidates(self, buf: np.ndarray, pool: ThreadPoolExecutor
                   ) -> np.ndarray:
        """Sorted offsets ``i + 1`` of every byte ``i`` whose hash matches."""
        def one(start):
            lo = max(0, start - (HASH_BITS - 1))
            h = self.gear_hash(buf[lo:start + WINDOW])[start - lo:]
            return np.flatnonzero((h & self.mask) == 0) + (start + 1)

        parts = list(pool.map(one, range(0, buf.size, WINDOW)))
        return np.concatenate([np.zeros(0, np.int64)] + parts)

    def cuts(self, candidate: np.ndarray, n: int) -> List[int]:
        """Chunk end offsets of an ``n``-byte stream."""
        ends: List[int] = []
        start = 0
        while start < n:
            hi = min(start + self.max_size, n)
            i = int(np.searchsorted(candidate, start + self.min_size))
            cut = int(candidate[i]) if (i < candidate.size
                                        and candidate[i] <= hi) else hi
            ends.append(cut)
            start = cut
        return ends

    def fingerprint(self, data) -> bytes:
        return hashlib.blake2b(data, digest_size=self.digest).digest()

    def recipe(self, data: bytes, pool: ThreadPoolExecutor
               ) -> Tuple[List[int], List[bytes]]:
        """(chunk sizes, chunk fingerprints) of ``data``."""
        buf = np.frombuffer(data, np.uint8)
        ends = self.cuts(self.candidates(buf, pool), buf.size)
        spans = list(zip([0] + ends[:-1], ends))
        view = memoryview(data)

        def hash_batch(i):
            return [self.fingerprint(view[s:e]) for s, e in spans[i:i + 1024]]

        fps = [fp for batch in pool.map(hash_batch, range(0, len(spans), 1024))
               for fp in batch]
        return [e - s for s, e in spans], fps

    # ---------------------------------------------------------------- CDMT

    def node_id(self, children: Sequence[bytes]) -> bytes:
        return self.fingerprint(b"".join(children))

    def cdmt_root(self, leaves: Sequence[bytes]) -> Optional[bytes]:
        level = list(leaves)
        if not level:
            return None
        while len(level) > 1:
            parents, group = [], []
            for child in level:
                group.append(child)
                close = len(group) >= self.max_fanout
                if len(group) >= self.window:
                    tail = self.node_id(group[-self.window:])
                    close = close or (tail[-1] & self.rule_mask) == 0
                if close:
                    parents.append(self.node_id(group))
                    group = []
            if group:
                parents.append(self.node_id(group))
            level = parents
        return level[0]
