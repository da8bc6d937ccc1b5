"""Artifact source of ``dockerhub-nginx``: a flattened container image and
its version history, made from the seed.

Version 0 is zipf-weighted words of a fixed dictionary (the one
``benchmarks/corpus.py`` draws for nginx) with a share of random spans.
Each later version applies the same edit schedule to the one before it:
``edits_per_version`` edits, one in each equal slot of the image at a
seeded offset; their sizes are a fixed ladder from ``edit_min_bytes`` to
``edit_max_bytes``; ``insert_delete_pairs`` of them insert and as many
delete the same sizes, the rest overwrite in place, in a seeded order.  So
every seed commits the same amount of new bytes per version, and every
version has the same length.  Only the newest version is held here.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


class Version:
    def __init__(self, tag: str, artifacts: List[Tuple[str, bytes]]):
        self.tag = tag
        self.artifacts = artifacts
        self.image = list(artifacts)


class ImageHistory:
    def __init__(self, artifact: dict, seed: int, spans):
        self.a = artifact
        self.spans = spans
        self.rng = np.random.default_rng([seed, 1])
        # one dictionary for every seed: the words' hashes set how densely
        # the text cuts, so a dictionary drawn per seed would change the
        # work from seed to seed (two to three times the chunks)
        letters = np.random.default_rng(artifact["dictionary_seed"]).integers(
            97, 123, size=(artifact["dictionary_words"],
                           artifact["word_letters"]), dtype=np.uint8)
        self.words = np.concatenate(
            [letters, np.full((letters.shape[0], 1), 32, np.uint8)], axis=1)
        self.image: np.ndarray = self._content(artifact["image_bytes"])
        self.k = 0
        n = artifact["edits_per_version"]
        pairs = artifact["insert_delete_pairs"]
        lo, hi = artifact["edit_min_bytes"], artifact["edit_max_bytes"]
        moves = np.linspace(lo, hi, pairs).astype(np.int64)
        self.sizes = np.concatenate(
            [np.linspace(lo, hi, n - 2 * pairs).astype(np.int64), moves, moves])
        self.kinds = np.array([0] * (n - 2 * pairs) + [1] * pairs + [2] * pairs)
        if hi >= artifact["image_bytes"] // n:
            raise ValueError(f"edits of up to {hi} B do not fit in "
                             f"{n} slots of a {artifact['image_bytes']} B image")

    def _content(self, n: int) -> np.ndarray:
        a = self.a
        width = self.words.shape[1]
        idx = self.rng.zipf(a["zipf_a"], n // width + 1) % self.words.shape[0]
        out = self.words[idx].ravel()[:n].copy()
        span = a["binary_span"]
        rows = out[:n // span * span].reshape(-1, span)
        if rows.shape[0]:
            pick = self.rng.choice(rows.shape[0],
                                   int(rows.shape[0] * a["binary_share"]),
                                   replace=False)
            rows[pick] = self.rng.integers(0, 256, (pick.size, span), np.uint8)
        return out

    def _edit(self) -> None:
        v = self.image
        n = self.sizes.size
        slot = v.size // n
        order = self.rng.permutation(n)
        pieces, cur = [], 0
        for i in range(n):
            size, kind = int(self.sizes[order[i]]), int(self.kinds[order[i]])
            pos = i * slot + int(self.rng.integers(0, max(1, slot - size)))
            pieces.append(v[cur:pos])
            if kind == 0:                                 # overwrite
                pieces.append(self._content(size))
                cur = pos + size
            elif kind == 1:                               # insert
                pieces.append(self._content(size))
                cur = pos
            else:                                         # delete
                cur = pos + size
        pieces.append(v[cur:])
        self.image = np.concatenate(pieces)

    def next(self) -> Version:
        """The next version; the window's clock stops while it is made."""
        with self.spans.paused("bench.generate"):
            if self.k:
                self._edit()
            tag = f"v{self.k}"
            self.k += 1
            return Version(tag, [(self.a["lineage"], self.image.tobytes())])

    def reference(self, version: Version) -> List[Tuple[str, bytes]]:
        """The bytes the registry must hold: the image itself."""
        return version.image

    def free(self) -> None:
        pass


def make(cfg: dict, seed: int, spans) -> ImageHistory:
    return ImageHistory(cfg["artifact"], seed, spans)
