"""The training batch generator: rows of uniform token ids from the seed.

A traffic file with ``"generator": "batches"`` gives ``batch`` (rows per
step), ``seq_len`` and ``batches`` (how many distinct batches a run draws;
the feed wraps round after them). Batch ``i`` is the (``batch``,
``seq_len`` + 1) array of ``numpy.random.default_rng([seed, i])``, split
into inputs and next-token labels.
"""

from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab

    @property
    def batch(self) -> int:
        return self.mix["batch"]

    @property
    def seq_len(self) -> int:
        return self.mix["seq_len"]

    def rows(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.vocab, (self.batch, self.seq_len + 1)).astype(np.int32)

    def batch_at(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """(tokens, labels) of step ``i`` (0-based), wrapping round."""
        r = self.rows(i % self.mix["batches"])
        return r[:, :-1], r[:, 1:]

    def write(self, path) -> None:
        """Every distinct batch, in order, as the uint32 token file the
        program's ``DataConfig(source=...)`` reads."""
        with open(path, "wb") as f:
            for i in range(self.mix["batches"]):
                self.rows(i).astype(np.uint32).tofile(f)
