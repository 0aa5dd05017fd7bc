"""The general request generator: arrivals, prompt lengths and output lengths
from a traffic file's parameters, prompt tokens from the run's seed.

A traffic file (``bench/traffic/<mix>.json``) with ``"generator":
"requests"`` gives

- ``arrivals``: ``{"process": "backlog", "queued_per_lane": q}`` (an offline
  queue kept at ``q`` × lanes requests) or ``{"process": "poisson",
  "rate_per_s": r}`` (open loop);
- ``prompt_len``: ``{"ladder": [...], "p": [...]}``, lengths from a fixed
  ladder, since the engine compiles one prefill per prompt length;
- ``output_len``: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
  "max": b}`` or ``{"dist": "uniform", "min": a, "max": b}``;
- ``pool``: how many (prompt, output, gap) triples are drawn, once, from
  ``sizes_seed``.

An open loop replays one arrival schedule: the pool's gaps and sizes in
the order they were drawn, so that every seed offers the same requests at
the same times, and the seed draws only their tokens; a tail such as the
95th percentile of time to first token then moves with the program and
not with the order of long prompts. A backlog takes the pool in an order
drawn from the seed. Prompt tokens are uniform over the vocabulary, drawn
per request from the seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray          # (P,) int32
    new_tokens: int
    due_s: float                # from the window's start; 0 for a backlog


class Requests:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        base = np.random.default_rng(mix.get("sizes_seed", 0))
        n = mix["pool"]
        pl = mix["prompt_len"]
        self._prompt = base.choice(np.asarray(pl["ladder"]), size=n, p=pl["p"])
        ol = mix["output_len"]
        if ol["dist"] == "lognormal":
            out = ol["median"] * np.exp(ol["sigma"] * base.standard_normal(n))
        elif ol["dist"] == "uniform":
            out = base.uniform(ol["min"], ol["max"] + 1, n)
        else:
            raise ValueError(f"unknown output length distribution {ol['dist']!r}")
        self._new = np.clip(np.floor(out), ol["min"], ol["max"]).astype(int)
        gaps = base.exponential(1.0, n)
        arr = mix["arrivals"]
        self.backlog = arr["process"] == "backlog"
        if self.backlog:
            self._perm = np.random.default_rng([seed, 1]).permutation(n)
            self._due = np.zeros(n)
        elif arr["process"] == "poisson":
            self._perm = np.arange(n)
            self._due = np.cumsum(gaps / arr["rate_per_s"])
        else:
            raise ValueError(f"unknown arrival process {arr['process']!r}")

    @property
    def ladder(self) -> list[int]:
        return list(self.mix["prompt_len"]["ladder"])

    def __len__(self) -> int:
        return len(self._perm)

    def due_s(self, i: int) -> float:
        return float(self._due[i % len(self._due)])

    def request(self, i: int) -> Request:
        j = self._perm[i % len(self._perm)]
        rng = np.random.default_rng([self.seed, 2, i])
        prompt = rng.integers(0, self.vocab, int(self._prompt[j])).astype(np.int32)
        return Request(i, prompt, int(self._new[j]), self.due_s(i))
