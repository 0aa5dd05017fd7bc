"""Seeded random weights in a family's own layout (``bench/families``).

The layout is flat: one array per name. ``make`` draws all of them on the
device in one jitted call, in the type they are served in; the reference
regenerates them from the same seed and never reads what the program holds.
A weight of kind ``one`` is drawn around 1 (a norm scale), any other around
0.
"""

from __future__ import annotations

import functools

import jax


def key(seed: int) -> jax.Array:
    """A key from any whole-number seed, 64-bit ones included."""
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=8)
def _maker(spec: tuple, dtype: str):
    def make(k):
        out = {}
        for i, (name, shape, kind, scale) in enumerate(spec):
            z = jax.random.normal(jax.random.fold_in(k, i), shape, dtype)
            out[name] = (1 + scale * z if kind == "one" else scale * z).astype(dtype)
        return out
    return jax.jit(make)


def make(layout: dict, seed: int, dtype: str = "bfloat16") -> dict[str, jax.Array]:
    """Every weight of ``layout`` (a family's name -> (shape, kind, scale)),
    drawn from ``seed`` in one jitted call."""
    spec = tuple((n, s, kind, sc) for n, (s, kind, sc) in layout.items())
    return _maker(spec, dtype)(key(seed))
