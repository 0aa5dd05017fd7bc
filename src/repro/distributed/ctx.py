"""Mesh context for in-model sharding constraints.

Model code is mesh-agnostic; the launcher (dryrun/train/serve) registers the
active mesh axis names + sizes here and model code calls :func:`constrain`
with *logical* specs — axis names not on the current mesh, or axes that do not
divide the dimension, are dropped; with no mesh registered the call is a
no-op (single-device tests/examples).
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Mapping

import jax
from jax.sharding import PartitionSpec as P

_AXES: dict[str, int] = {}

DP = ("pod", "host", "data")   # logical data-parallel axes
TP = "model"           # tensor/sequence-parallel axis


def set_mesh(axes: Mapping[str, int]) -> None:
    global _AXES
    _AXES = dict(axes)


@contextlib.contextmanager
def mesh_axes(axes: Mapping[str, int]) -> Iterator[None]:
    global _AXES
    prev = _AXES
    _AXES = dict(axes)
    try:
        yield
    finally:
        _AXES = prev


def _filter(entry, dim: int):
    """Keep only registered axes whose product divides ``dim``."""
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    kept: list[str] = []
    prod = 1
    for a in names:
        if a in _AXES and dim % (prod * _AXES[a]) == 0:
            kept.append(a)
            prod *= _AXES[a]
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint with logical axis names; no-op without a mesh."""
    if not _AXES:
        return x
    clean = tuple(_filter(s, d) for s, d in zip(spec, x.shape))
    if all(s is None for s in clean):
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))


def sharded() -> bool:
    """True while a mesh of more than one device is registered.

    Such programs are partitioned by GSPMD, which cannot split a Pallas
    kernel (a Mosaic custom call): model code then takes its XLA path.
    """
    return any(n > 1 for n in _AXES.values())


def dp_size() -> int:
    """Product of registered data-parallel axis sizes (1 without a mesh)."""
    n = 1
    for a in DP:
        n *= _AXES.get(a, 1)
    return n
