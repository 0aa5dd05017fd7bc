"""Production mesh construction (assignment: MULTI-POD DRY-RUN §1).

A function, not a module-level constant, so importing this module never
touches jax device state. Single pod = 16×16 = 256 chips (v5e pod slice);
multi-pod = 2 pods = 512 chips with the leading ``pod`` axis carrying
cross-pod data parallelism (DCN-grade link in reality — which is why the
gradient-compression hooks target that axis).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates shardings.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every gather
    and every jit outside ``jax.set_mesh`` must name its output sharding; the
    sharded code here is written for propagation.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int | None = None) -> Mesh:
    """A small mesh over whatever devices exist (tests / CPU examples).

    ``model`` must divide the device count exactly: silently flooring
    ``n // model`` would drop devices from the mesh, and ``model > n`` would
    surface as an opaque shape error from ``make_mesh``.
    """
    n = len(jax.devices())
    model = model or 1
    if model > n:
        raise ValueError(
            f"model={model} exceeds the {n} available device(s); "
            "set XLA_FLAGS=--xla_force_host_platform_device_count to fake more")
    if n % model != 0:
        raise ValueError(
            f"model={model} does not divide the {n} available device(s); "
            f"a ({n // model}, {model}) mesh would drop {n % model} of them")
    return auto_mesh((n // model, model), ("data", "model"))


def make_host_core_mesh(hosts: int, *, model: int | None = None) -> Mesh:
    """The third-level ``(host, data, model)`` mesh (DESIGN.md §8).

    ``hosts`` leading groups, each a ``(data, model)`` core grid over the
    remaining devices — the mesh the host-level pricing composes over: the
    ``host`` axis joins the DP axes (``shardspec.dp_axes``), so FSDP
    all-gathers and gradient reductions crossing it are exactly the traffic
    ``host_h_relation`` charges with ``(g_host, l_host)``. CI fakes the
    devices with ``--xla_force_host_platform_device_count=8`` for a 2×4
    host×core mesh, the HomebrewNLP trick from the related repos.

    Validation mirrors :func:`make_host_mesh`: every factor must divide so
    no device is silently dropped.
    """
    n = len(jax.devices())
    if hosts <= 0:
        raise ValueError(f"hosts must be positive, got {hosts}")
    if hosts > n:
        raise ValueError(
            f"hosts={hosts} exceeds the {n} available device(s); "
            "set XLA_FLAGS=--xla_force_host_platform_device_count to fake more")
    if n % hosts != 0:
        raise ValueError(
            f"hosts={hosts} does not divide the {n} available device(s); "
            f"would drop {n % hosts} of them")
    per_host = n // hosts
    model = model or per_host
    if per_host % model != 0:
        raise ValueError(
            f"model={model} does not divide the {per_host} device(s) per host; "
            f"would drop {per_host % model} of them")
    return auto_mesh((hosts, per_host // model, model),
                     ("host", "data", "model"))
