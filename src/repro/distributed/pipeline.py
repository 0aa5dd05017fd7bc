"""GPipe-style pipeline parallelism over a mesh axis (fill–drain schedule).

Each device owns one stage's parameters; microbatches flow through the ring
with ``ppermute`` — one hyperstep per tick, exactly the paper's systolic
pattern (the Cannon rotation with layers instead of matrix blocks). Bubble
fraction is (S−1)/(M+S−1), the standard GPipe trade-off; the train loop can
use this for depth-sharding models whose layers exceed one pod's HBM.

This is the demonstration PP implementation (forward; a full 1F1B training
schedule composes this with per-stage VJPs). The production configs use
FSDP+TP which covers the assigned shapes; PP is provided as a first-class
scale-out primitive and is exercised by ``tests/test_distributed.py``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # pytree with leading stage axis (S, ...)
    microbatches: jax.Array,    # (M, B, d) — M microbatches
    *,
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Apply S pipeline stages to M microbatches; returns (M, B, d)."""
    s_stages = mesh.shape[axis]
    m = microbatches.shape[0]

    def body(params_local, xs):
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % s_stages) for i in range(s_stages)]
        p_stage = jax.tree_util.tree_map(lambda t: t[0], params_local)
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        buf = jax.lax.pcast(buf, (axis,), to="varying")
        outs = jax.lax.pcast(outs, (axis,), to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t during the fill phase
            inj = xs[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(stage == 0, jnp.where(t < m, inj, jnp.zeros_like(inj)),
                            buf)
            y = fn(p_stage, cur)
            # the last stage emits microbatch t−(S−1) during the drain phase
            idx = t - (s_stages - 1)
            emit = jnp.logical_and(stage == s_stages - 1, idx >= 0)
            upd = jax.lax.dynamic_update_slice(
                outs, y[None], (jnp.clip(idx, 0, m - 1),) + (0,) * y.ndim)
            outs = jnp.where(emit, upd, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, m + s_stages - 1, tick, (buf, outs))
        # results live on the last stage only; share them along the ring
        outs = jax.lax.psum(jnp.where(stage == s_stages - 1, outs, 0), axis)
        return outs

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params,
                               is_leaf=lambda x: hasattr(x, "shape")),
        P(),
    )
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P())(
        stage_params, microbatches)
