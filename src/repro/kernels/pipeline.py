"""Lower a chip-level StreamPlan to a Pallas TPU pipeline.

This is the only module in the repo that calls ``pl.pallas_call``. Every
kernel in ``kernels/`` declares its streaming structure as a
:class:`repro.core.plan.StreamPlan` (token shapes, index maps, scratch,
dimension semantics) and hands it here together with the hyperstep body; the
mapping is mechanical (DESIGN.md §3):

  =============================  ==========================================
  StreamPlan                     pl.pallas_call
  =============================  ==========================================
  grid (hypersteps)              grid
  TokenSpec(block, index_map)    pl.BlockSpec(block, index_map)
  TokenSpec.direction "down"     in_specs entry (HBM→VMEM prefetch)
  TokenSpec.direction "up"       out_specs entry (VMEM→HBM write-back)
  TokenSpec.rate 0 (resident)    constant index map (fetched once)
  output TokenSpec.full_shape    out_shape=jax.ShapeDtypeStruct(...)
  ScratchSpec                    pltpu.VMEM scratch ref
  dimension_semantics            pltpu.CompilerParams
  =============================  ==========================================

Mosaic drains a finished output block's VMEM→HBM copy while the next grid
step computes — the same single-DMA-lane overlap the host-level
``HyperstepRunner`` gives ``move_up`` write-backs, and the reason Eq. 1's up
side is charged on the hyperstep where the output block index changes.

Mosaic's automatic grid pipelining then implements the hyperstep schedule:
the next grid step's HBM→VMEM DMA is issued while the current step computes,
which is the paper's prefetch-overlapped hyperstep (Fig. 1), and the double
pipeline buffers it allocates are exactly the paper's "prefetching halves the
effective local memory" — which is why :meth:`StreamPlan.vmem_bytes` charges
streamed tokens twice and the planner budgets against it.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import StreamPlan

__all__ = ["lower", "lower_cache_clear", "lower_cache_info", "operand_vma"]

# (plan fingerprint, body key, interpret, compiler kwargs) -> lowered call.
# Kernels rebuild their StreamPlan (and re-partial their body) on every
# invocation; without this cache each jit trace re-runs the whole
# BlockSpec/pallas_call construction per call site.
_LOWER_CACHE: dict[tuple, Callable[..., Any]] = {}
_CACHE_STATS = {"hits": 0, "misses": 0, "uncacheable": 0}


def _body_key(body: Callable[..., None]) -> Any:
    """Hashable identity of a kernel body, or None when not cacheable.

    Kernel modules pass ``functools.partial(module_level_fn, **static_kwargs)``
    — a fresh partial object per call, so the key is the underlying function
    plus its bound arguments. Only closure-free functions are cacheable: a
    per-call closure would never hit (each call makes a new function object)
    yet every insert would pin the closure and its pallas_call forever, so
    closures — and unhashable bound arguments — return None and skip the
    cache entirely.
    """
    if isinstance(body, functools.partial):
        fn, args = body.func, body.args
        kwargs = tuple(sorted(body.keywords.items()))
    else:
        fn, args, kwargs = body, (), ()
    if getattr(fn, "__closure__", None):
        return None
    if "<locals>" in getattr(fn, "__qualname__", ""):
        return None     # defined per call: a fresh object every time
    try:
        hash((fn, args, kwargs))
    except TypeError:
        return None
    return (fn, args, kwargs)


def operand_vma(*operands: Any) -> frozenset[str]:
    """Mesh axes any operand varies over (empty outside ``jax.shard_map``)."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def lower_cache_clear() -> None:
    _LOWER_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0, uncacheable=0)


def lower_cache_info() -> dict[str, int]:
    return dict(_CACHE_STATS, size=len(_LOWER_CACHE))


def lower(
    plan: StreamPlan,
    body: Callable[..., None],
    *,
    interpret: bool = False,
    vma: frozenset[str] = frozenset(),
    **compiler_kwargs: Any,
) -> Callable[..., Any]:
    """Emit the ``pl.pallas_call`` for ``plan`` with hyperstep body ``body``.

    ``body`` receives one ref per plan input (in order), one per output, then
    one per scratch spec — the standard Pallas kernel signature. Returns the
    callable to apply to the full (external-memory) operands. Plans with a
    single output return a bare array, matching ``pallas_call``.

    ``vma`` names the mesh axes the outputs vary over when the kernel runs
    inside ``jax.shard_map`` (the union of the operands' ``jax.typeof(x).vma``,
    see :func:`operand_vma`); empty outside one.

    Lowered calls are cached keyed by ``(plan.fingerprint(), body, interpret,
    vma, compiler kwargs)`` — the fingerprint covers everything this function
    reads from the plan — so re-invoking a kernel with the same shapes stops
    re-constructing (and re-tracing) the pallas_call.
    """
    try:
        key = (plan.fingerprint(), _body_key(body), interpret, vma,
               tuple(sorted(compiler_kwargs.items())))
        if key[1] is None:
            raise TypeError
        hash(key)
    except TypeError:
        key = None
        _CACHE_STATS["uncacheable"] += 1
    if key is not None:
        hit = _LOWER_CACHE.get(key)
        if hit is not None:
            _CACHE_STATS["hits"] += 1
            return hit
        _CACHE_STATS["misses"] += 1
    in_specs = [pl.BlockSpec(t.block_shape, t.index_map) for t in plan.inputs]
    out_specs = [pl.BlockSpec(t.block_shape, t.index_map) for t in plan.outputs]
    out_shapes = [jax.ShapeDtypeStruct(t.full_shape, t.dtype, vma=vma)
                  for t in plan.outputs]
    if len(plan.outputs) == 1:
        out_specs, out_shapes = out_specs[0], out_shapes[0]
    call = pl.pallas_call(
        body,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM(s.shape, s.dtype) for s in plan.scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan.dimension_semantics or None,
            **compiler_kwargs,
        ),
        interpret=interpret,
    )
    if key is not None:
        _LOWER_CACHE[key] = call
    return call
