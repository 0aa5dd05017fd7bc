"""Public jit'd entry points for the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels run compiled; on any other backend
they run under ``interpret=True`` — same kernel body, executed in Python,
used by every test against the ``ref.py`` oracles.

``matmul`` and ``attention`` are differentiable. ``matmul``'s backward runs
the same streamed kernel for dA = dC·Bᵀ and dB = Aᵀ·dC; ``attention``'s
backward is the FlashAttention-2 recomputation of
:func:`repro.models.flash.flash_attention_vjp`, so neither gradient
differentiates a ``pallas_call``.

Model code calls these wrappers, never ``pallas_call`` directly.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssm_scan import ssm_scan as _ssm
from repro.kernels.streamed_dot import streamed_dot as _dot
from repro.kernels.streamed_matmul import streamed_matmul as _matmul

__all__ = ["matmul", "dot", "attention", "selective_scan", "interpret_mode"]


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def matmul(a, b, *, block_m=256, block_n=256, block_k=256, out_dtype=None):
    return _matmul_vjp(a, b, (block_m, block_n, block_k), out_dtype)


def _streamed(a, b, blocks, out_dtype):
    bm, bn, bk = blocks
    return _matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                   out_dtype=out_dtype, interpret=interpret_mode())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_vjp(a, b, blocks, out_dtype):
    return _streamed(a, b, blocks, out_dtype)


def _matmul_fwd(a, b, blocks, out_dtype):
    return _streamed(a, b, blocks, out_dtype), (a, b)


def _matmul_bwd(blocks, out_dtype, res, dc):
    a, b = res
    da = _streamed(dc.astype(b.dtype), b.T, blocks, a.dtype)
    db = _streamed(a.T, dc.astype(a.dtype), blocks, b.dtype)
    return da, db


_matmul_vjp.defvjp(_matmul_fwd, _matmul_bwd)


def dot(v, u, *, token_size=8 * 1024):
    return _dot(v, u, token_size=token_size, interpret=interpret_mode())


def attention(q, k, v, *, causal=True, sm_scale=None, block_q=None, block_kv=None):
    """Tiles left as None are chosen from the shapes
    (:func:`repro.kernels.flash_attention.attention_tiles`)."""
    return _attention_vjp(q, k, v, causal, sm_scale, block_q, block_kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_vjp(q, k, v, causal, sm_scale, block_q, block_kv):
    return _flash(q, k, v, causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_kv=block_kv, interpret=interpret_mode())


def _attention_fwd(q, k, v, causal, sm_scale, block_q, block_kv):
    out = _attention_vjp(q, k, v, causal, sm_scale, block_q, block_kv)
    return out, (q, k, v)


def _attention_bwd(causal, sm_scale, block_q, block_kv, res, dout):
    # deferred: repro.models imports this module
    from repro.models.flash import flash_attention_vjp

    q, k, v = res
    d = q.shape[-1]
    # the jnp flash scales by d**-0.5; fold any other scale into q
    q_scale = 1.0 if sm_scale is None else sm_scale * d ** 0.5
    q_offset = k.shape[2] - q.shape[2]      # queries sit at the end, as above
    _, pullback = jax.vjp(
        lambda q_, k_, v_: flash_attention_vjp(
            q_ * q_scale, k_, v_, causal, q_offset, 1024, 1024),
        q, k, v)
    return pullback(dout)


_attention_vjp.defvjp(_attention_fwd, _attention_bwd)


def selective_scan(x, dt, b, c, a, d, *, chunk=128):
    return _ssm(x, dt, b, c, a, d, chunk=chunk, interpret=interpret_mode())
