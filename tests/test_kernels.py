"""Per-kernel allclose vs the pure-jnp oracles, across shape/dtype sweeps.

Every Pallas kernel runs under interpret=True on CPU (same kernel body the
TPU compiles) and must match ref.py within dtype tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.streamed_dot import streamed_dot
from repro.kernels.streamed_matmul import streamed_matmul, vmem_bytes

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------- matmul ----


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),       # exact single block
    (256, 512, 128),       # multi-block K stream
    (300, 200, 130),       # ragged (padding path)
    (64, 1024, 64),        # long stream, small tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streamed_matmul_matches_ref(rng, m, k, n, dtype):
    a, b = _rand(rng, (m, k), dtype), _rand(rng, (k, n), dtype)
    out = streamed_matmul(a, b, block_m=128, block_n=128, block_k=128,
                          interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8)


def test_matmul_block_shape_independence(rng):
    """BSPS cost depends on block size; the result must not (Eq. 2 semantics)."""
    a, b = _rand(rng, (256, 384), jnp.float32), _rand(rng, (384, 256), jnp.float32)
    outs = [
        np.asarray(streamed_matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                                   interpret=True))
        for bm, bn, bk in [(128, 128, 128), (64, 256, 96), (256, 64, 384)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


def test_vmem_budget_accounting():
    # double-buffered tokens + fp32 acc, paper's halved-effective-L rule
    assert vmem_bytes(128, 128, 128, itemsize=2) == 2 * (2 * 128 * 128 * 2) + 128 * 128 * 4


# ------------------------------------------------------------------- dot ----


@pytest.mark.parametrize("n,c", [(1024, 256), (5000, 512), (100, 128), (8192, 8192)])
def test_streamed_dot(rng, n, c):
    v, u = _rand(rng, (n,), jnp.float32), _rand(rng, (n,), jnp.float32)
    out = streamed_dot(v, u, token_size=c, interpret=True)
    np.testing.assert_allclose(float(out), float(ref.dot_ref(v, u)),
                               rtol=1e-4, atol=1e-3)


# -------------------------------------------------------------- attention ----


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("sq,skv", [(128, 128), (96, 96), (1, 128)])
def test_flash_attention_gqa(rng, hq, hkv, sq, skv):
    b, d = 2, 32
    q = _rand(rng, (b, hq, sq, d), jnp.float32)
    k = _rand(rng, (b, hkv, skv, d), jnp.float32)
    v = _rand(rng, (b, hkv, skv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_is_causal(rng):
    """Perturbing future keys must not change earlier outputs (token skipping)."""
    b, h, s, d = 1, 2, 64, 16
    q = _rand(rng, (b, h, s, d), jnp.float32)
    k = _rand(rng, (b, h, s, d), jnp.float32)
    v = _rand(rng, (b, h, s, d), jnp.float32)
    out1 = flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
    k2 = k.at[:, :, 40:].set(99.0)
    v2 = v.at[:, :, 40:].set(-99.0)
    out2 = flash_attention(q, k2, v2, block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :40]),
                               np.asarray(out2[:, :, :40]), rtol=1e-5, atol=1e-5)


def test_flash_attention_bf16(rng):
    b, h, s, d = 1, 2, 64, 32
    q, k, v = (_rand(rng, (b, h, s, d), jnp.bfloat16) for _ in range(3))
    out = flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=0.1, atol=0.1)


def _kernel_at_128(q, k, v):
    """The forward as it ran before tiles were chosen from the shapes: one
    head and 128 × 128 scores a hyperstep, q, k, p and v all in float32 on
    the MXU, the causal mask on every computed tile."""
    from jax.experimental import pallas as pl

    from repro.kernels import pipeline
    from repro.kernels.flash_attention import attention_plan

    b, h, s, d = q.shape

    def body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ki <= qi)
        def _body():
            q_pos = qi * 128 + jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
            k_pos = ki * 128 + jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
            s_ = jnp.dot(q_ref[0, 0].astype(jnp.float32),
                         k_ref[0, 0].astype(jnp.float32).T) * d ** -0.5
            s_ = jnp.where(q_pos >= k_pos, s_, -1e30)
            m_prev = m_ref[0]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
            p = jnp.exp(s_ - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[0] = alpha * acc_ref[0] + jnp.dot(
                p, v_ref[0, 0].astype(jnp.float32))
            m_ref[0] = m_new

        @pl.when(ki == s // 128 - 1)
        def _store():
            o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    plan = attention_plan(b, h, h, s, s, d, block_q=128, block_kv=128,
                          dtype=q.dtype)
    return pipeline.lower(plan, body, interpret=True)(q, k, v)


def _bf16_ulp(x, floor):
    """One bf16 unit in the last place at |x|, taken at no less than
    ``floor``: where a row's sum cancels to near 0, the float32 accumulator's
    own rounding (a few 2^-24 of the terms) is more than an ulp of the
    result, whatever the tiles."""
    mag = np.maximum(np.abs(np.asarray(x, np.float32)), floor)
    return np.exp2(np.floor(np.log2(mag)) - 7)


def test_flash_attention_pretrain_tiles_match_128(rng):
    """At the pretrain family's shape the kernel picks its own (large) tiles,
    bf16 operands on the MXU: the result is the 128 × 128 float32 kernel's to
    within one bf16 ulp per element, and no further from the float32 oracle
    than it, plus one ulp."""
    b, h, s, d = 1, 2, 2048, 64
    q, k, v = (_rand(rng, (b, h, s, d), jnp.bfloat16) for _ in range(3))
    out = np.asarray(flash_attention(q, k, v, interpret=True), np.float32)
    before = np.asarray(_kernel_at_128(q, k, v), np.float32)
    want = np.asarray(ref.attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)),
                                        causal=True))
    floor = 2.0 ** -16 * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    gap = np.abs(out - before)
    assert np.all(gap <= _bf16_ulp(np.maximum(np.abs(out), np.abs(before)),
                                   floor)), gap.max()
    err, err_before = np.abs(out - want).max(), np.abs(before - want).max()
    assert err <= err_before + _bf16_ulp(np.abs(want).max(), floor)


@pytest.mark.parametrize("hq,hkv,sq,skv,d", [
    (8, 2, 256, 256, 128),     # a head block covers two kv heads
    (8, 2, 1024, 1024, 128),   # 512 tiles, one kv head per head block
    (16, 2, 1024, 1024, 128),  # head block narrower than the group of 8
    (8, 2, 1, 600, 128),       # decode row at the end of a ragged cache
])
def test_flash_attention_gqa_default_tiles(rng, hq, hkv, sq, skv, d):
    q = _rand(rng, (1, hq, sq, d), jnp.bfloat16)
    k = _rand(rng, (1, hkv, skv, d), jnp.bfloat16)
    v = _rand(rng, (1, hkv, skv, d), jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    want = ref.attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)),
                             causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------------- ssm ----


@pytest.mark.parametrize("seq,chunk", [(64, 16), (100, 32), (128, 128)])
def test_ssm_scan(rng, seq, chunk):
    b, di, ds = 2, 8, 4
    x = _rand(rng, (b, seq, di), jnp.float32)
    dt = jnp.abs(_rand(rng, (b, seq, di), jnp.float32)) * 0.2
    bb = _rand(rng, (b, seq, ds), jnp.float32)
    c = _rand(rng, (b, seq, ds), jnp.float32)
    a = -jnp.abs(_rand(rng, (di, ds), jnp.float32)) - 0.1
    d = _rand(rng, (di,), jnp.float32)
    out = ssm_scan(x, dt, bb, c, a, d, chunk=chunk, interpret=True)
    want = ref.ssm_scan_ref(x, dt, bb, c, a, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ssm_state_isolation_across_batch(rng):
    """Grid resets state at chunk 0 per batch row — rows must not leak."""
    b, seq, di, ds = 3, 32, 4, 2
    x = _rand(rng, (b, seq, di), jnp.float32)
    dt = jnp.abs(_rand(rng, (b, seq, di), jnp.float32)) * 0.1
    bb = _rand(rng, (b, seq, ds), jnp.float32)
    c = _rand(rng, (b, seq, ds), jnp.float32)
    a = -jnp.ones((di, ds), jnp.float32)
    d = jnp.zeros((di,), jnp.float32)
    full = ssm_scan(x, dt, bb, c, a, d, chunk=8, interpret=True)
    row = ssm_scan(x[1:2], dt[1:2], bb[1:2], c[1:2], a, d, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(full[1:2]), np.asarray(row),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- flash custom-vjp ----


@pytest.mark.parametrize("sq,skv,q_off", [(64, 64, 0), (100, 100, 0), (32, 96, 64)])
def test_flash_vjp_matches_ref_fwd_and_grads(rng, sq, skv, q_off):
    from repro.models.flash import flash_attention_vjp
    b, hq, hkv, d = 2, 4, 2, 16
    q = _rand(rng, (b, hq, sq, d), jnp.float32)
    k = _rand(rng, (b, hkv, skv, d), jnp.float32)
    v = _rand(rng, (b, hkv, skv, d), jnp.float32)
    out = flash_attention_vjp(q, k, v, True, q_off, 32, 32)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    def f_flash(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention_vjp(q, k, v, True, q_off, 32, 32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.tanh(ref.attention_ref(q, k, v, causal=True)))

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_flash_vjp_unroll_matches_scan(rng):
    from repro.models.flash import flash_attention_vjp
    b, h, s, d = 1, 2, 96, 16
    q = _rand(rng, (b, h, s, d), jnp.float32)
    k = _rand(rng, (b, h, s, d), jnp.float32)
    v = _rand(rng, (b, h, s, d), jnp.float32)
    o1 = flash_attention_vjp(q, k, v, True, 0, 32, 32, False)
    o2 = flash_attention_vjp(q, k, v, True, 0, 32, 32, True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6, atol=1e-6)


def test_dense_cache_attention_matches_blockwise(rng):
    from repro.models.attention import blockwise_attention, dense_cache_attention
    b, hq, hkv, skv, d = 2, 4, 2, 64, 16
    q = _rand(rng, (b, hq, 1, d), jnp.float32)
    k = _rand(rng, (b, hkv, skv, d), jnp.float32)
    v = _rand(rng, (b, hkv, skv, d), jnp.float32)
    valid = jnp.asarray(37)
    o1 = dense_cache_attention(q, k, v, kv_valid_len=valid)
    o2 = blockwise_attention(q, k, v, causal=False, kv_valid_len=valid,
                             block_kv=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------ gradients through ops.* ----


@pytest.mark.parametrize("m,k,n", [(24, 40, 56), (130, 64, 200)])
def test_ops_matmul_grad_matches_ref(rng, m, k, n):
    """ops.matmul's custom VJP (the streamed kernel for dA and dB) against
    autodiff of the oracle."""
    from repro.kernels import ops
    a, b = _rand(rng, (m, k), jnp.float32), _rand(rng, (k, n), jnp.float32)

    def loss(mm):
        return lambda a, b: jnp.sum(jnp.tanh(mm(a, b)))

    got = jax.grad(loss(lambda a, b: ops.matmul(a, b, block_m=64, block_n=64,
                                                block_k=32)),
                   argnums=(0, 1))(a, b)
    want = jax.grad(loss(ref.matmul_ref), argnums=(0, 1))(a, b)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sq,skv,sm_scale", [(64, 64, None), (32, 96, None),
                                             (64, 64, 0.3)])
def test_ops_attention_grad_matches_ref(rng, sq, skv, sm_scale):
    """ops.attention: Pallas forward, flash recomputation backward — the
    gradient must equal autodiff of the dense oracle (GQA, causal, decode
    offset and a non-default scale)."""
    from repro.kernels import ops
    b, hq, hkv, d = 1, 4, 2, 16
    q = _rand(rng, (b, hq, sq, d), jnp.float32)
    k = _rand(rng, (b, hkv, skv, d), jnp.float32)
    v = _rand(rng, (b, hkv, skv, d), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.tanh(attn(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: ops.attention(
        q, k, v, sm_scale=sm_scale, block_q=32, block_kv=32)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=True, sm_scale=sm_scale)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4)

