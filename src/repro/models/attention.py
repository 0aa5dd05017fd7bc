"""GQA attention: streamed (blockwise/flash) prefill + KV-cache decode.

Three interchangeable inner implementations, all BSPS streamings of the KV
sequence (DESIGN.md: attention *is* a pseudo-streaming algorithm — resident Q
token, KV stream, online-softmax state):

* ``kernel``    — the Pallas flash kernel (one-chip TPU runtime path; its
                  backward is the custom-VJP flash below);
* ``blockwise`` — pure-JAX online softmax, KV stream chunks via ``lax.scan``
                  (portable lowering used by the multi-pod dry-run; linear
                  memory in sequence length);
* ``dense``     — materialised S² oracle (tests, short sequences).

``unroll_time=True`` unrolls the KV-chunk loop into real HLO ops so
``cost_analysis`` counts every chunk — used by the roofline lowerings
(EXPERIMENTS.md §Roofline methodology).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

import os

from repro.configs.base import ModelConfig
from repro.distributed import ctx
from repro.kernels import ops, ref
from repro.models.flash import flash_attention_vjp
from repro.models.layers import _dense_init, apply_rope

Params = dict[str, Any]

_NEG = -1e30


def init_attention(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, h * hd), dtype),
        "wk": _dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": _dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": _dense_init(ks[3], (h * hd, d), dtype),
    }


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, hkv, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, hkv, hd)
    if cfg.rope_type in ("rope", "mrope"):
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions)
    return q, k, v


def blockwise_attention(
    q: jax.Array,        # (B, Hq, Sq, D)
    k: jax.Array,        # (B, Hkv, Skv, D)
    v: jax.Array,        # (B, Hkv, Skv, D)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    kv_valid_len: jax.Array | None = None,
    block_kv: int = 512,
    unroll_time: bool = False,
) -> jax.Array:
    """Online-softmax attention, KV consumed as a stream of chunks.

    GQA is handled by folding query heads as (Hkv, group) — K/V tokens are
    reused across the group (the paper's token-reuse/seek pattern) without
    materialising a repeat. ``kv_valid_len`` masks a partially-filled cache.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    bk = min(block_kv, skv)
    pad = (-skv) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if kv_valid_len is None:
            kv_valid_len = jnp.asarray(skv)
    n_blocks = k.shape[2] // bk

    qg = q.reshape(b, hkv, group, sq, d).astype(jnp.float32)
    q_pos = jnp.arange(sq) + q_offset  # (Sq,) global positions of queries

    kb = k.reshape(b, hkv, n_blocks, bk, d).swapaxes(0, 2)  # (nB, hkv?, ...) ->
    vb = v.reshape(b, hkv, n_blocks, bk, d).swapaxes(0, 2)

    def step(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, start = inp  # (B?, ...) after swap: (hkv? ...)
        # k_blk: (Hkv, B, bk, D) due to swapaxes(0,2) -> reorder
        k_blk = k_blk.swapaxes(0, 1).astype(jnp.float32)  # (B, Hkv, bk, D)
        v_blk = v_blk.swapaxes(0, 1).astype(jnp.float32)
        s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_blk) * scale
        k_pos = start + jnp.arange(bk)
        mask = jnp.ones((sq, bk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        mask = mask[None]                      # (1|B, sq, bk)
        if kv_valid_len is not None:
            valid = jnp.asarray(kv_valid_len)
            if valid.ndim == 1:                # per-lane valid lengths
                mask = mask & (k_pos[None, None, :] < valid[:, None, None])
            else:
                mask = mask & (k_pos < valid)[None, None, :]
        s_ = jnp.where(mask[:, None, None], s_, _NEG)
        m_cur = jnp.max(s_, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p_ = jnp.exp(s_ - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p_, axis=-1)
        acc_new = alpha[..., None] * acc + jnp.einsum("bhgqk,bhkd->bhgqd", p_, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    starts = jnp.arange(n_blocks) * bk

    if unroll_time:
        carry = (m0, l0, a0)
        for i in range(n_blocks):
            carry, _ = step(carry, (kb[i], vb[i], starts[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def dense_cache_attention(
    q: jax.Array,              # (B, Hq, Sq, D) — Sq is tiny (decode)
    k: jax.Array,              # (B, Hkv, Skv, D) — the cache
    v: jax.Array,
    *,
    kv_valid_len: jax.Array,
    q_offset: int | jax.Array = 0,
) -> jax.Array:
    """Decode attention reading the cache exactly once (no chunk stream).

    For Sq = 1 the online-softmax stream buys nothing: the score matrix is
    (B, H, 1, Skv) — tiny — while the baseline's chunked scan materialises
    transposed cache views per chunk (measured 64× cache traffic per layer in
    the dry-run; EXPERIMENTS.md §Perf cell C). One masked dense pass is the
    memory-optimal schedule and shards cleanly over batch/head/sequence.

    ``kv_valid_len`` may be a scalar (every lane at the same position — the
    single-request serve path) or a ``(B,)`` vector (a packed continuous batch
    of requests at mixed positions — the serve engine's padding mask).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * d ** -0.5
    k_pos = jnp.arange(skv)
    kv_valid_len = jnp.asarray(kv_valid_len)
    if kv_valid_len.ndim == 1:                 # per-lane valid lengths
        mask = k_pos[None, None, :] < kv_valid_len[:, None, None]  # (B, 1, Skv)
    else:
        mask = (k_pos[None, :] < kv_valid_len)[None]               # (1, ?, Skv)
    if sq > 1:
        causal = (jnp.arange(sq) + q_offset)[:, None] >= k_pos[None, :]
        mask = mask & causal[None]
    mask = jnp.broadcast_to(mask, (b, sq, skv))
    s = jnp.where(mask[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def attention_core(
    cfg: ModelConfig,
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    kv_valid_len: jax.Array | None = None,
    impl: str = "auto",
    unroll_time: bool = False,
) -> jax.Array:
    """(B, S, H, D)-layout wrapper choosing the inner implementation."""
    qt, kt, vt = (t.swapaxes(1, 2) for t in (q, k, v))  # -> (B, H, S, D)
    if impl == "auto":
        if jax.default_backend() == "tpu" and not ctx.sharded():
            impl = "kernel"
        else:
            # portable path: flash (custom-vjp) is the shipped default after
            # §Perf validation; REPRO_ATTN_IMPL=blockwise selects the
            # paper-faithful baseline for comparison
            impl = os.environ.get("REPRO_ATTN_IMPL", "flash")
    if impl == "flash" and kv_valid_len is None:
        out = flash_attention_vjp(qt, kt, vt, causal, int(q_offset)
                                  if not hasattr(q_offset, 'shape') else 0,
                                  1024, 1024, unroll_time)
    elif impl == "kernel" and kv_valid_len is None:
        out = ops.attention(qt, kt, vt, causal=causal)
    elif impl == "dense":
        out = ref.attention_ref(qt, kt, vt, causal=causal)
        if kv_valid_len is not None:
            raise ValueError("dense impl does not support cache masking")
    else:
        out = blockwise_attention(
            qt, kt, vt, causal=causal, q_offset=q_offset,
            kv_valid_len=kv_valid_len, unroll_time=unroll_time,
        )
    return out.swapaxes(1, 2)


def attention_forward(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    *,
    impl: str = "auto",
    unroll_time: bool = False,
) -> jax.Array:
    """Full-sequence causal attention (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = attention_core(cfg, q, k, v, causal=True, impl=impl, unroll_time=unroll_time)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p["wo"])


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), dtype),
        "v": jnp.zeros((batch, max_len, hkv, hd), dtype),
    }


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,            # (B, S, d) — S = 1 (decode) or a prefill chunk
    cache: Params,
    cache_len: jax.Array,    # scalar int32, or (B,) int32 for packed lanes
    *,
    impl: str = "auto",
    unroll_time: bool = False,
) -> tuple[jax.Array, Params]:
    """One decode step: append k/v at ``cache_len``, attend over the cache.

    Two generalisations of the classic single-token step share this path:

    * **chunked prefill** — ``x`` carries S > 1 prompt tokens at once (scalar
      ``cache_len``); the chunk attends causally within itself plus over the
      cache, and all S k/v rows land in one ``dynamic_update_slice``.
    * **packed lanes** — ``cache_len`` is a ``(B,)`` vector: each lane of a
      continuous batch sits at its own position (mixed prompt lengths), with
      per-lane RoPE positions, per-lane cache writes, and per-lane validity
      masks. Vector lengths require S = 1 (the serve engine's decode shape).
    """
    b, s, _ = x.shape
    cache_len = jnp.asarray(cache_len)
    per_lane = cache_len.ndim == 1
    if per_lane and s != 1:
        raise ValueError("per-lane cache_len requires single-token steps")
    if per_lane:
        positions = cache_len.astype(jnp.int32)[:, None]          # (B, 1)
    else:
        positions = jnp.broadcast_to(
            (cache_len + jnp.arange(s)).astype(jnp.int32)[None], (b, s))
    if cfg.rope_type == "mrope":
        positions = jnp.broadcast_to(positions, (3, b, s))
    q, k, v = _project_qkv(cfg, p, x, positions)
    if per_lane:
        ck = jax.vmap(
            lambda c, upd, ln: jax.lax.dynamic_update_slice(
                c, upd.astype(c.dtype), (ln, 0, 0))
        )(cache["k"], k, cache_len)
        cv = jax.vmap(
            lambda c, upd, ln: jax.lax.dynamic_update_slice(
                c, upd.astype(c.dtype), (ln, 0, 0))
        )(cache["v"], v, cache_len)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_len, 0, 0))
    if impl == "auto":
        impl = os.environ.get("REPRO_DECODE_ATTN", "dense")
    if impl == "dense":
        out = dense_cache_attention(
            q.swapaxes(1, 2), ck.swapaxes(1, 2), cv.swapaxes(1, 2),
            kv_valid_len=cache_len + s,
            q_offset=cache_len if not per_lane else 0).swapaxes(1, 2)
    else:
        out = attention_core(
            cfg, q, ck, cv, causal=s > 1, kv_valid_len=cache_len + s,
            q_offset=cache_len if not per_lane else 0,
            impl=impl, unroll_time=unroll_time,
        )
    y = jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p["wo"])
    return y, {"k": ck, "v": cv}
