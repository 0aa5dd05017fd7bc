"""Shared neural layers: norms, activations, positions, MLPs, embeddings.

Pure-JAX parameter pytrees (nested dicts) — no flax. Every ``init_*`` is
jittable so the whole model can be shape-evaluated with ``jax.eval_shape`` for
the dry-run (no host allocation). Weights are stored in the config dtype
(bf16 by default); all norms/softmax/accumulation run in fp32.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import ctx
from repro.kernels import ops

Params = dict[str, Any]


# -- norms -------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dtype) -> Params:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    elif cfg.norm_type == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        raise ValueError(cfg.norm_type)
    return out.astype(x.dtype)


# -- activations ---------------------------------------------------------------


def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "squared_relu":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


# -- rotary / positional embeddings -------------------------------------------


def rope_freqs(cfg: ModelConfig) -> jax.Array:
    """Inverse frequencies (head_dim/2,)."""
    hd = cfg.head_dim_
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate (B, S, H, D) by per-token positions.

    positions: (B, S) for plain RoPE, (3, B, S) for M-RoPE (temporal, h, w) —
    the Qwen2-VL multimodal rotary embedding, where the head-dim frequency
    bands are split into ``mrope_sections`` and each section takes its angle
    from the corresponding position axis. Text tokens carry identical values
    on all three axes, making M-RoPE coincide with RoPE for pure text.
    """
    inv = rope_freqs(cfg)  # (hd/2,)
    if cfg.rope_type == "mrope":
        if positions.ndim != 3:
            raise ValueError("mrope needs positions (3, B, S)")
        angles = positions[..., None].astype(jnp.float32) * inv  # (3, B, S, hd/2)
        sections = list(cfg.mrope_sections)
        if sum(sections) != inv.shape[0]:
            raise ValueError(
                f"mrope sections {sections} must sum to head_dim/2 = {inv.shape[0]}"
            )
        parts = []
        start = 0
        for axis, sec in enumerate(sections):
            parts.append(angles[axis, :, :, start : start + sec])
            start += sec
        theta = jnp.concatenate(parts, axis=-1)  # (B, S, hd/2)
    else:
        theta = positions[..., None].astype(jnp.float32) * inv  # (B, S, hd/2)

    cos = jnp.cos(theta)[:, :, None, :]  # (B, S, 1, hd/2)
    sin = jnp.sin(theta)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(d_model: int, positions: jax.Array) -> jax.Array:
    """(B, S) int positions -> (B, S, d_model) sinusoidal embedding (musicgen)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -- dense MLP -----------------------------------------------------------------


def _dense_init(key, shape, dtype, scale_axis: int = 0):
    fan_in = shape[scale_axis]
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def init_mlp(cfg: ModelConfig, key: jax.Array, dtype, d_ff: int | None = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    gated = cfg.mlp_activation in ("swiglu", "geglu")
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _dense_init(ks[0], (cfg.d_model, d_ff), dtype),
        "w_down": _dense_init(ks[1], (d_ff, cfg.d_model), dtype),
    }
    if gated:
        p["w_gate"] = _dense_init(ks[2], (cfg.d_model, d_ff), dtype)
    return p


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    up = ops_matmul(x, p["w_up"])
    if cfg.mlp_activation == "swiglu":
        h = jax.nn.silu(ops_matmul(x, p["w_gate"])) * up
    elif cfg.mlp_activation == "geglu":
        h = jax.nn.gelu(ops_matmul(x, p["w_gate"]), approximate=True) * up
    else:
        h = activation(cfg.mlp_activation, up)
    return ops_matmul(h, p["w_down"])


def ops_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Batched (..., d) @ (d, f). Routes through the BSPS Pallas kernel on one
    TPU; on other backends, and under a sharded mesh (GSPMD cannot partition a
    Pallas kernel), XLA's dot."""
    if jax.default_backend() == "tpu" and not ctx.sharded():
        lead = x.shape[:-1]
        out = ops.matmul(x.reshape(-1, x.shape[-1]), w, out_dtype=x.dtype)
        return out.reshape(*lead, w.shape[-1])
    return jnp.einsum("...d,df->...f", x, w)


# -- embeddings ----------------------------------------------------------------


#: Standard deviation of the token-embedding init (also the tied lm head).
EMBED_INIT_STD = 0.02


def init_embedding(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    ks = jax.random.split(key, 2)
    v = cfg.padded_vocab
    p = {"tokens": (jax.random.normal(ks[0], (v, cfg.d_model), jnp.float32)
                    * EMBED_INIT_STD).astype(dtype)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(ks[1], (cfg.d_model, v), dtype)
    return p


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tokens"], tokens, axis=0)


def lm_head(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, p["tokens"])
    return ops_matmul(x, p["head"])
