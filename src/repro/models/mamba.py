"""Mamba (selective SSM) mixer for the jamba hybrid architecture.

Sequence mixing is a BSPS stream over sequence chunks (DESIGN.md): the
recurrent state (d_inner × d_state) is the resident local-memory token, the
sequence is the stream. Three paths:

* TPU runtime   — the Pallas ``ssm_scan`` kernel;
* portable      — chunked scan: ``lax.scan`` over chunks, dense ops within a
                  chunk (dry-run lowering; ``unroll_time=True`` unrolls the
                  chunk loop for exact ``cost_analysis`` accounting);
* oracle        — per-step ``lax.scan`` (tests).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import ctx
from repro.kernels import ops, ref
from repro.models.layers import _dense_init

Params = dict[str, Any]


def init_mamba(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    d, di, ds, dtr = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_d_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    # A initialised to -(1..ds) per channel (S4D-real), stored as log.
    a_init = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    return {
        "w_in": _dense_init(ks[0], (d, 2 * di), dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_d_conv, di), jnp.float32)
                   * 0.1).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "w_x": _dense_init(ks[2], (di, dtr + 2 * ds), dtype),
        "w_dt": _dense_init(ks[3], (dtr, di), dtype),
        "dt_bias": jnp.full((di,), -4.6, dtype),  # softplus^-1(0.01)
        "a_log": jnp.log(a_init).astype(dtype),
        "d_skip": jnp.ones((di,), dtype),
        "w_out": _dense_init(ks[4], (di, d), dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv over (B, S, di) with kernel (K, di).

    If ``state`` (B, K-1, di) is given (decode), it is the left context.
    """
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    # sum_k w[k] * x[t - (K-1) + k]  — small K: unrolled adds, no conv primitive
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(k))
    return out + b


def chunked_selective_scan(
    x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
    a: jax.Array, d: jax.Array,
    *,
    chunk: int = 128,
    h0: jax.Array | None = None,
    unroll_time: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Portable chunked selective scan. Returns (y, final_state).

    Within a chunk the recurrence is expanded in closed form with cumulative
    decays (dense einsums — MXU work); across chunks the (B, di, ds) state is
    carried — one hyperstep per chunk. All math fp32.
    """
    bsz, seq, di = x.shape
    ds = a.shape[1]
    ck = min(chunk, seq)
    pad = (-seq) % ck
    if pad:
        x, dt = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt))
        b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (b, c))
    nc = x.shape[1] // ck

    xf = x.reshape(bsz, nc, ck, di).astype(jnp.float32)
    dtf = dt.reshape(bsz, nc, ck, di).astype(jnp.float32)
    bf = b.reshape(bsz, nc, ck, ds).astype(jnp.float32)
    cf = c.reshape(bsz, nc, ck, ds).astype(jnp.float32)
    af = a.astype(jnp.float32)
    xs = (xf, dtf, bf, cf)
    xs = jax.tree_util.tree_map(lambda t: t.swapaxes(0, 1), xs)  # lead axis nc

    def chunk_step(h, inp):
        xc, dtc, bc, cc = inp          # (B, ck, ·)
        # log-decay per (t, di, ds): dA[t] = dt[t] ⊙ A ; cumulative within chunk
        dA = dtc[..., None] * af       # (B, ck, di, ds)
        cum = jnp.cumsum(dA, axis=1)   # Σ_{r<=t} dA_r
        # contribution of the carried state: exp(cum_t) ⊙ h
        y_state = jnp.einsum("btis,bis,bts->bti", jnp.exp(cum), h, cc)
        # within-chunk: y_t += Σ_{s<=t} exp(cum_t - cum_s) dt_s B_s x_s · C_t
        # expand u_s = exp(-cum_s) ⊙ (dt_s x_s ⊗ B_s)   (stable: cum ≤ 0, A<0 ⇒
        # -cum_s grows; subtract per-chunk max for safety)
        m = jnp.max(-cum, axis=1, keepdims=True)        # (B, 1, di, ds)
        u = jnp.exp(-cum - (-m)) * (dtc * xc)[..., None] * bc[:, :, None, :]
        upre = jnp.cumsum(u, axis=1)                     # prefix sums over s
        y_intra = jnp.einsum("btis,bts->bti", jnp.exp(cum - m) * upre, cc)
        y = y_state + y_intra
        # state update: h' = exp(cum_T) h + Σ_s exp(cum_T - cum_s) dt_s x_s B_s
        last = cum[:, -1][:, None]                       # (B, 1, di, ds)
        h_new = jnp.exp(last[:, 0]) * h + (jnp.exp(last - m) * upre[:, -1:])[:, 0]
        return h_new, y

    if h0 is None:
        h0 = jnp.zeros((bsz, di, ds), jnp.float32)
    if unroll_time:
        h, ys = h0, []
        for i in range(nc):
            h, y = chunk_step(h, jax.tree_util.tree_map(lambda t, i=i: t[i], xs))
            ys.append(y)
        y = jnp.stack(ys, axis=0)
    else:
        h, y = jax.lax.scan(chunk_step, h0, xs)
    y = y.swapaxes(0, 1).reshape(bsz, nc * ck, di)
    y = y + x.astype(jnp.float32) * d.astype(jnp.float32)
    if pad:
        y = y[:, :seq]
    return y, h


def mamba_forward(
    cfg: ModelConfig, p: Params, x: jax.Array,
    *,
    impl: str = "auto",
    unroll_time: bool = False,
) -> jax.Array:
    """Full-sequence mamba mixer. x: (B, S, d) -> (B, S, d)."""
    b, s, _ = x.shape
    di, ds, dtr = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.dt_rank
    xz = jnp.einsum("bsd,de->bse", x, p["w_in"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = jax.nn.silu(_causal_conv(xin, p["conv_w"].astype(xin.dtype), p["conv_b"]))
    proj = jnp.einsum("bsi,ie->bse", xin, p["w_x"])
    dt_low, bmat, cmat = jnp.split(proj, [dtr, dtr + ds], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,ri->bsi", dt_low, p["w_dt"])
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    if impl == "auto":
        impl = ("kernel" if jax.default_backend() == "tpu" and not ctx.sharded()
                else "chunked")
    if impl == "kernel":
        y = ops.selective_scan(xin, dt.astype(xin.dtype), bmat, cmat, a,
                               p["d_skip"].astype(jnp.float32))
    elif impl == "oracle":
        y = ref.ssm_scan_ref(xin, dt, bmat, cmat, a, p["d_skip"])
    else:
        y, _ = chunked_selective_scan(
            xin, dt, bmat, cmat, a, p["d_skip"], unroll_time=unroll_time,
        )
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return jnp.einsum("bsi,id->bsd", y, p["w_out"])


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    di, ds = cfg.ssm_d_inner, cfg.ssm_d_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, di), dtype),
        "h": jnp.zeros((batch, di, ds), jnp.float32),
    }


def mamba_decode(
    cfg: ModelConfig, p: Params, x: jax.Array, cache: Params,
) -> tuple[jax.Array, Params]:
    """Single-token recurrent step. x: (B, 1, d)."""
    di, ds, dtr = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.dt_rank
    xz = jnp.einsum("bsd,de->bse", x, p["w_in"])
    xin, z = jnp.split(xz, 2, axis=-1)
    conv_state = jnp.concatenate([cache["conv"], xin.astype(cache["conv"].dtype)], axis=1)
    xin = jax.nn.silu(_causal_conv(xin, p["conv_w"].astype(xin.dtype), p["conv_b"],
                                   state=cache["conv"]))
    proj = jnp.einsum("bsi,ie->bse", xin, p["w_x"])
    dt_low, bmat, cmat = jnp.split(proj, [dtr, dtr + ds], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,ri->bsi", dt_low, p["w_dt"])
                         + p["dt_bias"].astype(jnp.float32))  # (B,1,di)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    dA = jnp.exp(dt[:, 0, :, None] * a)                       # (B, di, ds)
    h = dA * cache["h"] + (dt[:, 0] * xin[:, 0].astype(jnp.float32))[..., None] \
        * bmat[:, 0, None, :].astype(jnp.float32)
    y = jnp.einsum("bis,bs->bi", h, cmat[:, 0].astype(jnp.float32))
    y = y + p["d_skip"].astype(jnp.float32) * xin[:, 0].astype(jnp.float32)
    y = (y[:, None].astype(x.dtype)) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    return out, {"conv": conv_state[:, 1:], "h": h}
