"""The plain float32 reference decoder, written from the published descriptions.

One pre-norm decoder block: RMSNorm (MiniCPM, arXiv 2404.06395) or
LayerNorm (StarCoder2, arXiv 2402.19173); rotary positions on the two halves
of each head (GPT-NeoX / Llama "rotate_half" convention); grouped-query
attention, query head ``i`` reading key/value head ``i // (H / Hkv)``, causal
and, where the configuration gives one, within a sliding window; a SwiGLU
(``silu``) or tanh-GELU MLP; a tied or untied head. The MiniCPM scalings
``scale_emb``, ``scale_depth`` and ``dim_model_base`` apply where the
configuration file gives them.

Every operation runs in float32 and every matmul at ``HIGHEST`` precision;
the weights are the bfloat16 values the configuration serves, widened.
``fp8=True`` is the control: each linear layer's operands rounded to
``float8_e4m3fn`` with one scale per tensor. Nothing here imports the
program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512       # query rows per attention block
LOGIT_CHUNK = 512   # positions per block of the head and the loss


def _q8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8_e4m3fn under one scale; its gradient passes
    straight through, as fp8 training's does."""
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...d,df->...f", x, w, precision=HIGHEST)


def _norm(c: dict, x, scale, bias=None):
    if c["norm_type"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["norm_eps"])
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + c["norm_eps"])
    y = y * scale.astype(jnp.float32)
    return y if bias is None else y + bias.astype(jnp.float32)


def _rope(c: dict, x, pos):
    """x: (B, T, H, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / c["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(c: dict, q, k, v):
    """Causal (and windowed) softmax attention; q: (B, T, H, hd), k/v:
    (B, T, Hkv, hd). Computed in blocks of query rows."""
    b, t, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    window = c.get("sliding_window")
    chunk = min(Q_CHUNK, t)
    n = -(-t // chunk)
    qp = jnp.pad(q, ((0, 0), (0, n * chunk - t), (0, 0), (0, 0)))
    kpos = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qc = jax.lax.dynamic_slice_in_dim(qp, i * chunk, chunk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k, precision=HIGHEST) / math.sqrt(hd)
        qpos = i * chunk + jnp.arange(chunk)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(n))                 # (n, B, C, H, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, n * chunk, h, hd)
    return out[:, :t]


def _block(c: dict, fp8: bool, x, lw):
    b, t, _ = x.shape
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    pos = jnp.arange(t)
    res = (c["scale_depth"] / math.sqrt(c["num_hidden_layers"])
           if c.get("scale_depth") else 1.0)
    y = _norm(c, x, lw["attn_norm.scale"], lw.get("attn_norm.bias"))
    q = _rope(c, _mm(y, lw["wq"], fp8).reshape(b, t, h, hd), pos)
    k = _rope(c, _mm(y, lw["wk"], fp8).reshape(b, t, hkv, hd), pos)
    v = _mm(y, lw["wv"], fp8).reshape(b, t, hkv, hd)
    a = _attention(c, q, k, v).reshape(b, t, h * hd)
    x = x + res * _mm(a, lw["wo"], fp8)
    y = _norm(c, x, lw["mlp_norm.scale"], lw.get("mlp_norm.bias"))
    if c["hidden_act"] == "silu":
        m = jax.nn.silu(_mm(y, lw["w_gate"], fp8)) * _mm(y, lw["w_up"], fp8)
    else:
        m = jax.nn.gelu(_mm(y, lw["w_up"], fp8), approximate=True)
    return x + res * _mm(m, lw["w_down"], fp8)


def _layer_weights(w: dict) -> dict:
    return {k: v for k, v in w.items()
            if k not in ("embed", "head") and not k.startswith("final_norm")}


def hidden(c: dict, w: dict, tokens, fp8: bool = False):
    """Final-normed hidden states (B, T, d) in float32."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x = x * c.get("scale_emb", 1.0)

    def body(x, lw):
        return jax.checkpoint(functools.partial(_block, c, fp8))(x, lw), None

    x, _ = jax.lax.scan(body, x, _layer_weights(w))
    return _norm(c, x, w["final_norm.scale"], w.get("final_norm.bias"))


def head(c: dict, w: dict, y, fp8: bool = False):
    """Logits of hidden states ``y`` (..., d)."""
    mat = w["embed"].T if c["tie_word_embeddings"] else w["head"]
    logits = _mm(y, mat, fp8)
    if c.get("dim_model_base"):
        logits = logits / (c["hidden_size"] / c["dim_model_base"])
    return logits


def _chunks(y, chunk):
    """(B, T, ...) -> (n, B, chunk, ...), zero-padded."""
    b, t = y.shape[:2]
    n = -(-t // chunk)
    y = jnp.pad(y, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (y.ndim - 2))
    return jnp.moveaxis(y.reshape(b, n, chunk, *y.shape[2:]), 1, 0)


def _unchunk(a, t):
    """(n, B, chunk) -> (B, t)."""
    return jnp.moveaxis(a, 0, 1).reshape(a.shape[1], -1)[:, :t]


MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "hidden_act", "norm_type", "norm_eps", "rope_theta",
              "tie_word_embeddings", "sliding_window", "scale_emb",
              "scale_depth", "dim_model_base")


def _items(c: dict) -> tuple:
    """The configuration as a hashable static argument."""
    out = tuple((k, c.get(k)) for k in MODEL_KEYS)
    if "optimizer" in c:
        out += (("optimizer", tuple(sorted(c["optimizer"].items()))),)
    return out


def _config(items: tuple) -> dict:
    c = {k: v for k, v in items if v is not None}
    if "optimizer" in c:
        c["optimizer"] = dict(c["optimizer"])
    return c


@functools.partial(jax.jit, static_argnames=("items", "fp8"))
def _gaps(items, w, tokens, fp8):
    c = _config(items)
    t = tokens.shape[1]
    chunk = min(LOGIT_CHUNK, t)
    ys = _chunks(hidden(c, w, tokens), chunk)
    y8s = _chunks(hidden(c, w, tokens, fp8=True), chunk) if fp8 else ys
    nxt = _chunks(jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1), chunk)

    def block(args):
        yc, y8c, nc = args
        lg = head(c, w, yc)
        best, sd = jnp.max(lg, -1), jnp.std(lg, -1)
        served = jnp.take_along_axis(lg, nc[..., None], -1)[..., 0]
        if not fp8:
            return (best - served) / sd, (best - served) / sd
        pick = jnp.argmax(head(c, w, y8c, fp8=True), -1)
        return ((best - served) / sd,
                (best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]) / sd)

    g, g8 = jax.lax.map(block, (ys, y8s, nxt))
    return _unchunk(g, t), _unchunk(g8, t)


def token_gaps(c: dict, w: dict, tokens, fp8: bool = False):
    """For (B, T) tokens, at every position t: the reference's best logit
    minus its logit of token t+1, and (``fp8``) minus its logit of the token
    the control puts first there; both in standard deviations of the
    reference's logits at t, so that a gap reads alike at any width. Both
    (B, T), float32."""
    return _gaps(_items(c), w, jnp.asarray(tokens, jnp.int32), fp8)


# -- training -------------------------------------------------------------------


def loss(c: dict, w: dict, tokens, labels, fp8: bool = False):
    """Mean next-token cross entropy over every position, in float32."""
    t = tokens.shape[1]
    chunk = min(LOGIT_CHUNK, t)
    ys = _chunks(hidden(c, w, tokens, fp8), chunk)
    ls = _chunks(labels, chunk)

    @jax.checkpoint
    def block(args):
        i, yc, lc = args
        lg = head(c, w, yc, fp8)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(i * chunk + jnp.arange(chunk) < t, nll, 0.0))

    return jnp.sum(jax.lax.map(block, (jnp.arange(ys.shape[0]), ys, ls))) / labels.size


def leaf_norms(tree: dict) -> dict:
    """The L2 norm of each leaf in float32; of each layer, for the leaves
    stacked over the layers."""
    stacked = _layer_weights(tree)
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=tuple(range(1, x.ndim)) if k in stacked else None))
            for k, x in tree.items()}


@functools.partial(jax.jit, static_argnames=("items", "fp8"))
def _loss_and_grad(items, w, tokens, labels, fp8):
    """The loss and its float32 gradient at the weights ``w``, held in the
    type the configuration stores them in: the gradient is taken with
    respect to a float32 zero added to each widened weight."""
    c = _config(items)
    zero = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    return jax.value_and_grad(lambda d: loss(
        c, {k: w[k].astype(jnp.float32) + d[k] for k in w}, tokens, labels, fp8))(zero)


@functools.partial(jax.jit, static_argnames=("items",), donate_argnums=(1, 2, 3, 4))
def _adamw(items, w, m, v, g, step):
    """One AdamW step (Loshchilov & Hutter) after clipping the global norm,
    with the configuration's ``optimizer`` hyperparameters, computed in
    float32 and stored back in the weights' own type. Returns the new state
    and the per-leaf norms of the clipped gradient."""
    o = _config(items)["optimizer"]
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    g = {k: x * jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9)) for k, x in g.items()}
    bc1 = 1 - o["b1"] ** step
    bc2 = 1 - o["b2"] ** step
    m = {k: o["b1"] * m[k] + (1 - o["b1"]) * g[k] for k in w}
    v = {k: o["b2"] * v[k] + (1 - o["b2"]) * g[k] * g[k] for k in w}

    def update(k):
        p = w[k].astype(jnp.float32)
        u = (m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + o["eps"]) + o["weight_decay"] * p
        return (p - o["lr"] * u).astype(w[k].dtype)

    return {k: update(k) for k in w}, m, v, leaf_norms(g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _change_norms(w, w0):
    return leaf_norms({k: w[k].astype(jnp.float32) - w0[k].astype(jnp.float32) for k in w})


def train(c: dict, make, batches, fp8: bool = False) -> dict:
    """AdamW from the weights ``make()`` returns, over ``batches`` of
    (tokens, labels), one per step: each step's loss, the first clipped
    gradient's per-leaf norms, and the final weights. The weights stay in
    the type the configuration stores them in; the moments are float32."""
    items = _items(c)
    w = make()
    m = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    v = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    losses, g1 = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        lval, g = _loss_and_grad(items, w, jnp.asarray(tokens, jnp.int32),
                                 jnp.asarray(labels, jnp.int32), fp8)
        losses.append(float(lval))
        w, m, v, gn = _adamw(items, w, m, v, g, jnp.float32(step))
        del g
        g1 = jax.device_get(gn) if g1 is None else g1
    return {"losses": losses, "grad_norms": g1, "w": w}


def change_norms(w: dict, w0: dict) -> dict:
    """Per-leaf norms of ``w - w0`` (see :func:`leaf_norms`); ``w`` is consumed."""
    return jax.device_get(_change_norms(w, w0))
