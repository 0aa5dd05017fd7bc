"""The dense decoder: one pre-norm attention + MLP block repeated over the
layers (MiniCPM, StarCoder2).

The plain float32 reference is written from the published descriptions:
RMSNorm (MiniCPM, arXiv 2404.06395) or LayerNorm (StarCoder2, arXiv
2402.19173); rotary positions on the two halves of each head (GPT-NeoX /
Llama "rotate_half" convention); grouped-query attention, query head ``i``
reading key/value head ``i // (H / Hkv)``, causal and, where the
configuration gives one, within a sliding window; a SwiGLU (``silu``) or
tanh-GELU MLP; a tied or untied head. The MiniCPM scalings ``scale_emb``,
``scale_depth`` and ``dim_model_base`` apply where the configuration file
gives them. Nothing of the reference imports the program.

The weights' layout is flat: one array per name, the per-block ones
stacked over the layers. FLOPs count a multiply-add as two.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.reference import HIGHEST, _mm

EMBED_STD = 0.02
Q_CHUNK = 512       # query rows per attention block


def program_config(c: dict, options: dict | None = None):
    """The program's ``ModelConfig`` for the configuration file ``c``: the
    registry entry at ``c["num_hidden_layers"]`` layers, with the cell's
    program ``options`` (``scan_layers``, ``remat``), refused if any width
    differs from the file."""
    from repro.configs import get_config

    cfg = get_config(c["registry"], smoke=c.get("registry_smoke", False))
    opts = {"scan_layers": True, **(options or {})}
    cfg = dataclasses.replace(cfg, num_layers=c["num_hidden_layers"], **opts)
    act = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}[c["hidden_act"]]
    want = {"d_model": c["hidden_size"], "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"], "head_dim_": c["head_dim"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "padded_vocab": c["vocab_size"], "mlp_activation": act,
            "norm_type": c["norm_type"], "norm_eps": c["norm_eps"],
            "rope_theta": c["rope_theta"], "rope_type": "rope",
            "tie_embeddings": c["tie_word_embeddings"], "dtype": c["torch_dtype"]}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if bad or any(b.mixer != "attn" or b.mlp != "dense" for b in cfg.pattern):
        raise ValueError(f"{c['registry']}: the program's config differs from "
                         f"the configuration file: {bad}")
    return cfg


def smoke(c: dict) -> dict:
    """The widths of the program's smoke entry for ``c``'s registry name, as
    configuration keys, and the switch that makes ``program_config`` take
    that entry."""
    from repro.configs import get_config

    cfg = get_config(c["registry"], smoke=True)
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "registry_smoke": True}


# -- weights --------------------------------------------------------------------


def layout(c: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, scale) for the configuration file ``c``. Norm
    scales and biases are drawn around 1 and 0, not set to them, so that a
    norm wired to the wrong tensor shows."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    n = c["num_hidden_layers"]
    ln = c["norm_type"] == "layernorm"
    out: dict[str, tuple[tuple[int, ...], str, float]] = {
        "embed": ((v, d), "normal", EMBED_STD)}
    if not c["tie_word_embeddings"]:
        out["head"] = ((d, v), "normal", d ** -0.5)
    for norm in ("attn_norm", "mlp_norm"):
        out[f"{norm}.scale"] = ((n, d), "one", 0.1)
        if ln:
            out[f"{norm}.bias"] = ((n, d), "normal", 0.1)
    out["wq"] = ((n, d, h * hd), "normal", d ** -0.5)
    out["wk"] = ((n, d, hkv * hd), "normal", d ** -0.5)
    out["wv"] = ((n, d, hkv * hd), "normal", d ** -0.5)
    out["wo"] = ((n, h * hd, d), "normal", (h * hd) ** -0.5)
    if c["hidden_act"] == "silu":
        out["w_gate"] = ((n, d, f), "normal", d ** -0.5)
    out["w_up"] = ((n, d, f), "normal", d ** -0.5)
    out["w_down"] = ((n, f, d), "normal", f ** -0.5)
    out["final_norm.scale"] = ((d,), "one", 0.1)
    if ln:
        out["final_norm.bias"] = ((d,), "normal", 0.1)
    return out


def _per_layer(name: str) -> bool:
    return name not in ("embed", "head") and not name.startswith("final_norm")


def stacked(c: dict) -> tuple[str, ...]:
    """The weights stacked over the layers: every one but the embedding, the
    head and the final norm."""
    return tuple(k for k in layout(c) if _per_layer(k))


@jax.jit
def _split(x):
    return tuple(x[i] for i in range(x.shape[0]))


def to_program(c: dict, w: dict[str, jax.Array], scanned: bool = True) -> dict:
    """The program's parameter tree (``repro.models.model``) over the arrays
    of ``w``. With ``scanned`` the blocks are one period stacked over the
    layers, the same arrays with no copy; else one period per layer, sliced
    leaf by leaf out of ``w``, which gives its stacked arrays up."""
    if c.get("use_bias"):
        raise ValueError("the program's blocks have no biases")
    have = set(w)
    names = {"ln1": ("attn_norm", ("scale", "bias")), "ln2": ("mlp_norm", ("scale", "bias")),
             "mixer": ("", ("wq", "wk", "wv", "wo")), "mlp": ("", ("w_up", "w_down", "w_gate"))}

    def block(get):
        out = {}
        for part, (prefix, keys) in names.items():
            full = {k: f"{prefix}.{k}" if prefix else k for k in keys}
            out[part] = {k: get(n) for k, n in full.items() if n in have}
        return out

    embed = {"tokens": w["embed"]}
    if "head" in w:
        embed["head"] = w["head"]
    final = {k: w[f"final_norm.{k}"] for k in ("scale", "bias") if f"final_norm.{k}" in w}
    if scanned:
        stack = [block(lambda n: w[n])]
    else:
        layers = [{} for _ in range(c["num_hidden_layers"])]
        for n in [n for n in w if _per_layer(n)]:
            for layer, x in zip(layers, _split(w.pop(n))):
                layer[n] = x
        stack = [[block(lambda n, layer=layer: layer[n])] for layer in layers]
    return {"embed": embed, "stack": stack, "final_norm": final}


def from_program(tree: dict) -> dict[str, jax.Array]:
    """The inverse of :func:`to_program`: the flat layout over the program's
    arrays (parameters, or any tree of the same structure such as a moment)."""
    blk = tree["stack"][0]
    out = {"embed": tree["embed"]["tokens"]}
    if "head" in tree["embed"]:
        out["head"] = tree["embed"]["head"]
    for prefix, p in (("attn_norm", blk["ln1"]), ("mlp_norm", blk["ln2"]),
                      ("final_norm", tree["final_norm"])):
        for k, v in p.items():
            out[f"{prefix}.{k}"] = v
    out.update(blk["mixer"])
    out.update(blk["mlp"])
    return out


# -- the reference --------------------------------------------------------------


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


def hidden(c: dict, w: dict, tokens, fp8: bool = False):
    """Final-normed hidden states (B, T, d) in float32."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    x = x * c.get("scale_emb", 1.0)

    def body(x, lw):
        return jax.checkpoint(functools.partial(_block, c, fp8))(x, lw), None

    x, _ = jax.lax.scan(body, x, {k: v for k, v in w.items() if _per_layer(k)})
    return _norm(c, x, w["final_norm.scale"], w.get("final_norm.bias"))


def head(c: dict, w: dict, y, fp8: bool = False):
    """Logits of hidden states ``y`` (..., d)."""
    mat = w["embed"].T if c["tie_word_embeddings"] else w["head"]
    logits = _mm(y, mat, fp8)
    if c.get("dim_model_base"):
        logits = logits / (c["hidden_size"] / c["dim_model_base"])
    return logits


MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "hidden_act", "norm_type", "norm_eps", "rope_theta",
              "tie_word_embeddings", "sliding_window", "scale_emb",
              "scale_depth", "dim_model_base")


def _items(c: dict) -> tuple:
    """The configuration as a hashable static argument."""
    return tuple((k, c.get(k)) for k in MODEL_KEYS)


def _config(items: tuple) -> dict:
    return {k: v for k, v in items if v is not None}


def _forward(c: dict, w: dict):
    return (lambda tokens, fp8: hidden(c, w, tokens, fp8),
            lambda y, fp8: head(c, w, y, fp8))


@functools.partial(jax.jit, static_argnames=("items", "fp8"))
def _gaps(items, w, tokens, fp8):
    return R.gaps(*_forward(_config(items), w), tokens, fp8)


def token_gaps(c: dict, w: dict, tokens, fp8: bool = False):
    """For (B, T) tokens, at every position t: the reference's best logit
    minus its logit of token t+1, and (``fp8``) minus its logit of the token
    the control puts first there; both in standard deviations of the
    reference's logits at t, so that a gap reads alike at any width. Both
    (B, T), float32."""
    return _gaps(_items(c), w, jnp.asarray(tokens, jnp.int32), fp8)


def loss(c: dict, w: dict, tokens, labels, fp8: bool = False):
    """Mean next-token cross entropy over every position, in float32."""
    return R.loss(*_forward(c, w), tokens, labels, fp8)


@functools.partial(jax.jit, static_argnames=("items", "fp8"))
def _loss_and_grad(items, w, tokens, labels, fp8):
    """The loss and its float32 gradient at the weights ``w``, held in the
    type the configuration stores them in: the gradient is taken with
    respect to a float32 zero added to each widened weight."""
    c = _config(items)
    zero = {k: jnp.zeros(x.shape, jnp.float32) for k, x in w.items()}
    return jax.value_and_grad(lambda d: loss(
        c, {k: w[k].astype(jnp.float32) + d[k] for k in w}, tokens, labels, fp8))(zero)


def train(c: dict, make, batches, fp8: bool = False) -> dict:
    """AdamW with the configuration's ``optimizer`` from the weights
    ``make()`` returns, over ``batches`` (see ``bench.reference.train``)."""
    items = _items(c)
    return R.train(lambda w, tokens, labels: _loss_and_grad(items, w, tokens, labels, fp8),
                   c["optimizer"], stacked(c), make, batches)


# -- model FLOPs ----------------------------------------------------------------


def widths(c: dict) -> dict[str, int]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "hkv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "f": c["intermediate_size"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "gated": c["hidden_act"] == "silu"}


def layer_matmul_params(c: dict) -> int:
    """Weights of one block's matmuls (norm scales and biases left out)."""
    w = widths(c)
    attn = w["d"] * w["h"] * w["hd"] * 2 + 2 * w["d"] * w["hkv"] * w["hd"]
    mlp = (3 if w["gated"] else 2) * w["d"] * w["f"]
    return attn + mlp


def matmul_params(c: dict) -> int:
    """N of the ``2N``/``6N`` counts: every block's matmuls plus the head."""
    w = widths(c)
    return w["layers"] * layer_matmul_params(c) + w["d"] * w["v"]


def serve_token_flops(c: dict, context: int) -> float:
    """One token's forward: 2N plus attention over its ``context`` keys
    (QKᵀ and PV, 2·hd FLOPs per head and key each)."""
    w = widths(c)
    return 2.0 * matmul_params(c) + 4.0 * w["layers"] * w["h"] * w["hd"] * context


def train_token_flops(c: dict, seq: int) -> float:
    """6N + 12·L·H·hd·S per token (PaLM, Chowdhery et al. 2022, App. B)."""
    w = widths(c)
    return 6.0 * matmul_params(c) + 12.0 * w["layers"] * w["h"] * w["hd"] * seq


def prefill_flops(c: dict, prompt: int) -> float:
    """A prompt's forward: token ``i`` (1-based) attends over ``i`` keys."""
    w = widths(c)
    return (2.0 * matmul_params(c) * prompt
            + 4.0 * w["layers"] * w["h"] * w["hd"] * prompt * (prompt + 1) / 2)


def decode_flops(c: dict, prompt: int, tokens: int) -> float:
    """``tokens`` decode steps after a prompt: step ``k`` attends over
    ``prompt + k`` keys."""
    w = widths(c)
    ctx = tokens * prompt + tokens * (tokens + 1) / 2
    return 2.0 * matmul_params(c) * tokens + 4.0 * w["layers"] * w["h"] * w["hd"] * ctx


def kernel_sizes(c: dict) -> tuple[int, ...]:
    """The sizes a kernel's operand may have been padded from: the model
    width, the MLP's, the vocabulary, and the query and key/value widths."""
    w = widths(c)
    return (w["d"], w["f"], w["v"], w["h"] * w["hd"], w["hkv"] * w["hd"])
