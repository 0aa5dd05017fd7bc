"""Seeded random weights in the benchmark's own layout, and the map into the
program's parameter tree.

The layout is flat: one array per name, the per-block ones stacked over the
layers. ``make`` draws all of them on the device in one jitted call, in the
type they are served in; the reference regenerates them from the same seed
and never reads what the program holds. Norm scales and biases are drawn
around 1 and 0, not set to them, so that a norm wired to the wrong tensor
shows.
"""

from __future__ import annotations

import functools

import jax

EMBED_STD = 0.02


def key(seed: int) -> jax.Array:
    """A key from any whole-number seed, 64-bit ones included."""
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def layout(c: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, scale) for the configuration file ``c``."""
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


@functools.lru_cache(maxsize=8)
def _maker(spec: tuple, dtype: str):
    def make(k):
        out = {}
        for i, (name, shape, kind, scale) in enumerate(spec):
            z = jax.random.normal(jax.random.fold_in(k, i), shape, dtype)
            out[name] = (1 + scale * z if kind == "one" else scale * z).astype(dtype)
        return out
    return jax.jit(make)


def make(c: dict, seed: int, dtype: str = "bfloat16") -> dict[str, jax.Array]:
    """Every weight of ``c``, drawn from ``seed`` in one jitted call."""
    spec = tuple((n, s, kind, sc) for n, (s, kind, sc) in layout(c).items())
    return _maker(spec, dtype)(key(seed))


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
        for n in [n for n in w if n not in ("embed", "head") and not n.startswith("final")]:
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
