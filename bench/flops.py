"""Operations and bytes, from the shapes the harness sends.

Everything here reads the configuration file's published keys (see
``bench/configs/``), never the program. FLOPs count a multiply-add as two.
"""

from __future__ import annotations


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


def matmul_cost(m: int, k: int, n: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, least bytes) of C[m,n] = A[m,k] · B[k,n]: each operand once."""
    return 2.0 * m * k * n, float(itemsize * (m * k + k * n + m * n))


def flash_cost(b: int, hq: int, hkv: int, s: int, hd: int,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, least bytes) of causal self-attention over ``s`` positions:
    query i meets keys 0..i, 4·hd FLOPs per pair; q, k, v and o once."""
    flops = 4.0 * b * hq * hd * s * (s + 1) / 2
    nbytes = float(itemsize * b * s * hd * (2 * hq + 2 * hkv))
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and the bound that sets it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


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


def unpad(x: int, sizes, block: int = 256) -> int:
    """The size among ``sizes`` that a kernel padded up to ``x`` (its blocks
    of ``block``), or ``x`` where none did."""
    for s in sizes:
        if s != x and s > block and -(-s // block) * block == x:
            return s
    return x


def kernel_cost(kernel: str, operands, c: dict) -> tuple[float, float]:
    """(FLOPs, least bytes) of one traced kernel call, from its operands'
    shapes with the kernel's zero padding taken off."""
    w = widths(c)
    sizes = (w["d"], w["f"], w["v"], w["h"] * w["hd"], w["hkv"] * w["hd"])
    if kernel == "streamed_matmul":
        (_, (m, k)), (_, (_, n)) = operands[:2]
        return matmul_cost(unpad(m, sizes), unpad(k, sizes), unpad(n, sizes))
    if kernel == "flash_attention":
        (_, (b, hq, s, hd)), (_, (_, hkv, _, _)) = operands[:2]
        return flash_cost(b, hq, hkv, s, hd)
    raise ValueError(f"no cost model for kernel {kernel!r}")
