"""``kernels/flash_attention.py``: causal attention of q (B, H, S, hd) over
k and v (B, Hkv, S, hd)."""

from bench.flops import flash_cost


def cost(operands, c: dict, family) -> tuple[float, float]:
    (_, (b, hq, s, hd)), (_, (_, hkv, _, _)) = operands[:2]
    return flash_cost(b, hq, hkv, s, hd)
