"""Operations and bytes, from the shapes the harness sends: what every
kernel cost file (``bench/kernels``) and family (``bench/families``) shares.
FLOPs count a multiply-add as two.
"""

from __future__ import annotations


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


def unpad(x: int, sizes, block: int = 256) -> int:
    """The size among ``sizes`` that a kernel padded up to ``x`` (its blocks
    of ``block``), or ``x`` where none did."""
    for s in sizes:
        if s != x and s > block and -(-s // block) * block == x:
            return s
    return x
