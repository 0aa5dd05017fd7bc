"""``kernels/streamed_matmul.py``: C[m,n] = A[m,k] · B[k,n], its operands
padded to blocks of 256 where a size is not a multiple."""

from bench.flops import matmul_cost, unpad


def cost(operands, c: dict, family) -> tuple[float, float]:
    sizes = family.kernel_sizes(c)
    (_, (m, k)), (_, (_, n)) = operands[:2]
    return matmul_cost(unpad(m, sizes), unpad(k, sizes), unpad(n, sizes))
