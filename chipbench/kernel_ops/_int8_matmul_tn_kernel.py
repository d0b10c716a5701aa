"""The int8 weight-gradient matmul, (M, K)^T @ (M, N): 2 * M * K * N
operations at the int8 peak."""
from chipbench.flops import matmul_ops

PRECISION = "int8"


def ops(call) -> float:
    return matmul_ops(call, 1)
