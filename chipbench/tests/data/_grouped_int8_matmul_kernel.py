"""A grouped int8 expert matmul, for the benchmark's tests only: tokens
sorted by expert, (M, K), against every expert's (G, K, N) weights, each
row through its own expert's: 2 * M * K * N operations at the int8
peak."""
PRECISION = "int8"


def ops(call) -> float:
    (_, (m, k)), (_, (_, _, n)) = call.operands[:2]
    return 2.0 * m * k * n
