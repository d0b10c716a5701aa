"""The fused 8-bit AdamW update: its few operations per element are
negligible beside its bytes, so only the bytes bound it."""
PRECISION = "bf16"


def ops(call) -> float:
    return 0.0
