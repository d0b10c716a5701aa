"""Pallas TPU kernels for the paper's hot spots (int8 matmuls, fused
quantize-dequantize, fused 8-bit AdamW, flash and int8-KV decode attention),
their jit'd wrappers (``ops.py``) and pure-jnp oracles (``ref.py``)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(flag: Optional[bool] = None) -> bool:
    """The one place a kernel wrapper picks its mode: compiled wherever the
    backend is a TPU, the Pallas interpreter elsewhere (CPU tests).  An
    explicit ``flag`` wins."""
    if flag is not None:
        return flag
    return jax.default_backend() != "tpu"
