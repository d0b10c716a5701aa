"""Fused fake-quantization Pallas kernel (VPU, one VMEM pass).

The paper's fake-quant op (Eq. 1) is elementwise-plus-row-reduction.  Executed
naively it costs three HBM round trips (absmax reduce, quantize, dequantize);
fused it is one read + one write.  Tiling: (block_rows, features) VMEM tiles,
row-aligned so per-token scales never cross tile boundaries; features padded
to the 128-lane register width by the ops.py wrapper.

TARGET: TPU (pl.pallas_call + BlockSpec).  VALIDATED: interpret=True on CPU
against ref.py (tests/test_kernels.py sweeps shapes/dtypes/bits).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256


def default_block_rows() -> int:
    """Tile row count used when the caller passes none.  ``REPRO_OPT_BLOCK``
    overrides it here and in kernels/opt_update.py -- one knob, read at call
    time, for block-size autotune sweeps across both VPU kernel families."""
    v = os.environ.get("REPRO_OPT_BLOCK", "")
    return int(v) if v else DEFAULT_BLOCK_ROWS


def _qdq_row_kernel(x_ref, o_ref, *, qmax: int):
    """Per-row (per-token) symmetric fake quantization on one tile."""
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def _qdq_scaled_kernel(x_ref, scale_ref, o_ref, *, qmax: int):
    """Fake quantization with an externally supplied broadcastable scale
    (per-tensor or per-channel: the reduction spans tiles, so the scale is
    computed outside and streamed in)."""
    x = x_ref[...].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def qdq_row(x: jnp.ndarray, bits: int = 8,
            block_rows: int = 0,
            interpret: bool = False) -> jnp.ndarray:
    """x: (rows, features) -> fake-quantized, per-row scales."""
    rows, feat = x.shape
    qmax = 2 ** (bits - 1) - 1
    block_rows = min(block_rows or default_block_rows(), rows)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_qdq_row_kernel, qmax=qmax),
        name="_qdq_row_kernel",
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, feat), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


def qdq_scaled(x: jnp.ndarray, scale: jnp.ndarray, bits: int = 8,
               block_rows: int = 0,
               interpret: bool = False) -> jnp.ndarray:
    """x: (rows, features); scale: (1, features) per-channel or (1, 1)
    per-tensor."""
    rows, feat = x.shape
    qmax = 2 ** (bits - 1) - 1
    block_rows = min(block_rows or default_block_rows(), rows)
    grid = (pl.cdiv(rows, block_rows),)
    scol = scale.shape[1]
    return pl.pallas_call(
        functools.partial(_qdq_scaled_kernel, qmax=qmax),
        name="_qdq_scaled_kernel",
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
                  pl.BlockSpec((1, scol), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, scale)
