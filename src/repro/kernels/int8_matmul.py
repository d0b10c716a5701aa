"""Int8 x int8 -> int32 tiled matmuls with fused per-row/per-col dequant.

The real-compute path the paper's fake quantization simulates: TPU v5e MXUs
run int8 at ~2x bf16 throughput (394 vs 197 TOPS).  Tiling is MXU-aligned
(128x128x128 by default): A (bm, bk) x B (bk, bn) accumulated in an int32
VMEM scratch across the k grid dim; the epilogue applies the paper's
W-per-channel x A-per-token scale pair -- a rank-1 rescale, which is exactly
why that granularity pairing is the hardware-efficient one (Section 3.2).

Three layouts cover the full training step (Fig. 1):

  * :func:`int8_matmul`    -- y  = Xq  @ Wq   (forward; both operands int8)
  * :func:`int8_matmul_nt` -- dx = Gq  @ Wq^T (backward input-grad)
  * :func:`int8_matmul_tn` -- dW = Xq^T @ Gq  (backward weight-grad)

The transposed kernels take the *fp* gradient plus a fold scale and quantize
it inside the grid (a fused quant prologue): the counterpart operand's scale
is element-folded into g before rounding, which moves every scale off the
contracted axis and keeps the dequant epilogue rank-1 -- see ops.py for the
scale algebra.  The stored int8 forward payloads (w for dx, x for dW) are
consumed directly; no padded int8 intermediate ever lands in HBM.

Scales equal to 0 (zero-padding of non-128-multiple shapes) are guarded to
1.0 in both the quant prologue and the dequant epilogue so ragged shapes
cannot emit NaN/Inf (0/0) from the padding lanes.

TARGET: TPU.  VALIDATED: interpret=True vs ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN, BK = 128, 128, 128


def scale_guard(scale: jnp.ndarray) -> jnp.ndarray:
    """0-scale padding lanes -> 1.0 (their payloads are 0, so the product is
    still 0; the guard only prevents 0/0 NaN in the quant prologue and keeps
    the epilogue multiply clean).  The canonical guard for every kernel that
    consumes zero-padded scale sidecars (matmul epilogues, decode attention,
    q8 prefill); oracles mirror it as ``ref._guard_ref`` and the reference
    KV path as ``models.attention._kv_guard``."""
    return jnp.where(scale == 0.0, 1.0, scale)


def _int8_matmul_kernel(x_ref, w_ref, rs_ref, cs_ref, o_ref, acc_ref, *,
                        nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * scale_guard(rs_ref[...])
                      * scale_guard(cs_ref[...])).astype(o_ref.dtype)


def int8_matmul(x: jnp.ndarray, w: jnp.ndarray, row_scale: jnp.ndarray,
                col_scale: jnp.ndarray, out_dtype=jnp.bfloat16,
                bm: int = BM, bn: int = BN, bk: int = BK,
                interpret: bool = False) -> jnp.ndarray:
    """x: int8 (M, K); w: int8 (K, N); row_scale fp32 (M, 1);
    col_scale fp32 (1, N) -> (M, N) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, row_scale, col_scale)


# ---------------------------------------------------------------------------
# Transposed layouts for the training backward (fused gradient-quant prologue)
# ---------------------------------------------------------------------------

def _int8_matmul_nt_kernel(g_ref, w_ref, fs_ref, qs_ref, o_ref, acc_ref, *,
                           nk: int):
    """dx block: quantize (g * fold) per-token in VMEM, dot against the int8
    weight payload with N contracted, dequant by the row scale."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = scale_guard(qs_ref[...].astype(jnp.float32))              # (bm, 1)
    h = g_ref[...].astype(jnp.float32) * fs_ref[...].astype(jnp.float32)
    hq = jnp.clip(jnp.round(h / qs), -128, 127).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        hq, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * qs).astype(o_ref.dtype)


def int8_matmul_nt(g: jnp.ndarray, w: jnp.ndarray, fold_scale: jnp.ndarray,
                   q_scale: jnp.ndarray, out_dtype=jnp.bfloat16,
                   bm: int = BM, bk: int = BK, bn: int = BN,
                   interpret: bool = False) -> jnp.ndarray:
    """dx = qdq_token(g * fold_scale) @ w^T with real int8 compute.

    g: fp (M, N) output gradient; w: int8 (K, N) stored forward payload;
    fold_scale fp32 (1, N) = the weight's per-channel dequant scales;
    q_scale fp32 (M, 1) = per-token quant scale of g*fold (absmax/127,
    computed by the ops.py wrapper) -> (M, K) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads); 0-padded
    q_scale rows are guarded inside the kernel.
    """
    m, n = g.shape
    k, n2 = w.shape
    assert n == n2, (g.shape, w.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    grid = (m // bm, k // bk, n // bn)
    return pl.pallas_call(
        functools.partial(_int8_matmul_nt_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(g, w, fold_scale, q_scale)


def _int8_matmul_tn_kernel(x_ref, g_ref, fs_ref, qs_ref, o_ref, acc_ref, *,
                           nk: int):
    """dW block: quantize (g * fold) per-channel in VMEM, dot against the
    int8 activation payload with M (tokens) contracted, dequant by the col
    scale."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = scale_guard(qs_ref[...].astype(jnp.float32))              # (1, bn)
    h = g_ref[...].astype(jnp.float32) * fs_ref[...].astype(jnp.float32)
    hq = jnp.clip(jnp.round(h / qs), -128, 127).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], hq, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * qs).astype(o_ref.dtype)


def int8_matmul_tn(x: jnp.ndarray, g: jnp.ndarray, fold_scale: jnp.ndarray,
                   q_scale: jnp.ndarray, out_dtype=jnp.float32,
                   bk: int = BK, bn: int = BN, bm: int = BM,
                   interpret: bool = False) -> jnp.ndarray:
    """dW = x^T @ qdq_channel(g * fold_scale) with real int8 compute.

    x: int8 (M, K) stored forward payload; g: fp (M, N) output gradient;
    fold_scale fp32 (M, 1) = the activation's per-token dequant scales;
    q_scale fp32 (1, N) = per-channel quant scale of g*fold (absmax/127,
    computed by the ops.py wrapper) -> (K, N) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads); 0-padded
    q_scale cols are guarded inside the kernel.
    """
    m, k = x.shape
    m2, n = g.shape
    assert m == m2, (x.shape, g.shape)
    bk, bn, bm = min(bk, k), min(bn, n), min(bm, m)
    grid = (k // bk, n // bn, m // bm)
    return pl.pallas_call(
        functools.partial(_int8_matmul_tn_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, g, fold_scale, q_scale)
