"""Int8 x int8 -> int32 tiled matmuls with fused per-row/per-col dequant.

The real-compute path the paper's fake quantization simulates: TPU v5e MXUs
run int8 at ~2x bf16 throughput (394 vs 197 TOPS).  A (bm, bk) x B (bk, bn)
tiles accumulate in an int32 VMEM scratch across the k grid dim; the
epilogue applies the paper's W-per-channel x A-per-token scale pair -- a
rank-1 rescale, which is exactly why that granularity pairing is the
hardware-efficient one (Section 3.2).

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

Tiling (:func:`pick_tiles`) is a function of the layout, the padded
shape and the dtypes.  A grid step costs a fixed ~0.35 us on a v5e, and a
128x128x128 tile holds ~11 ns of MXU work, so at that tile these kernels
run at 10-14 TOP/s.  A sweep on one v5e over every training shape of
gpt2-small and a 4-layer Yi-6B at 16,384 tokens (PERF.md, PR 14) found the
pattern the rule encodes (its picks ran the Yi shapes at 240-377 TOP/s):

  * the token tile is fixed: 1024 output rows for the forward and input-
    gradient kernels, 2048 contracted rows for the weight gradient;
  * the K tile is as large as fits: a whole K keeps the forward's x block
    resident across its row of output blocks, and the transposed kernels
    quantize each gradient block once per K tile;
  * the N tile is as large as then fits.

Tiles are multiples of 128 that divide the padded dimension (or the whole
dimension); the VMEM limit rises only where they need more than the
compiler's default.  Re-tiling changes no number: accumulation is exact
int32 and the prologue and epilogue are elementwise.

Scales equal to 0 (zero-padding of non-128-multiple shapes) are guarded to
1.0 in both the quant prologue and the dequant epilogue so ragged shapes
cannot emit NaN/Inf (0/0) from the padding lanes.

TARGET: TPU.  VALIDATED: interpret=True vs ref.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the TPU compiler's scoped-VMEM limit for a kernel that names none (v5e)
DEFAULT_SCOPED_VMEM = 16 << 20
#: the most a picked tiling may ask for (a v5e core has 128 MiB of VMEM);
#: the swept tiles that won sat at or below it
VMEM_BUDGET = 72 << 20
#: the token tile of each layout: output rows of the forward and input-
#: gradient kernels, contracted rows of the weight-gradient kernel
TOKEN_TILE = {"fwd": 1024, "nt": 1024, "tn": 2048}
#: the narrowest N tile the K tile must leave room for: at 128 the
#: weight-gradient kernel re-reads its int8 operand N/128 times
MIN_N_TILE = 256


@dataclasses.dataclass(frozen=True)
class Tiles:
    """One kernel's block sizes, in its own argument names, and the scoped
    VMEM they need (an upper estimate, checked by compiling for a v5e)."""
    bm: int
    bn: int
    bk: int
    vmem_bytes: int

    def compiler_params(self) -> pltpu.CompilerParams:
        """Raises the scoped-VMEM limit only where the tiles need more than
        the compiler's default."""
        limit = (self.vmem_bytes if self.vmem_bytes > DEFAULT_SCOPED_VMEM
                 else None)
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=limit)


def _sizes(d: int):
    """Block sizes that tile ``d``: the multiples of 128 that divide it, or
    ``d`` itself where it is no multiple of 128 (a whole-dimension block)."""
    if d % 128:
        return [d]
    return [c for c in range(128, d + 1, 128) if d % c == 0]


def vmem_bytes(layout: str, bm: int, bn: int, bk: int, out_bytes: int,
               g_bytes: int = 0) -> int:
    """Scoped VMEM of one kernel's tiles, in the kernels' argument names
    (``layout`` is ``"fwd"``, ``"nt"`` or ``"tn"``): every block double-
    buffered, a (rows, 1) f32 scale block padded to 128 lanes, the int32
    accumulator and the dot's int32 result, the quant prologue's f32 and
    int8 copies of the gradient block, and two more copies of the int8
    operand the MXU reads whole or transposed.  An upper bound on what the
    TPU compiler allocates at each of the 33 tilings measured (PERF.md);
    tests compile the picked tiles at every cell shape."""
    if layout == "fwd":
        blocks = bm * bk + bk * bn + 512 * bm + 32 * bn + out_bytes * bm * bn
        return 2 * blocks + 8 * bm * bn + 2 * bm * bk
    if layout == "nt":
        blocks = (g_bytes * bm * bn + bk * bn + 32 * bn + 512 * bm
                  + out_bytes * bm * bk)
        return 2 * blocks + 8 * bm * bk + 5 * bm * bn + 2 * bk * bn
    blocks = (bm * bk + g_bytes * bm * bn + 512 * bm + 32 * bn
              + out_bytes * bk * bn)
    return 2 * blocks + 8 * bk * bn + 5 * bm * bn + 2 * bm * bk


def _tiles(layout: str, bm: int, bn: int, bk: int, out_dtype,
           g_dtype) -> Tiles:
    g_bytes = 0 if g_dtype is None else jnp.dtype(g_dtype).itemsize
    return Tiles(bm, bn, bk, vmem_bytes(layout, bm, bn, bk,
                                        jnp.dtype(out_dtype).itemsize,
                                        g_bytes))


def pick_tiles(layout: str, m: int, k: int, n: int, out_dtype,
               g_dtype=None) -> Tiles:
    """Tiles for one kernel call on the padded (m, k, n) of its docstring
    (``layout`` ``"fwd"``, ``"nt"`` or ``"tn"``; ``g_dtype`` the fp
    gradient's dtype of the transposed kernels).

    ``bm`` is the token tile (:data:`TOKEN_TILE`, or all of a shorter m);
    ``bk`` the largest K tile that still leaves room, within
    :data:`VMEM_BUDGET`, for an N tile of :data:`MIN_N_TILE`; ``bn`` the
    largest N tile that then fits."""
    if layout not in TOKEN_TILE:
        raise ValueError(f"unknown int8 matmul layout {layout!r}")
    bm = max([c for c in _sizes(m) if c <= TOKEN_TILE[layout]]
             or [min(_sizes(m))])

    def fits(bn, bk):
        return _tiles(layout, bm, bn, bk, out_dtype,
                      g_dtype).vmem_bytes <= VMEM_BUDGET

    floor = [c for c in _sizes(n) if c >= min(MIN_N_TILE, n)][0]
    bk = max([c for c in _sizes(k) if fits(floor, c)] or [min(_sizes(k))])
    bn = max([c for c in _sizes(n) if fits(c, bk)] or [min(_sizes(n))])
    return _tiles(layout, bm, bn, bk, out_dtype, g_dtype)


def _resolve(layout: str, m: int, k: int, n: int, out_dtype, g_dtype,
             bm: Optional[int], bn: Optional[int],
             bk: Optional[int]) -> Tiles:
    """The picked tiles, each overridden where the caller names one (tests
    pin the 128^3 grid); a block larger than its dimension is cut to it."""
    t = pick_tiles(layout, m, k, n, out_dtype, g_dtype)
    bm, bn, bk = (min(b if b is not None else p, d) for b, p, d in
                  zip((bm, bn, bk), (t.bm, t.bn, t.bk), (m, n, k)))
    return _tiles(layout, bm, bn, bk, out_dtype, g_dtype)


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
                bm: Optional[int] = None, bn: Optional[int] = None,
                bk: Optional[int] = None,
                interpret: bool = False) -> jnp.ndarray:
    """x: int8 (M, K); w: int8 (K, N); row_scale fp32 (M, 1);
    col_scale fp32 (1, N) -> (M, N) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads); a block size
    left None is picked (:func:`pick_tiles`).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    t = _resolve("fwd", m, k, n, out_dtype, None, bm, bn, bk)
    bm, bn, bk = t.bm, t.bn, t.bk
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel, nk=grid[2]),
        name="_int8_matmul_kernel",
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
        compiler_params=t.compiler_params(),
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
                   bm: Optional[int] = None, bk: Optional[int] = None,
                   bn: Optional[int] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """dx = qdq_token(g * fold_scale) @ w^T with real int8 compute.

    g: fp (M, N) output gradient; w: int8 (K, N) stored forward payload;
    fold_scale fp32 (1, N) = the weight's per-channel dequant scales;
    q_scale fp32 (M, 1) = per-token quant scale of g*fold (absmax/127,
    computed by the ops.py wrapper) -> (M, K) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads); a block size
    left None is picked (:func:`pick_tiles`).  0-padded q_scale rows are
    guarded inside the kernel.
    """
    m, n = g.shape
    k, n2 = w.shape
    assert n == n2, (g.shape, w.shape)
    t = _resolve("nt", m, k, n, out_dtype, g.dtype, bm, bn, bk)
    bm, bn, bk = t.bm, t.bn, t.bk
    grid = (m // bm, k // bk, n // bn)
    return pl.pallas_call(
        functools.partial(_int8_matmul_nt_kernel, nk=grid[2]),
        name="_int8_matmul_nt_kernel",
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
        compiler_params=t.compiler_params(),
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
                   bk: Optional[int] = None, bn: Optional[int] = None,
                   bm: Optional[int] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """dW = x^T @ qdq_channel(g * fold_scale) with real int8 compute.

    x: int8 (M, K) stored forward payload; g: fp (M, N) output gradient;
    fold_scale fp32 (M, 1) = the activation's per-token dequant scales;
    q_scale fp32 (1, N) = per-channel quant scale of g*fold (absmax/127,
    computed by the ops.py wrapper) -> (K, N) out_dtype.

    Shapes must be multiples of the block sizes (ops.py pads); a block size
    left None is picked (:func:`pick_tiles`).  0-padded q_scale cols are
    guarded inside the kernel.
    """
    m, k = x.shape
    m2, n = g.shape
    assert m == m2, (x.shape, g.shape)
    t = _resolve("tn", m, k, n, out_dtype, g.dtype, bm, bn, bk)
    bm, bn, bk = t.bm, t.bn, t.bk
    grid = (k // bk, n // bn, m // bm)
    return pl.pallas_call(
        functools.partial(_int8_matmul_tn_kernel, nk=grid[2]),
        name="_int8_matmul_tn_kernel",
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
        compiler_params=t.compiler_params(),
        interpret=interpret,
    )(x, g, fold_scale, q_scale)
