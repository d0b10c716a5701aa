"""Fused 8-bit AdamW update kernel (VPU, one VMEM pass) -- paper Section 4.4
deployed the way Dettmers-style 8-bit optimizers actually ship.

The reference loop in ``optim/adamw.py`` decodes each int8 moment into a full
fp32 materialization, runs the update as unfused XLA elementwise ops, and
re-encodes: roughly six HBM round trips over moment-sized buffers per step,
which erases most of the 4x storage win at the bandwidth level.  This kernel
executes the whole step per (block_rows, block_size) tile in one VMEM pass:

  stream in   grad tile + fp32 param tile + int8 m1/m2 payloads + fp32
              scale/zero sidecars + an SMEM scalar vector
              (clip, lr, b1, b2, eps, wd, c1, c2)
  in-register dequantize m1/m2 (square for sqrt-domain m2), apply the
              bias-corrected AdamW update with the global-norm clip factor
              folded into g, blockwise absmax (or min/max for asymmetric
              codecs) and re-quantize both moments
  write out   updated param + new int8 payloads + new scales/zeros + a
              per-tile partial sum of ||lr * update||^2 (the update_norm stat)

one read and one write per buffer instead of ~6.  The row layout is exactly
``core.qadam``'s blockwise codec: each moment row is one quantization block of
``spec.block_size`` elements with its own (scale, zero) pair, so payloads are
consumed and produced in their stored form -- the optimizer counterpart of the
int8 residuals of kernels/int8_matmul.py.

Arithmetic follows ``optim/adamw.py``'s decode -> update -> encode loop op
for op (same reduction axis, same ``maximum(.., 1e-12)`` guards), so the two
paths agree to float rounding; tests/test_opt_update.py pins the parity.
Fully-padded bucket rows (added to round the row count up to a tile) carry
scale == 0 sidecars; decode only multiplies by the scale (no division), and
the encode guard ``maximum(absmax, 1e-12)`` keeps the fresh scales nonzero,
so padding can never emit NaN/Inf.

``REPRO_OPT_BLOCK`` overrides the tile row count (here and in qdq.py's
kernels, via the shared ``qdq.default_block_rows`` read at call time) for
block-size autotune sweeps.

TARGET: TPU (pl.pallas_call + BlockSpec).  VALIDATED: interpret=True on CPU
against the adamw.py loop (tests/test_opt_update.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
# kernel tile row count; the shared REPRO_OPT_BLOCK knob (read at call time)
# retiles this kernel and the qdq family together for autotune sweeps
from repro.kernels.qdq import default_block_rows as tile_rows

_EPS = 1e-12

#: SMEM scalar vector layout (one fp32 slot per AdamW hyper/step scalar).
SCALARS = ("clip", "lr", "b1", "b2", "eps", "wd", "c1", "c2")


class MomentCodec(NamedTuple):
    """Static (hashable) per-moment codec parameters the kernel bakes in --
    mirrors the QuantSpec fields the blockwise int path consumes."""
    qmin: int
    qmax: int
    symmetric: bool
    sqrt_domain: bool


def codec_of(spec) -> MomentCodec:
    return MomentCodec(qmin=spec.qmin, qmax=spec.qmax,
                       symmetric=spec.symmetric,
                       sqrt_domain=spec.sqrt_domain)


def _dequant(q_ref, s_ref, z_ref, codec: MomentCodec) -> jnp.ndarray:
    """dequantize_int + (sqrt-domain square), blockwise rows.  Multiplies by
    the stored scale only -- 0-scale padding rows decode to exact 0."""
    deq = s_ref[...] * (q_ref[...].astype(jnp.float32) + z_ref[...])
    if codec.sqrt_domain:
        deq = jnp.square(deq)
    return deq


def _requant(x: jnp.ndarray, codec: MomentCodec
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """quantize_int's blockwise row codec in-register: per-row scale/zero over
    the last dim (one quantization block per row).  Same op order and 1e-12
    guards as core.quantizer.compute_scale_zero, so re-encoded payloads match
    the loop path's bit for bit."""
    if codec.sqrt_domain:
        x = jnp.sqrt(jnp.maximum(x, 0.0))
    if codec.symmetric:
        absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.maximum(absmax, _EPS) / codec.qmax
        zero = jnp.zeros_like(scale)
    else:
        xmin = jnp.min(x, axis=-1, keepdims=True)
        xmax = jnp.max(x, axis=-1, keepdims=True)
        scale = jnp.maximum(xmax - xmin, _EPS) / (codec.qmax - codec.qmin)
        zero = jnp.round(xmin / scale) - codec.qmin
    q = jnp.clip(jnp.round(x / scale) - zero, codec.qmin, codec.qmax)
    return q.astype(jnp.int8), scale, zero


def _adamw_kernel(sc_ref, g_ref, p_ref, q1_ref, s1_ref, z1_ref,
                  q2_ref, s2_ref, z2_ref,
                  po_ref, q1o_ref, s1o_ref, z1o_ref,
                  q2o_ref, s2o_ref, z2o_ref, un_ref, *,
                  m1: MomentCodec, m2: MomentCodec, wd_on: bool):
    clip, lr, b1, b2 = sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3]
    eps, wd, c1, c2 = sc_ref[4], sc_ref[5], sc_ref[6], sc_ref[7]

    g = g_ref[...].astype(jnp.float32) * clip
    p = p_ref[...].astype(jnp.float32)
    mom1 = b1 * _dequant(q1_ref, s1_ref, z1_ref, m1) + (1.0 - b1) * g
    mom2 = b2 * _dequant(q2_ref, s2_ref, z2_ref, m2) \
        + (1.0 - b2) * jnp.square(g)

    upd = (mom1 / c1) / (jnp.sqrt(mom2 / c2) + eps)
    if wd_on:
        upd = upd + wd * p
    delta = lr * upd
    po_ref[...] = (p - delta).astype(po_ref.dtype)
    # one (8, 128) register tile per grid cell: the smallest lane-legal
    # block for a per-tile scalar; the wrapper reads element [0, 0]
    un_ref[...] = jnp.full(un_ref.shape, jnp.sum(jnp.square(delta)),
                           jnp.float32)

    q1o_ref[...], s1o_ref[...], z1o_ref[...] = _requant(mom1, m1)
    q2o_ref[...], s2o_ref[...], z2o_ref[...] = _requant(mom2, m2)


def fused_adamw_blocks(g: jnp.ndarray, p: jnp.ndarray,
                       m1_q: jnp.ndarray, m1_scale: jnp.ndarray,
                       m1_zero: jnp.ndarray,
                       m2_q: jnp.ndarray, m2_scale: jnp.ndarray,
                       m2_zero: jnp.ndarray,
                       scalars: jnp.ndarray, *,
                       m1_codec: MomentCodec, m2_codec: MomentCodec,
                       weight_decay: bool,
                       block_rows: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """One fused AdamW step over a (rows, block_size) bucket.

    ``g``/``p``: fp (rows, bs); ``m?_q``: int8 (rows, bs); ``m?_scale`` /
    ``m?_zero``: fp32 (rows, 1); ``scalars``: fp32 (8,) in :data:`SCALARS`
    order.  Row count must be a multiple of the tile (adamw.py pads; padded
    rows stream 0 payloads / 0 scales and write exact-0 params).

    Returns (p_new, (m1_q, m1_scale, m1_zero), (m2_q, ..), update_sumsq)
    where ``update_sumsq`` is sum ||lr * upd||^2 over the bucket (the
    update_norm partial -- padding rows contribute exact 0).
    """
    rows, bs = g.shape
    br = min(block_rows or tile_rows(), rows)
    assert rows % br == 0, (rows, br)
    grid = (rows // br,)
    data = pl.BlockSpec((br, bs), lambda i: (i, 0))
    side = pl.BlockSpec((br, 1), lambda i: (i, 0))
    part = pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_adamw_kernel, m1=m1_codec, m2=m2_codec,
                          wd_on=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  data, data, data, side, side, data, side, side],
        out_specs=(data, data, side, side, data, side, side, part),
        out_shape=(jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct((rows, bs), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, bs), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((grid[0], 8, 128), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(scalars, g, p, m1_q, m1_scale, m1_zero, m2_q, m2_scale, m2_zero)
    p_new, q1, s1, z1, q2, s2, z2, un = out
    return p_new, (q1, s1, z1), (q2, s2, z2), jnp.sum(un[:, 0, 0])
