"""Flash attention (forward) as a Pallas TPU kernel.

EXPERIMENTS.md §Perf attributes the dominant memory-roofline term of every
train/prefill cell to the XLA-materialized score/softmax chain (~13 HBM
passes over fp32 (chunk, S) slabs).  This kernel is the structural fix: the
online-softmax tiles live in VMEM scratch; HBM traffic is exactly the
BlockSpec DMA schedule

    bytes = b*h * ( Sq*d (q, once) + Sq*d (o, once)
                    + 2 * Skv*d * ceil(Sq/block_q) (k+v reload per q row) )

computable in closed form via :func:`hbm_traffic_bytes` -- for llama3
train_4k this is ~0.3 GB/layer vs ~13 GB/layer for the materialized chain.

Grid: (b*h, Sq/bq, Skv/bk), kv innermost; scratch carries the running
(m, l, acc) per q tile.  Causal tiles above the diagonal are skipped via
pl.when.  TARGET: TPU.  VALIDATED: interpret=True vs ref (tests).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.int8_matmul import scale_guard

BLOCK_Q = 512
BLOCK_K = 512


def online_softmax_update(s, vt, m_ref, l_ref, acc_ref):
    """One online-softmax accumulation step: fold the scores tile ``s`` into
    the running (m, l, acc) scratch against the value tile ``vt``.  Shared
    by every forward kernel (fp, LSE-emitting, int8-dequant-prologue, and
    the decode kernel in decode_attn.py) so the recurrence exists once."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      scale: float, causal: bool, bq: int, bk: int, nk: int,
                      q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: the whole (qi, ki) tile is masked iff its first kv position
    # exceeds the last q position
    first_k = ki * bk
    last_q = q_offset + qi * bq + bq - 1
    live = (first_k <= last_q) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        online_softmax_update(s, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, q_offset: int = 0,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (BH, Sq, d); k, v: (BH, Skv, d) -> (BH, Sq, d).

    Sq % block_q == 0 and Skv % block_k == 0 (callers pad or shrink blocks).
    """
    interpret = resolve_interpret(interpret)
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bk = min(block_k, skv)
    while skv % bk:
        bk //= 2
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, q_offset=q_offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum
            pltpu.VMEM((bq, d), jnp.float32),     # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_ref, l_ref, acc_ref, *, scale, causal, bq, bk,
                          nk, q_offset):
    """Forward that also emits the log-sum-exp rows (for the Pallas bwd)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ((ki * bk) <= (q_offset + qi * bq + bq - 1)) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        online_softmax_update(s, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                           bq, bk, nq, q_offset):
    """Grid (bh, nk, nq): accumulate dK/dV for one kv tile over q tiles."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = ((ki * bk) <= (q_offset + qi * bq + bq - 1)) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        p = jnp.exp(s - lse_ref[0])                        # (bq, bk)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, scale, causal, bq, bk, nk,
                         q_offset):
    """Grid (bh, nq, nk): accumulate dQ for one q tile over kv tiles."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = ((ki * bk) <= (q_offset + qi * bq + bq - 1)) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        p = jnp.exp(s - lse_ref[0])
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _blocks(sq, skv, block_q, block_k):
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bk = min(block_k, skv)
    while skv % bk:
        bk //= 2
    return bq, bk


def _fwd_with_lse(q, k, v, causal, q_offset, block_q, block_k, interpret):
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bk = _blocks(sq, skv, block_q, block_k)
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(d)
    return pl.pallas_call(
        functools.partial(_flash_fwd_lse_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, q_offset=q_offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: Optional[bool] = None):
    """Differentiable flash attention: Pallas forward AND backward (the
    classic dKdV / dQ two-kernel recompute scheme with saved LSE rows)."""
    interpret = resolve_interpret(interpret)
    o, _ = _fwd_with_lse(q, k, v, causal, q_offset, block_q, block_k,
                         interpret)
    return o


def _ref_attend(q, k, v, causal, q_offset):
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = jnp.arange(k.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def _fa_fwd(q, k, v, causal, q_offset, block_q, block_k, interpret):
    interpret = resolve_interpret(interpret)
    o, lse = _fwd_with_lse(q, k, v, causal, q_offset, block_q, block_k,
                           interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, q_offset, block_q, block_k, interpret, res, g):
    interpret = resolve_interpret(interpret)
    q, k, v, o, lse = res
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bk = _blocks(sq, skv, block_q, block_k)
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(d)
    # (bh, sq, 1): row statistics ride as (bq, 1) column blocks, the
    # lane-legal layout the score tiles broadcast against
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, q_offset=q_offset),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # do
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),   # lse
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, skv, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, skv, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g.astype(q.dtype), lse, delta)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, q_offset=q_offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # do
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),   # lse
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g.astype(q.dtype), lse, delta)

    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# int8-KV prefill: the forward kernel with a dequant prologue.  Consumes the
# decode cache's stored form -- (B, Skv, K, hd) int8 payloads + (B, Skv, K, 1)
# fp32 per-(position, head) scale sidecars -- directly, so int8-KV prefill
# stops materializing a full fp K/V copy of the (max_seq-sized) cache buffer.
# GQA: q head h reads kv head h // g of the same tile, no head repeat.
# Forward-only (serving path); scale==0 padding rows are guarded (see
# decode_attn._guard).
# ---------------------------------------------------------------------------

def _flash_fwd_q8_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                         bq: int, bk: int, nk: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    h, kh = q_ref.shape[2], kq_ref.shape[2]
    g = h // kh

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ((ki * bk) <= (q_offset + qi * bq + bq - 1)) if causal else True

    @pl.when(live)
    def _compute():
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
        for j in range(kh):     # each kv tile dequantized once per kv head
            kt = (kq_ref[0, :, j, :].astype(jnp.float32)
                  * scale_guard(ks_ref[0, :, j, :]))            # (bk, d)
            vt = (vq_ref[0, :, j, :].astype(jnp.float32)
                  * scale_guard(vs_ref[0, :, j, :]))
            for hh in range(j * g, (j + 1) * g):        # its GQA q heads
                q = q_ref[0, :, hh, :].astype(jnp.float32) * scale  # (bq, d)
                s = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if causal:
                    s = jnp.where(kpos <= qpos, s, -1e30)
                online_softmax_update(s, vt, m_ref.at[hh], l_ref.at[hh],
                                      acc_ref.at[hh])

    @pl.when(ki == nk - 1)
    def _done():
        for hh in range(h):
            o_ref[0, :, hh, :] = acc_ref[hh] / jnp.maximum(l_ref[hh], 1e-30)


def flash_attention_fwd_q8(q: jnp.ndarray,
                           kq: jnp.ndarray, ks: jnp.ndarray,
                           vq: jnp.ndarray, vs: jnp.ndarray, *,
                           causal: bool = True, q_offset: int = 0,
                           block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Sq, H, hd); kq/vq: (B, Skv, K, hd) int8; ks/vs: (B, Skv, K, 1)
    fp32 -> (B, Sq, H, hd).  H % K == 0 (GQA/MQA); causal masking makes any
    never-written cache tail (rows >= q_offset + Sq) invisible.

    Grid ``(B, Sq/bq, Skv/bk)``: every block spans all heads, so its last two
    dims are the array's own ``(H|K, hd)`` -- the TPU tiling rule -- and each
    int8 kv tile is DMA'd once for all the q heads that read it."""
    interpret = resolve_interpret(interpret)
    b, sq, h, hd = q.shape
    skv, kh = kq.shape[1], kq.shape[2]
    assert h % kh == 0, (h, kh)
    if not causal and skv != q_offset + sq:
        # nothing but the causal mask hides never-written cache rows (their
        # guarded scale-0 / payload-0 entries would otherwise enter the
        # softmax with exp(0) weight and silently dilute every output)
        raise ValueError(
            f"causal=False requires a fully written cache: Skv={skv} vs "
            f"q_offset+Sq={q_offset + sq}")
    bq, bk = _blocks(sq, skv, block_q, block_k)
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(hd)
    kv_tile = pl.BlockSpec((1, bk, kh, hd), lambda b, i, j: (b, j, 0, 0))
    kv_scale = pl.BlockSpec((1, bk, kh, 1), lambda b, i, j: (b, j, 0, 0))
    q_tile = pl.BlockSpec((1, bq, h, hd), lambda b, i, j: (b, i, 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_fwd_q8_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, q_offset=q_offset),
        grid=(b, nq, nk),
        in_specs=[q_tile, kv_tile, kv_scale, kv_tile, kv_scale],
        out_specs=q_tile,
        # f32 out: Mosaic stores one head's (bq, hd) rows into the strided
        # (bq, H, hd) tile for 32-bit types only
        out_shape=jax.ShapeDtypeStruct((b, sq, h, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((h, bq, 1), jnp.float32),
                        pltpu.VMEM((h, bq, 1), jnp.float32),
                        pltpu.VMEM((h, bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, kq, ks, vq, vs).astype(q.dtype)


def hbm_traffic_bytes(bh: int, sq: int, skv: int, d: int,
                      dtype_bytes: int = 2, block_q: int = BLOCK_Q) -> int:
    """Exact DMA traffic implied by the BlockSpec schedule (the kernel's
    memory-roofline claim; used for the §Perf flash projection)."""
    nq = max(sq // min(block_q, sq), 1)
    q_o = 2 * sq * d
    kv = 2 * skv * d * nq
    return bh * (q_o + kv) * dtype_bytes
