"""Fused int8-KV decode attention: attend directly on the quantized cache.

The serving hot path stores the KV cache as int8 payloads with per-(position,
head) fp32 scale sidecars (``policy.kv_spec()``), but the reference decode
path dequantizes the *whole* buffer to fp and re-casts before attending --
every step, every layer.  At the memory roofline that reads ~5-9x the
quantized bytes (int8 read + fp materialize + fp re-read) and erases the
storage win exactly where it matters most (Jorgensen 2025; Bondarenko et al.
2021 make the same point for kernels generally: the low-bit payload must be
consumed *in-kernel*).

This kernel runs one decode step per (slot, kv tile) grid cell, over every
kv head of the tile:

  stream in   int8 K/V payload tiles spanning all kv heads + fp32 scale
              sidecars (BlockSpec DMA; the blocks' last two dims are the
              array's own (K, hd) / (K, 1), the TPU tiling rule), the
              (K, G, hd) query tile, and the step's fresh fp K/V rows
  in VMEM     dequantize each head's (bk, hd) tile against its scale column
              and fold it into that head's online softmax; only int8 crosses
              HBM.  scale==0 padding rows are guarded like the NT/TN matmul
              kernels
  fused       quantize the new K/V rows (symmetric nearest per-(position,
              head), the `_kv_quant` codec) and scatter payload + scales into
              the cache row ``pos[b]`` via scalar-prefetch-indexed output
              blocks aliased onto the cache buffers
  write out   the (K, G, hd) context tile and ONE int8 row + scale row per
              K/V buffer -- one read of the int8 cache, one int8 row write.

Per-slot ``pos`` (B,) drives both the validity mask (cache rows < pos[b])
and the scatter target, so ragged continuous-batching slots are handled
in-kernel.  ``REPRO_DECODE_BLOCK`` overrides the kv tile length for
block-size autotune sweeps (``benchmarks/serve_throughput.py --sweep``).

TARGET: TPU.  VALIDATED: interpret=True vs the dequantize-whole-buffer
reference path (tests/test_decode_attn.py).
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.flash_attn import online_softmax_update

DEFAULT_BLOCK_K = 256

#: fp32 VPU register tile is (8 sublanes, 128 lanes); a (G, hd) query tile
#: with G < 8 occupies a ragged partial tile per grid cell.  The wrappers
#: zero-pad the query-head dim up to this so every tile is lane-aligned --
#: pad rows cost nothing real (softmax over zero scores) and are sliced off.
Q_TILE_SUBLANES = 8

_EPS = 1e-12


def default_block_k() -> int:
    """KV tile length used when the caller passes none.  ``REPRO_DECODE_BLOCK``
    overrides it (read at call time) for block-size autotune sweeps, the
    decode-kernel counterpart of ``REPRO_OPT_BLOCK``."""
    v = os.environ.get("REPRO_DECODE_BLOCK", "")
    return int(v) if v else DEFAULT_BLOCK_K


def effective_block_k(s: int, block_k: Optional[int] = None) -> int:
    """The kv tile length :func:`decode_attention` will actually compile for
    an ``s``-row cache: the requested (or ``REPRO_DECODE_BLOCK``/default)
    tile clamped to ``s`` and shrunk to a divisor.  Exposed so reporting
    (``Engine.path_summary``) names the compiled tile, not the request."""
    bk = min(block_k or default_block_k(), s)
    while s % bk:
        bk //= 2
    return bk


def fused_decode_enabled() -> bool:
    """Should the int8-KV attention kernels replace the dequantize-whole-
    buffer reference path?  Default: on TPU (the kernels' target); interpret
    mode is functional but slow, so CPU keeps the reference path unless
    ``REPRO_FUSED_DECODE=1`` forces it (tests/CI pin ``1``; ``0`` forces the
    reference path everywhere)."""
    force = os.environ.get("REPRO_FUSED_DECODE", "")
    if force:
        return force != "0"
    return jax.default_backend() == "tpu"


# never-written cache rows carry scale == 0 sidecars (buffers init to
# zeros); their payloads are 0 and the validity mask excludes them anyway,
# but guard to 1.0 so no reciprocal/dequant on padding lanes can emit
# NaN/Inf -- the canonical guard from the int8 matmul kernel family
from repro.kernels.int8_matmul import scale_guard as _guard


def _lane_align_q(q: jnp.ndarray):
    """Pad the (G, hd) query tile up to :data:`Q_TILE_SUBLANES` rows when the
    GQA group is small (``n_heads // n_kv_heads < 8``): every grid cell then
    streams a full (8, lane) register tile instead of a ragged one.  Pad rows
    are zero queries -- their scores are 0 everywhere, the online softmax
    stays finite, and their context rows are sliced off by
    :func:`_lane_trim_ctx`.  Real rows are bit-identical to the unpadded
    launch (row-independent math).  Returns ``(q_padded, g_padded, g)``."""
    b, kh, g, hd = q.shape
    if g >= Q_TILE_SUBLANES:
        return q, g, g
    gp = Q_TILE_SUBLANES
    q = jnp.concatenate(
        [q, jnp.zeros((b, kh, gp - g, hd), q.dtype)], axis=2)
    return q, gp, g


def _lane_trim_ctx(ctx: jnp.ndarray, g_real: int) -> jnp.ndarray:
    return ctx if ctx.shape[2] == g_real else ctx[:, :, :g_real]


def _decode_attn_kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                        nk_ref, nv_ref,
                        o_ref, okq_ref, oks_ref, ovq_ref, ovs_ref,
                        m_ref, l_ref, acc_ref, *,
                        bk: int, nblk: int, scale: float,
                        qmin: int, qmax: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    pos = pos_ref[b]
    kh = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tiles entirely past the slot's valid rows contribute nothing: skip
    @pl.when(ki * bk < pos)
    def _tile():
        t = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        for h in range(kh):          # every kv head of the one DMA'd tile
            q = q_ref[0, h].astype(jnp.float32) * scale            # (G, hd)
            kt = (kq_ref[0, :, h, :].astype(jnp.float32)
                  * _guard(ks_ref[0, :, h, :]))                    # (bk, hd)
            s = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(t < pos, s, -1e30)  # prior rows; new row below
            vt = (vq_ref[0, :, h, :].astype(jnp.float32)
                  * _guard(vs_ref[0, :, h, :]))
            online_softmax_update(s, vt, m_ref.at[h], l_ref.at[h],
                                  acc_ref.at[h])

    @pl.when(ki == nblk - 1)
    def _done():
        # fused quantize + scatter of the step's K/V rows (all heads): the
        # attention reads the freshly *quantized* values (parity with the
        # stored form), and the int8 payload + scales land in the cache row
        # ``pos[b]`` through the scalar-prefetch-indexed, cache-aliased
        # output blocks.
        knew = nk_ref[0].astype(jnp.float32)                   # (K, hd)
        vnew = nv_ref[0].astype(jnp.float32)
        ks_new = jnp.maximum(jnp.max(jnp.abs(knew), axis=-1, keepdims=True),
                             _EPS) / qmax                      # (K, 1)
        kq_new = jnp.clip(jnp.round(knew / ks_new), qmin, qmax)
        vs_new = jnp.maximum(jnp.max(jnp.abs(vnew), axis=-1, keepdims=True),
                             _EPS) / qmax
        vq_new = jnp.clip(jnp.round(vnew / vs_new), qmin, qmax)
        okq_ref[0, 0] = kq_new.astype(okq_ref.dtype)
        oks_ref[0, 0] = ks_new
        ovq_ref[0, 0] = vq_new.astype(ovq_ref.dtype)
        ovs_ref[0, 0] = vs_new
        # attend on the rows as stored, all heads at once: (K, G, hd) q
        # against the (K, 1, hd) new K/V rows
        kdeq = (okq_ref[0, 0].astype(jnp.float32) * oks_ref[0, 0])[:, None]
        vdeq = (ovq_ref[0, 0].astype(jnp.float32) * ovs_ref[0, 0])[:, None]
        q = q_ref[0].astype(jnp.float32) * scale               # (K, G, hd)
        s_new = jnp.sum(q * kdeq, axis=-1, keepdims=True)      # (K, G, 1)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s_new)
        alpha = jnp.exp(m_prev - m_new)
        p_new = jnp.exp(s_new - m_new)
        l = alpha * l_ref[...] + p_new
        acc = acc_ref[...] * alpha + p_new * vdeq
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _decode_scratch(kh: int, g: int, hd: int):
    return [pltpu.VMEM((kh, g, 1), jnp.float32),      # running max
            pltpu.VMEM((kh, g, 1), jnp.float32),      # running sum
            pltpu.VMEM((kh, g, hd), jnp.float32)]     # accumulator


def decode_attention(q: jnp.ndarray,
                     kq: jnp.ndarray, ks: jnp.ndarray,
                     vq: jnp.ndarray, vs: jnp.ndarray,
                     new_k: jnp.ndarray, new_v: jnp.ndarray,
                     pos: jnp.ndarray, *,
                     qmin: int = -128, qmax: int = 127,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray, jnp.ndarray]:
    """One fused decode-attention step on the int8 KV cache.

    q: (B, K, G, hd) fp grouped queries; kq/vq: (B, S, K, hd) int8 payloads;
    ks/vs: (B, S, K, 1) fp32 scale sidecars; new_k/new_v: (B, K, hd) fp rows
    for this step (RoPE already applied); pos: (B,) int32 per-slot validity
    lengths == scatter rows.  Returns ``(ctx, kq', ks', vq', vs')`` where the
    primed buffers are the caches with the new row written (aliased in
    place: the inputs are donated).

    Grid ``(slots, kv_tiles)``: each block spans every kv head, so its last
    two dims are the array's own ``(K, hd)`` / ``(K, 1)`` -- the TPU tiling
    rule -- and each cache tile is DMA'd once for all heads.
    """
    interpret = resolve_interpret(interpret)
    b, kh, g, hd = q.shape
    q, g, g_real = _lane_align_q(q)
    s = kq.shape[1]
    bk = effective_block_k(s, block_k)
    nblk = s // bk
    scale = 1.0 / math.sqrt(hd)

    def row(pos_ref, bi):
        # scatter target.  pos == s is the degenerate freed-slot case (a
        # length-finished slot keeps decoding in the batch with its stale
        # position until readmission): clamp to the last row, matching the
        # reference path's dynamic_update_slice semantics -- the row is
        # never read back (masks stop at the slot's next admitted length)
        # and the slot's output is discarded by the scheduler.
        return jnp.minimum(pos_ref[bi], s - 1)

    tile = lambda b, j, pos_ref: (b, j, 0, 0)
    at_row = lambda b, j, pos_ref: (b, row(pos_ref, b), 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, kh, g, hd), lambda b, j, pos_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, kh, hd), tile),
            pl.BlockSpec((1, bk, kh, 1), tile),
            pl.BlockSpec((1, bk, kh, hd), tile),
            pl.BlockSpec((1, bk, kh, 1), tile),
            pl.BlockSpec((1, kh, hd), lambda b, j, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, kh, hd), lambda b, j, pos_ref: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kh, g, hd), lambda b, j, pos_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, kh, hd), at_row),
            pl.BlockSpec((1, 1, kh, 1), at_row),
            pl.BlockSpec((1, 1, kh, hd), at_row),
            pl.BlockSpec((1, 1, kh, 1), at_row),
        ],
        scratch_shapes=_decode_scratch(kh, g, hd),
    )
    ctx, okq, oks, ovq, ovs = pl.pallas_call(
        functools.partial(_decode_attn_kernel, bk=bk, nblk=nblk, scale=scale,
                          qmin=qmin, qmax=qmax),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
            jax.ShapeDtypeStruct(kq.shape, kq.dtype),
            jax.ShapeDtypeStruct(ks.shape, ks.dtype),
            jax.ShapeDtypeStruct(vq.shape, vq.dtype),
            jax.ShapeDtypeStruct(vs.shape, vs.dtype),
        ],
        # the int8 caches and scale sidecars update in place: only the
        # pos[b] row blocks are DMA'd back
        input_output_aliases={2: 1, 3: 2, 4: 3, 5: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, q, kq, ks, vq, vs, new_k, new_v)
    return _lane_trim_ctx(ctx, g_real), okq, oks, ovq, ovs


def _paged_decode_attn_kernel(pos_ref, pt_ref, *refs, **kw):
    """Paged wrapper: identical compute to :func:`_decode_attn_kernel` -- the
    page table is consumed purely by the BlockSpec index maps (physical-page
    DMA routing), never by the kernel body, so the in-register
    dequant-into-softmax and the fused row quantize+scatter are reused
    verbatim."""
    del pt_ref
    _decode_attn_kernel(pos_ref, *refs, **kw)


def decode_attention_paged(q: jnp.ndarray,
                           kq: jnp.ndarray, ks: jnp.ndarray,
                           vq: jnp.ndarray, vs: jnp.ndarray,
                           new_k: jnp.ndarray, new_v: jnp.ndarray,
                           pos: jnp.ndarray, page_table: jnp.ndarray, *,
                           qmin: int = -128, qmax: int = 127,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray, jnp.ndarray]:
    """One fused decode-attention step on the *paged* int8 KV pool.

    q: (B, K, G, hd) fp grouped queries; kq/vq: (P, page, K, hd) int8 page
    pools (no slot axis -- pages are shared across slots); ks/vs:
    (P, page, K, 1) fp32 scale sidecar pools; new_k/new_v: (B, K, hd) fp rows;
    pos: (B,) validity lengths; page_table: (B, max_pages) int32 mapping each
    slot's logical page j to a physical pool page (unmapped entries point at
    the trash page 0).

    The grid is the dense kernel's ``(slots, kv_tiles)`` with the kv tile
    pinned to one page: both ``pos`` and the page table are scalar-
    prefetched, and the *input* index maps route logical tile ``j`` to
    physical page ``page_table[b, min(j, ceil(pos[b]/page)-1)]`` -- tiles
    past the slot's live length are clamped to the last live page, so no
    slot ever DMAs more than ``ceil(pos[b]/page)`` distinct pages (their
    compute is skipped by the ``ki*bk < pos`` guard regardless).  The fused
    new-row scatter targets ``(page_table[b, pos[b]//page], pos[b]%page)``.
    Returns ``(ctx, kq', ks', vq', vs')`` with the pools aliased in place.
    """
    interpret = resolve_interpret(interpret)
    b, kh, g, hd = q.shape
    q, g, g_real = _lane_align_q(q)
    page = kq.shape[1]
    maxp = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    last = maxp * page - 1

    def rd(b, j, pos_ref, pt_ref):
        live_last = jnp.maximum((pos_ref[b] + page - 1) // page - 1, 0)
        return pt_ref[b, jnp.minimum(j, live_last)], 0, 0, 0

    def wr(b, j, pos_ref, pt_ref):
        # clamp like the dense kernel: pos == maxp*page is the degenerate
        # freed-slot case; the row lands in the slot's last mapped page and
        # is never read back
        p = jnp.minimum(pos_ref[b], last)
        return pt_ref[b, p // page], p % page, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, kh, g, hd),
                         lambda b, j, pos_ref, pt_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, kh, hd), rd),
            pl.BlockSpec((1, page, kh, 1), rd),
            pl.BlockSpec((1, page, kh, hd), rd),
            pl.BlockSpec((1, page, kh, 1), rd),
            pl.BlockSpec((1, kh, hd), lambda b, j, pos_ref, pt_ref: (b, 0, 0)),
            pl.BlockSpec((1, kh, hd), lambda b, j, pos_ref, pt_ref: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kh, g, hd),
                         lambda b, j, pos_ref, pt_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, kh, hd), wr),
            pl.BlockSpec((1, 1, kh, 1), wr),
            pl.BlockSpec((1, 1, kh, hd), wr),
            pl.BlockSpec((1, 1, kh, 1), wr),
        ],
        scratch_shapes=_decode_scratch(kh, g, hd),
    )
    ctx, okq, oks, ovq, ovs = pl.pallas_call(
        functools.partial(_paged_decode_attn_kernel, bk=page, nblk=maxp,
                          scale=scale, qmin=qmin, qmax=qmax),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
            jax.ShapeDtypeStruct(kq.shape, kq.dtype),
            jax.ShapeDtypeStruct(ks.shape, ks.dtype),
            jax.ShapeDtypeStruct(vq.shape, vq.dtype),
            jax.ShapeDtypeStruct(vs.shape, vs.dtype),
        ],
        # pools alias in place (operands 0/1 are the prefetched scalars):
        # only the one written row block per slot is DMA'd back
        input_output_aliases={3: 1, 4: 2, 5: 3, 6: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, page_table, q, kq, ks, vq, vs, new_k, new_v)
    return _lane_trim_ctx(ctx, g_real), okq, oks, ovq, ovs


# ---------------------------------------------------------------------------
# SPMD dispatch: shard_map the decode kernels over the KV-head axis
# ---------------------------------------------------------------------------

def spmd_head_shardable(n_kv_heads: int, rules) -> bool:
    """Can the fused decode kernels run per-shard over the kv-head axis of
    ``rules.mesh``?  True when the rules map ``kv`` to exactly one mesh axis
    whose size divides the head count -- each shard then launches the
    unchanged Pallas kernel on its local ``K // tp`` head slice (the grid's
    kv-head dim is embarrassingly parallel: no cross-head reduction
    anywhere).  Otherwise callers fall back to the gather/reference path."""
    if rules is None:
        return False
    ax = rules.axis_map.get("kv") or ()
    if len(ax) != 1:
        return False
    size = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))[ax[0]]
    return n_kv_heads % size == 0


def decode_attention_spmd(q, kq, ks, vq, vs, new_k, new_v, pos, *,
                          mesh, kv_axis: str = "model",
                          qmin: int = -128, qmax: int = 127,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """:func:`decode_attention` under SPMD: shard_map over the kv-head axis,
    each shard running the Pallas kernel on its local head slice of the
    cache (payloads AND scale sidecars arrive pre-sharded -- the per-shard
    BlockSpec DMA never crosses chips).  Math is bitwise identical to the
    single-device kernel: per-(slot, head) online softmax has no cross-shard
    reduction.  ``pos`` is replicated (host-side slot bookkeeping)."""
    from jax.sharding import PartitionSpec as P
    kv4 = P(None, None, kv_axis, None)

    def f(q_, kq_, ks_, vq_, vs_, nk_, nv_, pos_):
        return decode_attention(q_, kq_, ks_, vq_, vs_, nk_, nv_, pos_,
                                qmin=qmin, qmax=qmax, block_k=block_k,
                                interpret=interpret)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, kv_axis, None, None), kv4, kv4, kv4, kv4,
                  P(None, kv_axis, None), P(None, kv_axis, None), P()),
        out_specs=(P(None, kv_axis, None, None), kv4, kv4, kv4, kv4),
        check_vma=False,
    )(q, kq, ks, vq, vs, new_k, new_v, pos)


def decode_attention_paged_spmd(q, kq, ks, vq, vs, new_k, new_v, pos,
                                page_table, *,
                                mesh, kv_axis: str = "model",
                                qmin: int = -128, qmax: int = 127,
                                interpret: Optional[bool] = None):
    """:func:`decode_attention_paged` under SPMD: the page pools shard over
    their kv-head dim (``PAGED_POOL_AXES``), the page table and ``pos`` are
    replicated scalar bookkeeping, and every shard DMAs pages of its local
    head slice only."""
    from jax.sharding import PartitionSpec as P
    pool4 = P(None, None, kv_axis, None)

    def f(q_, kq_, ks_, vq_, vs_, nk_, nv_, pos_, pt_):
        return decode_attention_paged(q_, kq_, ks_, vq_, vs_, nk_, nv_,
                                      pos_, pt_, qmin=qmin, qmax=qmax,
                                      interpret=interpret)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, kv_axis, None, None), pool4, pool4, pool4, pool4,
                  P(None, kv_axis, None), P(None, kv_axis, None), P(), P()),
        out_specs=(P(None, kv_axis, None, None), pool4, pool4, pool4, pool4),
        check_vma=False,
    )(q, kq, ks, vq, vs, new_k, new_v, pos, page_table)


def decode_kv_read_bytes(mode: str, batch: int, max_seq: int,
                         n_kv_heads: int, head_dim: int, *,
                         n_layers: int = 1, fp_bytes: int = 2) -> int:
    """Analytic HBM bytes moved per decode step by the KV read, per the
    attention path's access pattern (the benchmark's roofline claim):

      * ``fp``      -- read the fp K+V buffers once.
      * ``dequant`` -- dequantize-on-read reference: read the int8 payloads
        and fp32 scale sidecars, materialize fp K+V copies (one write), and
        the attention reads those copies back (one read).
      * ``fused``   -- the kernel's BlockSpec DMA schedule: read the int8
        payloads + scale sidecars, nothing materialized.

    The fused path's one int8-row write (and q/ctx tiles) is O(1/max_seq) of
    the cache read and is excluded from all three for comparability.
    """
    elems = batch * max_seq * n_kv_heads * head_dim      # per buffer (K or V)
    scales = batch * max_seq * n_kv_heads                # fp32 sidecar elems
    if mode == "fp":
        per_layer = 2 * elems * fp_bytes
    elif mode == "dequant":
        per_layer = 2 * (elems * (1 + 2 * fp_bytes) + 4 * scales)
    elif mode == "fused":
        per_layer = 2 * (elems + 4 * scales)
    else:
        raise ValueError(f"unknown mode {mode!r} (fp | dequant | fused)")
    return per_layer * n_layers
