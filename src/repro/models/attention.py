"""Multi-head attention with GQA/MQA, RoPE, qk-norm, KV cache, cross-attn.

All weight-bearing projections route through the layer-aware
``QuantPolicy.linear`` dispatch (roles ``attn_qkv`` / ``attn_out``); the
score/context einsums are not linear layers and stay in carrier precision.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from repro.core.qconfig import Granularity
from repro.core.qpolicy import LinearCtx, as_policy
from repro.core.quantizer import (compute_scale_zero, quantize_int,
                                  storage_dtype)
from repro.models.common import ParamSpec, constrain, rmsnorm, rope


def attn_spec(cfg, d_in: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = d_in if d_in is not None else cfg.d_model
    h, k, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((d, h * hd), ("embed", "heads"), "fan_in"),
        "wk": ParamSpec((d, k * hd), ("embed", "kv"), "fan_in"),
        "wv": ParamSpec((d, k * hd), ("embed", "kv"), "fan_in"),
        "wo": ParamSpec((h * hd, d), ("heads", "embed"), "fan_in",
                        scale=1.0 / max(cfg.n_layers, 1)),
    }
    if cfg.use_bias:
        spec.update({
            "bq": ParamSpec((h * hd,), ("heads",), "zeros"),
            "bk": ParamSpec((k * hd,), ("kv",), "zeros"),
            "bv": ParamSpec((k * hd,), ("kv",), "zeros"),
            "bo": ParamSpec((d,), ("embed",), "zeros"),
        })
    if cfg.qk_norm:
        spec.update({
            "q_norm": ParamSpec((hd,), (None,), "ones"),
            "k_norm": ParamSpec((hd,), (None,), "ones"),
        })
    return spec


def init_cache(cfg, batch: int, max_seq: int, dtype, d_in: Optional[int] = None,
               kv_spec=None) -> Dict[str, jnp.ndarray]:
    """KV cache buffers for one layer.  ``kv_spec`` (a symmetric QuantSpec,
    from ``policy.kv_spec()``) switches storage to integer payloads plus fp32
    per-(position, head) scale sidecars -- the resident cache is ~1/2 (bf16)
    to ~1/4 (fp32) the size, consumed directly by the fused attention kernels
    where supported (kernels/decode_attn.py) and dequantized on read
    otherwise."""
    k, hd = cfg.n_kv_heads, cfg.head_dim
    if kv_spec is not None:
        qdt = storage_dtype(kv_spec.bits)
        return {
            "k": jnp.zeros((batch, max_seq, k, hd), qdt),
            "v": jnp.zeros((batch, max_seq, k, hd), qdt),
            "k_scale": jnp.zeros((batch, max_seq, k, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, max_seq, k, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_seq, k, hd), dtype),
        "v": jnp.zeros((batch, max_seq, k, hd), dtype),
    }


def _kv_guard(scale: jnp.ndarray) -> jnp.ndarray:
    """Scale sidecars of never-written cache rows are 0 (buffers init to
    zeros; every *written* row's scale is > 0 via the ``maximum(absmax, eps)``
    guard in ``compute_scale_zero``).  Guard 0 -> 1.0 before any dequant /
    reciprocal so padding rows cannot emit NaN/Inf -- the payloads there are
    0, so the dequantized value stays exactly 0.  Mirrors
    ``kernels.int8_matmul.scale_guard`` (kept local: the reference path must
    not pull in pallas imports)."""
    return jnp.where(scale == 0.0, 1.0, scale)


def _kv_quant(t: jnp.ndarray, spec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize new K/V rows (B, s, K, hd) for cache storage.  Per-token
    specs give one scale per (batch, position, head); per-tensor specs give
    one scale per *slot's* write block (never reducing over the batch axis:
    a request's stored precision must not depend on its batch neighbours)."""
    if spec.granularity is Granularity.PER_TENSOR:
        xf = t.astype(jnp.float32)
        scale, _ = compute_scale_zero(xf, spec, axes=(1, 2, 3))  # (B,1,1,1)
        q = jnp.clip(jnp.round(xf / scale), spec.qmin,
                     spec.qmax).astype(storage_dtype(spec.bits))
    else:
        q, scale, _ = quantize_int(t, spec)
    scale = jnp.broadcast_to(scale.astype(jnp.float32), t.shape[:-1] + (1,))
    return q, scale


def _cache_update(buf: jnp.ndarray, rows: jnp.ndarray,
                  offset) -> jnp.ndarray:
    """Write ``rows`` (B, s, ...) into ``buf`` (B, S_max, ...) at ``offset``:
    a scalar (all rows at one position -- the uniform-batch path) or a (B,)
    vector of per-slot positions (continuous batching)."""
    off = jnp.asarray(offset)
    if off.ndim == 0:
        return jax.lax.dynamic_update_slice(
            buf, rows, (0, off) + (0,) * (buf.ndim - 2))
    def one(b, r, o):
        return jax.lax.dynamic_update_slice(b, r, (o,) + (0,) * (b.ndim - 1))
    return jax.vmap(one)(buf, rows, off)


MAX_DENSE_Q = 1024        # q-chunk length for the memory-bounded path


def _mask_chunk(mask, qpos: jnp.ndarray, s_kv: int) -> Optional[jnp.ndarray]:
    """Materialize a (len(qpos), s_kv) boolean mask for one query chunk.
    ``mask`` is None (full), a dict spec, or a ready (Sq, Skv) array."""
    if mask is None:
        return None
    if isinstance(mask, dict):
        kpos = jnp.arange(s_kv)
        kind = mask["kind"]
        if kind == "causal":
            return kpos[None, :] <= qpos[:, None]
        if kind == "prefix":
            p = mask["prefix"]
            base = kpos[None, :] <= qpos[:, None]
            return base | ((qpos[:, None] < p) & (kpos[None, :] < p))
        if kind == "full":
            return None
        raise ValueError(kind)
    return mask


def _attend_block(qg, k, v, mask_b) -> jnp.ndarray:
    """qg: (B,qc,K,G,hd); k,v: (B,Skv,K,hd); mask_b: (qc,Skv) or None.

    Keeps XLA's native softmax pattern: a hand-rolled "minimal-pass" variant
    (bf16 probs, normalization on the context) was tried and REFUTED -- it
    added an fp32 exp slab before the cast and broke XLA's softmax fusion
    (memory term 5.65s -> 6.28s; see EXPERIMENTS.md Section Perf iter 2)."""
    hd = qg.shape[-1]
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask_b is not None:
        if mask_b.ndim == 2:                 # (Sq, Skv) shared across batch
            mask_b = mask_b[None, None, None]
        elif mask_b.ndim == 3:               # (B, Sq, Skv) per-slot masks
            mask_b = mask_b[:, None, None]
        scores = jnp.where(mask_b, scores, jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)


def _pick_chunk(sq: int, skv: int, b: int, h: int, rules,
                budget_bytes: float = 768e6) -> int:
    """Largest power-of-two q-chunk (<= MAX_DENSE_Q, dividing sq) whose fp32
    score slab (b_loc, h_loc, chunk, skv) stays under the budget."""
    dp = rules.dp_size if rules is not None else 1
    tp = rules.tp_size if rules is not None else 1
    b_loc = max(b // max(dp, 1), 1)
    h_loc = h // tp if h % tp == 0 else h
    chunk = MAX_DENSE_Q
    while chunk > 128 and b_loc * h_loc * chunk * skv * 4 > budget_bytes:
        chunk //= 2
    while sq % chunk:
        chunk //= 2
    return max(chunk, 1)


def _fused_kv_ok(policy, rules, kv_source,
                 n_kv_heads: Optional[int] = None) -> bool:
    """Static gate for the int8-KV attention kernels (fused decode + q8
    prefill): self-attention, a registered backend whose kernels consume the
    stored spec directly, and the ``REPRO_FUSED_DECODE`` switch (default:
    TPU only -- interpret mode keeps the bit-compared dequantize-on-read
    path as the oracle).

    Under sharding rules the gate is decode-only: callers pass
    ``n_kv_heads`` and the kernels run per-shard via ``shard_map`` over the
    kv-head axis when the head count divides the mesh
    (:func:`~repro.kernels.decode_attn.spmd_head_shardable`); otherwise --
    and always for the q8 *prefill* kernel, which is not shard_mapped --
    SPMD keeps the XLA gather/reference path."""
    if kv_source is not None:
        return False
    if rules is not None:
        from repro.kernels.decode_attn import spmd_head_shardable
        if n_kv_heads is None or not spmd_head_shardable(n_kv_heads, rules):
            return False
    from repro.kernels.decode_attn import fused_decode_enabled
    if not fused_decode_enabled():
        return False
    name, _ = policy.decode_attn_backend()
    return name == "int8_pallas"


def _flash_path_ok(impl: str, sq: int, mask) -> bool:
    if impl != "flash_pallas" or sq == 1:
        return False
    return mask is None or (isinstance(mask, dict)
                            and mask["kind"] in ("causal", "full"))


def _gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                mask, rules, q_offset=0, impl: str = "xla") -> jnp.ndarray:
    """q: (B,Sq,H,hd); k,v: (B,Skv,K,hd).  Softmax in fp32.

    Training/prefill (Sq > 1): kv heads are repeated to the full head count
    so the head dim shards cleanly on the tensor axis (GQA group dims like
    8x4 cannot map onto a 16-way mesh axis), and the computation runs
    query-chunked: the (Sq,Skv) score matrix never materializes -- only a
    (chunk,Skv) slab per scan step, with per-chunk masks synthesized from the
    mask spec (flash-attention memory behaviour, XLA-native).

    Decode (Sq == 1): grouped-query form so the KV cache is NOT inflated."""
    b, sq, h, hd = q.shape
    kheads = k.shape[2]
    g = h // kheads

    if sq == 1:
        qg = q.reshape(b, sq, kheads, g, hd)
        qpos = jnp.arange(sq) + q_offset
        ctx = _attend_block(qg, k, v, _mask_chunk(mask, qpos, k.shape[1]))
        return ctx.reshape(b, sq, h * hd)

    if g > 1:
        # pre-repeat boundary: gather seq / settle kv sharding BEFORE the
        # broadcast-reshape, else SPMD back-propagates the post-repeat head
        # sharding into half-head splits (involuntary full rematerialization)
        k = constrain(k, rules, "batch", None, "kv", None)
        v = constrain(v, rules, "batch", None, "kv", None)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # Megatron-SP boundary: gather sequence, shard heads (clean bwd since
    # GQA group dims like 8x4 cannot map onto a 16-way axis, but the repeated
    # h-dim can).
    k = constrain(k, rules, "batch", None, "heads", None)
    v = constrain(v, rules, "batch", None, "heads", None)
    qg = constrain(q, rules, "batch", None, "heads", None
                   ).reshape(b, sq, h, 1, hd)

    if _flash_path_ok(impl, sq, mask) and rules is None:
        # Pallas flash attention: VMEM-resident online softmax (fwd+bwd
        # kernels, kernels/flash_attn.py).  Single-device/TPU path; under
        # pjit the XLA q-chunked path below is used (interpret-mode pallas
        # does not partition).
        from repro.kernels.flash_attn import flash_attention
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
        kt = k.transpose(0, 2, 1, 3).reshape(b * h, k.shape[1], hd)
        vt = v.transpose(0, 2, 1, 3).reshape(b * h, v.shape[1], hd)
        causal = mask is not None and mask.get("kind") == "causal"
        ot = flash_attention(qt, kt, vt, causal, q_offset)
        return ot.reshape(b, h, sq, hd).transpose(0, 2, 1, 3).reshape(
            b, sq, h * hd)

    chunk = _pick_chunk(sq, k.shape[1], b, h, rules)
    if not (mask is None or isinstance(mask, dict)):
        # materialized (B, Sq, Skv) masks (packed-prefill segment masks)
        # cannot be re-sliced per chunk -- run the direct path
        chunk = sq
    if sq <= chunk:
        qpos = jnp.arange(sq) + q_offset
        ctx = _attend_block(qg, k, v, _mask_chunk(mask, qpos, k.shape[1]))
        return ctx.reshape(b, sq, h * hd)

    n_chunks = sq // chunk

    def body(_, xs):
        qc, i = xs
        qpos = jnp.arange(chunk) + i * chunk + q_offset
        mb = _mask_chunk(mask, qpos, k.shape[1])
        return None, _attend_block(qc, k, v, mb)

    # checkpoint: the chunk scan's backward recomputes scores/probs from
    # (qc, k, v) instead of saving a probs slab per chunk
    body = jax.checkpoint(body, prevent_cse=False)
    q_chunks = jnp.moveaxis(qg.reshape(b, n_chunks, chunk, h, 1, hd), 1, 0)
    _, chunks = jax.lax.scan(body, None, (q_chunks, jnp.arange(n_chunks)))
    # chunks: (n_chunks, B, chunk, H, 1, hd) -> (B, Sq, H*hd)
    ctx = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, h, 1, hd)
    return ctx.reshape(b, sq, h * hd)


def _paged_decode(q, k, v, cache, page_table, pos_vec, cfg, *,
                  policy, rules, kv_source, out_dtype):
    """One decode step on *paged* KV pools (``repro.infer.pages``).

    cache leaves are page pools ``(P, page, K, hd)`` shared by every slot;
    ``page_table`` (B, maxp) maps each slot's logical pages to physical ones.
    int8 pools with a supported backend run the fused paged kernel (page-
    routed DMA, in-register dequant, fused row quantize+scatter); otherwise
    the bit-compared gather reference: scatter the new row at
    ``(table[pos//page], pos%page)``, gather the slot's logical view, and
    return fp K/V for the shared masked-softmax path.

    Returns ``(ctx_or_None, k_full, v_full, new_cache)`` -- ``ctx`` is set
    only on the fused path."""
    b = q.shape[0]
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    page = cache["k"].shape[1]
    maxp = page_table.shape[1]
    quantized = "k_scale" in cache
    if quantized and _fused_kv_ok(policy, rules, kv_source, n_kv_heads=kh):
        from repro.kernels.decode_attn import (decode_attention_paged,
                                               decode_attention_paged_spmd)
        kv_spec = policy.kv_spec()
        qg = q[:, 0].reshape(b, kh, h // kh, hd)
        if rules is not None:
            ctx, nkq, nks, nvq, nvs = decode_attention_paged_spmd(
                qg, cache["k"], cache["k_scale"],
                cache["v"], cache["v_scale"],
                k[:, 0], v[:, 0], pos_vec, page_table,
                mesh=rules.mesh, kv_axis=rules.axis_map["kv"][0],
                qmin=kv_spec.qmin, qmax=kv_spec.qmax)
        else:
            ctx, nkq, nks, nvq, nvs = decode_attention_paged(
                qg, cache["k"], cache["k_scale"],
                cache["v"], cache["v_scale"],
                k[:, 0], v[:, 0], pos_vec, page_table,
                qmin=kv_spec.qmin, qmax=kv_spec.qmax)
        new_cache = {"k": nkq, "v": nvq, "k_scale": nks, "v_scale": nvs}
        return ctx.reshape(b, 1, h * hd), None, None, new_cache
    # gather reference: same values at the same logical rows as the dense
    # reference path, so tokens stay bitwise identical to a dense engine
    pc = jnp.minimum(pos_vec, maxp * page - 1)
    pid = page_table[jnp.arange(b), pc // page]
    row = pc % page
    if quantized:
        kv_spec = policy.kv_spec()
        kqn, ksn = _kv_quant(k, kv_spec)
        vqn, vsn = _kv_quant(v, kv_spec)
        new_cache = {
            "k": cache["k"].at[pid, row].set(kqn[:, 0]),
            "v": cache["v"].at[pid, row].set(vqn[:, 0]),
            "k_scale": cache["k_scale"].at[pid, row].set(ksn[:, 0]),
            "v_scale": cache["v_scale"].at[pid, row].set(vsn[:, 0]),
        }
        kf = (new_cache["k"][page_table].astype(jnp.float32)
              * _kv_guard(new_cache["k_scale"][page_table]))
        vf = (new_cache["v"][page_table].astype(jnp.float32)
              * _kv_guard(new_cache["v_scale"][page_table]))
    else:
        new_cache = {
            "k": cache["k"].at[pid, row].set(k[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[pid, row].set(v[:, 0].astype(cache["v"].dtype)),
        }
        kf, vf = new_cache["k"][page_table], new_cache["v"][page_table]
    kf = kf.reshape(b, maxp * page, kh, hd).astype(out_dtype)
    vf = vf.reshape(b, maxp * page, kh, hd).astype(out_dtype)
    return None, kf, vf, new_cache


def attn_apply(params, x: jnp.ndarray, cfg, *,
               policy=None, rules=None,
               positions: jnp.ndarray,
               mask: Optional[jnp.ndarray],
               kv_source: Optional[jnp.ndarray] = None,
               cache: Optional[Dict[str, jnp.ndarray]] = None,
               cache_offset=None,
               page_table: Optional[jnp.ndarray] = None,
               layer=None, n_layers: int = 0,
               ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """One attention call.

    * self-attention:  kv_source is None -> k/v from x, RoPE applied.
    * cross-attention: kv_source is the encoder output; no RoPE on k.
    * decode:          cache holds (B, S_max, K, hd); the new k/v rows are
      written at ``cache_offset`` and attention runs over the whole buffer
      with a validity mask supplied by the caller.

    ``policy`` is anything ``as_policy`` accepts (None / QuantRecipe /
    QuantPolicy); ``layer`` may be a traced index from the layer scan.
    """
    policy = as_policy(policy)
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ctx_qkv = LinearCtx("attn_qkv", layer, n_layers)
    ctx_out = LinearCtx("attn_out", layer, n_layers)

    q = policy.linear(ctx_qkv, x, params["wq"], params.get("bq")
                      ).reshape(b, s, h, hd)
    src = x if kv_source is None else kv_source
    k = policy.linear(ctx_qkv, src, params["wk"], params.get("bk"))
    v = policy.linear(ctx_qkv, src, params["wv"], params.get("bv"))
    k = k.reshape(b, k.shape[1], kh, hd)
    v = v.reshape(b, v.shape[1], kh, hd)

    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])

    if cfg.pos == "rope" and kv_source is None:
        q = rope(q, positions, cfg.rope_theta)
        # k rows carry the same per-token positions as q: in decode the
        # caller's ``positions`` already equals the write offset, and under
        # packed (segment-id) prefill each segment restarts from 0 -- the
        # offset-derived arange the cache path used before is only correct
        # for single-segment rows
        k = rope(k, positions, cfg.rope_theta)
    elif cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta)

    new_cache = None
    ctx = None
    if cache is not None and page_table is not None:
        # paged decode (s == 1 only: prefill fills dense buffers that the
        # engine pages in afterwards)
        pos_vec = jnp.broadcast_to(
            jnp.asarray(cache_offset, jnp.int32).reshape(-1), (b,))
        ctx, kf, vf, new_cache = _paged_decode(
            q, k, v, cache, page_table, pos_vec, cfg, policy=policy,
            rules=rules, kv_source=kv_source, out_dtype=x.dtype)
        if ctx is None:
            k, v = kf, vf
    elif cache is not None:
        # decode / incremental: write rows at cache_offset (scalar, or (B,)
        # per-slot offsets under continuous batching), attend over buffer
        if "k_scale" in cache:
            # int8 KV storage (role ``kv_cache``): payload + per-(position,
            # head) scale sidecars.  Capability dispatch: when a backend's
            # attention kernels consume the stored form directly, decode runs
            # the fused quantize+scatter+attend launch and prefill the
            # dequant-prologue flash kernel; otherwise (the bit-compared
            # oracle) quantize the new rows here and dequantize the whole
            # buffer for the attention read.
            kv_spec = policy.kv_spec()
            fused_dec = _fused_kv_ok(policy, rules, kv_source, n_kv_heads=kh)
            fused_pre = _fused_kv_ok(policy, rules, kv_source)
            if fused_dec and s == 1:
                # fused decode: one read of the int8 cache, one int8 row
                # write; the kernel quantizes and scatters this step's rows
                # (decode contract: ``cache_offset`` IS the per-slot count of
                # valid prior rows, matching the caller's validity mask)
                from repro.kernels.decode_attn import (decode_attention,
                                                       decode_attention_spmd)
                pos = jnp.broadcast_to(
                    jnp.asarray(cache_offset, jnp.int32).reshape(-1), (b,))
                qg = q[:, 0].reshape(b, kh, h // kh, hd)
                if rules is not None:
                    ctx, nkq, nks, nvq, nvs = decode_attention_spmd(
                        qg, cache["k"], cache["k_scale"],
                        cache["v"], cache["v_scale"],
                        k[:, 0], v[:, 0], pos,
                        mesh=rules.mesh, kv_axis=rules.axis_map["kv"][0],
                        qmin=kv_spec.qmin, qmax=kv_spec.qmax)
                else:
                    ctx, nkq, nks, nvq, nvs = decode_attention(
                        qg, cache["k"], cache["k_scale"],
                        cache["v"], cache["v_scale"],
                        k[:, 0], v[:, 0], pos,
                        qmin=kv_spec.qmin, qmax=kv_spec.qmax)
                new_cache = {"k": nkq, "v": nvq,
                             "k_scale": nks, "v_scale": nvs}
                ctx = ctx.reshape(b, 1, h * hd)
            else:
                kq, ks = _kv_quant(k, kv_spec)
                vq, vs = _kv_quant(v, kv_spec)
                new_cache = {
                    "k": _cache_update(cache["k"], kq, cache_offset),
                    "v": _cache_update(cache["v"], vq, cache_offset),
                    "k_scale": _cache_update(cache["k_scale"], ks,
                                             cache_offset),
                    "v_scale": _cache_update(cache["v_scale"], vs,
                                             cache_offset),
                }
                if (fused_pre and s > 1 and isinstance(mask, dict)
                        and mask["kind"] == "causal"
                        and isinstance(cache_offset, int)):
                    # int8-KV prefill: flash forward with a dequant prologue
                    # on the stored payloads -- no fp K/V copy of the
                    # max_seq-sized buffer; causal masking hides the
                    # never-written tail (kernels/flash_attn.py)
                    from repro.kernels.flash_attn import flash_attention_fwd_q8
                    ctx = flash_attention_fwd_q8(
                        q, new_cache["k"], new_cache["k_scale"],
                        new_cache["v"], new_cache["v_scale"],
                        causal=True, q_offset=cache_offset)
                    ctx = ctx.reshape(b, s, h * hd)
                else:
                    k = (new_cache["k"].astype(jnp.float32)
                         * _kv_guard(new_cache["k_scale"])).astype(x.dtype)
                    v = (new_cache["v"].astype(jnp.float32)
                         * _kv_guard(new_cache["v_scale"])).astype(x.dtype)
        else:
            ck = _cache_update(cache["k"], k.astype(cache["k"].dtype),
                               cache_offset)
            cv = _cache_update(cache["v"], v.astype(cache["v"].dtype),
                               cache_offset)
            ck = constrain(ck, rules, "batch", "kv_seq", "kv", None)
            cv = constrain(cv, rules, "batch", "kv_seq", "kv", None)
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv

    if ctx is None:
        with jax.named_scope("attention"):
            ctx = _gqa_attend(q, k, v, mask, rules,
                              impl=getattr(cfg, "attention_impl", "xla"))
    # named for the remat policy: saving ctx prunes one full score-chain
    # recompute from the backward (EXPERIMENTS.md Section Perf iter 4)
    ctx = checkpoint_name(ctx, "attn_ctx")
    y = policy.linear(ctx_out, ctx, params["wo"], params.get("bo"))
    return y, new_cache
