"""Decoder-only language model covering dense / MoE / SSM / hybrid / VLM
families: scan-over-layers (compile-time O(1) in depth), remat, chunked
cross-entropy (never materializes (B,S,V) logits), KV-cache prefill/decode.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qpolicy import LinearCtx, as_policy
from repro.models.attention import attn_apply, attn_spec, init_cache
from repro.models.blocks import block_apply, block_spec
from repro.models.common import (ParamSpec, apply_norm, cast_params,
                                 causal_mask, constrain, norm_spec,
                                 prefix_lm_mask, stack_layer_specs)
from repro.models.mlp import mlp_apply, mlp_spec
from repro.models.ssm import init_ssm_state, ssm_dims
from repro.configs.base import ArchConfig

AUX_COEF = 0.01
ZLOSS_COEF = 1e-3


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def shared_block_spec(cfg) -> Dict:
    """zamba2: one attention+MLP block shared across the depth, operating on
    concat(h, input_embedding) in 2*d_model space, projected back to d."""
    d2 = 2 * cfg.d_model
    return {
        "ln1": norm_spec(d2, cfg.norm),
        "attn": attn_spec(cfg, d_in=d2),
        "ln2": norm_spec(d2, cfg.norm),
        "mlp": mlp_spec(cfg, d_in=d2, d_ff=cfg.d_ff),
        "proj": ParamSpec((d2, cfg.d_model), ("embed2", "embed"), "fan_in",
                          scale=1.0 / max(cfg.n_layers, 1)),
    }


def lm_spec(cfg: ArchConfig) -> Dict:
    spec: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_padded, cfg.d_model), ("vocab", "embed"),
                           "normal", 0.02),
    }
    if cfg.pos == "learned":
        spec["pos_embed"] = ParamSpec((cfg.max_seq, cfg.d_model),
                                      (None, "embed"), "normal", 0.01)
    spec["blocks"] = stack_layer_specs(block_spec(cfg), cfg.n_layers)
    spec["final_norm"] = norm_spec(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_padded),
                                    ("embed", "vocab"), "fan_in")
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        spec["shared"] = shared_block_spec(cfg)
    if cfg.family == "vlm":
        # stub frontend: a single linear adapting precomputed patch embeddings
        spec["patch_proj"] = ParamSpec((cfg.d_model, cfg.d_model),
                                       ("embed2", "embed"), "fan_in")
    return spec


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(params, tokens: jnp.ndarray, cfg, positions=None,
                 dtype=None, policy=None) -> jnp.ndarray:
    """Token (+learned position) embedding.  The ``embed`` role governs a
    weight-only qdq of the table (fp under ``from_recipe`` policies unless
    ``include_embeddings`` -- the paper scopes to block linears)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    table = params["embed"]
    if policy is not None:
        table = policy.quantize_weight("embed", table)
    e = jnp.take(table, tokens, axis=0).astype(dtype)
    if cfg.embed_scale:
        e = e * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if cfg.pos == "learned":
        assert positions is not None
        pe = jnp.take(params["pos_embed"], positions, axis=0).astype(dtype)
        e = e + pe
    return e


@jax.named_scope("head")
def logits_chunk(params, h: jnp.ndarray, cfg, policy=None) -> jnp.ndarray:
    """(B, C, d) -> (B, C, V_padded) in fp32, padded tail masked to -inf.
    The ``lm_head`` role governs a weight-only qdq of the head matrix (the
    tied embedding table when ``tie_embeddings``)."""
    if cfg.tie_embeddings:
        table = params["embed"]
        if policy is not None:
            table = policy.quantize_weight("lm_head", table)
        logits = jnp.einsum("bcd,vd->bcv", h, table.astype(h.dtype),
                            preferred_element_type=jnp.float32)
    else:
        head = params["lm_head"]
        if policy is not None:
            head = policy.quantize_weight("lm_head", head)
        logits = jnp.einsum("bcd,dv->bcv", h, head.astype(h.dtype),
                            preferred_element_type=jnp.float32)
    if cfg.vocab_padded > cfg.vocab_size:
        neg = jnp.asarray(-1e30, logits.dtype)
        mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
        logits = jnp.where(mask[None, None, :], logits, neg)
    return logits


def _chunk_len(s: int, target: int) -> int:
    if s <= target:
        return s
    for c in range(target, 0, -1):
        if s % c == 0:
            return c
    return s


@jax.named_scope("head")
def chunked_ce(params, h: jnp.ndarray, labels: jnp.ndarray,
               mask: Optional[jnp.ndarray], cfg, rules,
               policy=None) -> jnp.ndarray:
    """Cross entropy computed in sequence chunks so (B,S,V) never exists.
    Vocab stays sharded ('vocab' -> tensor axis) inside each chunk."""
    b, s, _ = h.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    chunk = _chunk_len(s, cfg.logit_chunk or s)
    n_chunks = s // chunk

    def body(carry, i):
        tot, cnt = carry
        hc = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, 1)
        lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, 1)
        mc = jax.lax.dynamic_slice_in_dim(mask, i * chunk, chunk, 1)
        logits = logits_chunk(params, hc, cfg, policy)
        logits = constrain(logits, rules, "batch", None, "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((logz - gold) * mc.astype(jnp.float32))
        cnt = cnt + jnp.sum(mc.astype(jnp.float32))
        return (tot, cnt), None

    # checkpoint: the backward recomputes each chunk's logits instead of
    # keeping an fp32 (B, chunk, V) slab alive per chunk
    body = jax.checkpoint(body, prevent_cse=False)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2,
                                 jnp.arange(n_chunks))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Layer stack execution
# ---------------------------------------------------------------------------

def _scan_blocks(params, h, cfg, *, policy, rules, positions, mask,
                 caches=None, cache_offset=None, ssm_states=None,
                 decode=False, page_table=None):
    """Homogeneous layer scan.  caches/ssm_states are stacked (L, ...).
    The scanned xs carry the depth index so depth-indexed policy rules can
    select per-layer quantization inside the (layer-invariant) trace.
    ``page_table`` (paged decode) is one table for every layer -- captured
    by the body closure, not scanned."""

    def body(carry, xs):
        hh, aux, z = carry
        bp, cache, sst, li = xs
        hh, ncache, nsst, a, zz = block_apply(
            bp, hh, cfg, policy=policy, rules=rules, positions=positions,
            mask=mask, cache=cache, cache_offset=cache_offset,
            ssm_state=sst, decode=decode, layer=li, page_table=page_table)
        return (hh, aux + a, z + zz), (ncache, nsst)

    if cfg.remat and not decode:
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("attn_ctx"))

    zero = jnp.zeros((), jnp.float32)
    with jax.named_scope("layers"):
        (h, aux, z), (ncaches, nssts) = jax.lax.scan(
            body, (h, zero, zero),
            (params["blocks"], caches, ssm_states,
             jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return h, ncaches, nssts, aux, z


def _shared_attn(params, h, emb0, cfg, *, policy, rules, positions, mask,
                 cache=None, cache_offset=None):
    """zamba2 shared block: operates on concat(h, emb0).  The block's weights
    are shared across depth, so its linears resolve depth-less (layer=None);
    the down-projection is the ``shared_proj`` role."""
    sp = params["shared"]
    x2 = jnp.concatenate([h, emb0], axis=-1)
    x = apply_norm(x2, sp["ln1"], cfg.norm)
    y, ncache = attn_apply(sp["attn"], x, cfg, policy=policy, rules=rules,
                           positions=positions, mask=mask, cache=cache,
                           cache_offset=cache_offset)
    x2 = x2 + y
    x = apply_norm(x2, sp["ln2"], cfg.norm)
    x2 = x2 + mlp_apply(sp["mlp"], x, cfg, policy=policy, rules=rules)
    return h + policy.linear(LinearCtx("shared_proj"), x2, sp["proj"]), ncache


def _hybrid_blocks(params, h, cfg, *, policy, rules, positions, mask,
                   emb0, caches=None, cache_offset=None, ssm_states=None,
                   decode=False):
    """zamba2: groups of `hybrid_attn_every` mamba layers, each followed by
    the shared attention block.  caches: (G, B, S, K, hd); ssm stacked (L,...)."""
    per = cfg.hybrid_attn_every
    groups = cfg.n_layers // per
    grouped = jax.tree_util.tree_map(
        lambda x: x.reshape(groups, per, *x.shape[1:]), params["blocks"])
    g_ssm = (None if ssm_states is None else jax.tree_util.tree_map(
        lambda x: x.reshape(groups, per, *x.shape[1:]), ssm_states))
    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32).reshape(groups, per)

    def group_body(carry, xs):
        hh, aux, z = carry
        gparams, gssm, gcache, g_layers = xs

        def inner(c, xs2):
            hhh, a2, z2 = c
            bp, sst, li = xs2
            hhh, _, nsst, a, zz = block_apply(
                bp, hhh, cfg, policy=policy, rules=rules, positions=positions,
                mask=None, ssm_state=sst, decode=decode, layer=li)
            return (hhh, a2 + a, z2 + zz), nsst

        (hh, aux, z), nssm = jax.lax.scan(inner, (hh, aux, z),
                                          (gparams, gssm, g_layers))
        hh, ncache = _shared_attn(params, hh, emb0, cfg, policy=policy,
                                  rules=rules, positions=positions, mask=mask,
                                  cache=gcache, cache_offset=cache_offset)
        return (hh, aux, z), (nssm, ncache)

    if cfg.remat and not decode:
        group_body = jax.checkpoint(
            group_body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("attn_ctx"))

    zero = jnp.zeros((), jnp.float32)
    with jax.named_scope("layers"):
        (h, aux, z), (nssm, ncaches) = jax.lax.scan(
            group_body, (h, zero, zero), (grouped, g_ssm, caches, layer_ids))
    if nssm is not None:
        nssm = jax.tree_util.tree_map(
            lambda x: x.reshape(cfg.n_layers, *x.shape[2:]), nssm)
    return h, ncaches, nssm, aux, z


def run_stack(params, h, cfg, **kw):
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        assert kw.pop("page_table", None) is None, \
            "paged KV is dense/moe-family only"
        return _hybrid_blocks(params, h, cfg, **kw)
    kw.pop("emb0", None)
    return _scan_blocks(params, h, cfg, **kw)


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------

def lm_loss(params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig, *,
            policy=None, rules=None,
            rng: Optional[jax.Array] = None) -> Tuple[jnp.ndarray, Dict]:
    """batch: {"tokens": (B, S+1) int32[, "patches": (B,P,d)]}.
    Returns (loss, metrics).  ``policy`` is anything ``as_policy`` accepts
    (None / QuantRecipe / QuantPolicy / policy string)."""
    policy = as_policy(policy)
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    b, s_text = inp.shape
    positions_text = jnp.broadcast_to(jnp.arange(s_text), (b, s_text))

    if cfg.family == "vlm":
        patches = batch["patches"].astype(dtype)
        patches = policy.linear(LinearCtx("patch_proj"), patches,
                                params["patch_proj"])
        p = patches.shape[1]
        positions = jnp.broadcast_to(jnp.arange(p + s_text), (b, p + s_text))
        e = embed_tokens(params, inp, cfg, positions=positions_text + p,
                         dtype=dtype, policy=policy)
        h = jnp.concatenate([patches, e], axis=1)
        mask = {"kind": "prefix", "prefix": p}
    else:
        positions = positions_text
        h = embed_tokens(params, inp, cfg, positions=positions, dtype=dtype,
                         policy=policy)
        mask = {"kind": "causal"} if cfg.family != "ssm" else None

    h = constrain(h, rules, "batch", "seq", None)
    h, _, _, aux, z = run_stack(params, h, cfg, policy=policy, rules=rules,
                                positions=positions, mask=mask, emb0=h)
    h = apply_norm(h, params["final_norm"], cfg.norm)

    if cfg.family == "vlm":
        h = h[:, h.shape[1] - s_text:, :]
    loss_mask = batch.get("loss_mask")
    ce = chunked_ce(params, h, labels, loss_mask, cfg, rules, policy)
    total = ce
    metrics = {"ce": ce}
    if cfg.n_experts:
        total = total + AUX_COEF * aux / cfg.n_layers + \
            ZLOSS_COEF * z / cfg.n_layers
        metrics.update({"moe_aux": aux / cfg.n_layers,
                        "moe_z": z / cfg.n_layers})
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_seq: int, dtype,
                kv_spec=None):
    """Stacked decode state for the whole stack.  ``kv_spec`` (from
    ``policy.kv_spec()``) selects int8 KV storage; fp is the default."""
    caches = None
    ssm_states = None
    if cfg.family in ("dense", "moe", "vlm"):
        one = init_cache(cfg, batch, max_seq, dtype, kv_spec=kv_spec)
        caches = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), one)
    elif cfg.family == "ssm":
        one = init_ssm_state(cfg, batch, dtype)
        ssm_states = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), one)
    elif cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.hybrid_attn_every
        one = init_cache(cfg, batch, max_seq, dtype, kv_spec=kv_spec)
        caches = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (groups,) + x.shape).copy(), one)
        s_one = init_ssm_state(cfg, batch, dtype)
        ssm_states = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(),
            s_one)
    return caches, ssm_states


def lm_prefill(params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig, *,
               policy=None, rules=None, max_seq: Optional[int] = None,
               last_pos=None, segments=None):
    """Process the full prompt; returns (last_logits (B,V), caches, ssm_states).
    Cache buffers sized to max_seq (defaults to prompt length).

    ``last_pos`` selects which position's logits are returned: None (default)
    takes the final row; a scalar or per-row (B,) index supports right-padded
    prompts (the serving engine pads prompts to bucketed lengths -- causal
    masking makes the pad tail invisible to positions <= last_pos); an
    (M, 2) array of ``(row, col)`` pairs gathers one hidden vector per packed
    prompt (returns (M, V) logits, M independent of B).  Indices are into the
    full hidden sequence (VLM callers account for patch rows).

    ``segments`` (B, S) int32 packs multiple prompts into one row: equal ids
    mark one prompt's span, -1 marks padding.  Positions restart at each
    segment start and the attention mask is ``same-segment AND causal``, so
    every packed prompt computes exactly what it would alone (pad/binary
    neighbours contribute exact zeros through the softmax) -- the chunked-
    prefill idiom (MaxText ``prefill_concat`` segment-id masks).  Decoder-
    only attention families; requires ``segments`` spans to be contiguous."""
    policy = as_policy(policy)
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    tokens = batch["tokens"]
    b = tokens.shape[0]
    if segments is not None and (cfg.family not in ("dense", "moe")
                                 or (cfg.family == "vlm")):
        raise NotImplementedError(
            "packed (segment-id) prefill is attention-family only")
    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(dtype)
        patches = policy.linear(LinearCtx("patch_proj"), patches,
                                params["patch_proj"])
        p = patches.shape[1]
        s = p + tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        e = embed_tokens(params, tokens, cfg,
                         positions=positions[:, p:], dtype=dtype,
                         policy=policy)
        h = jnp.concatenate([patches, e], axis=1)
        max_seq = max_seq or s
        mask_full = {"kind": "prefix", "prefix": p}
    else:
        s = tokens.shape[1]
        max_seq = max_seq or s
        if segments is not None:
            seg = jnp.asarray(segments, jnp.int32)
            t = jnp.arange(s)
            is_start = jnp.concatenate(
                [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
            starts = jax.lax.cummax(
                jnp.where(is_start, t[None, :], 0), axis=1)
            positions = t[None, :] - starts          # restart per segment
            segk = jnp.pad(seg, ((0, 0), (0, max_seq - s)),
                           constant_values=-1)
            mask_full = ((seg[:, :, None] == segk[:, None, :])
                         & (t[:, None] >= jnp.arange(max_seq)[None, :])[None]
                         & (seg >= 0)[:, :, None])   # (B, S, max_seq)
        else:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            mask_full = {"kind": "causal"}
        h = embed_tokens(params, tokens, cfg, positions=positions, dtype=dtype,
                         policy=policy)
    h = constrain(h, rules, "batch", "seq", None)

    caches, ssm_states = init_caches(cfg, b, max_seq, dtype,
                                     kv_spec=policy.kv_spec())
    mask = None
    if cfg.family != "ssm":
        mask = mask_full
    h, caches, ssm_states, _, _ = run_stack(
        params, h, cfg, policy=policy, rules=rules, positions=positions,
        mask=mask, caches=caches, cache_offset=0, ssm_states=ssm_states,
        emb0=h)
    h = apply_norm(h, params["final_norm"], cfg.norm)
    if last_pos is None:
        hc = h[:, -1:, :]
    else:
        lp = jnp.asarray(last_pos, jnp.int32)
        if lp.ndim == 0:
            hc = jax.lax.dynamic_slice_in_dim(h, lp, 1, axis=1)
        elif lp.ndim == 2:                       # (M, 2) packed (row, col)
            hc = h[lp[:, 0], lp[:, 1]][:, None, :]
        else:                                    # (B,) per-row last indices
            hc = h[jnp.arange(b)[:, None], lp[:, None], :]
    logits = logits_chunk(params, hc, cfg, policy)[:, 0, :]
    return logits, caches, ssm_states


def lm_decode(params, caches, ssm_states, token: jnp.ndarray,
              pos: jnp.ndarray, cfg: ArchConfig, *, policy=None, rules=None,
              page_table=None):
    """One-token decode.  token: (B,1) int32; pos: the number of tokens
    already in the cache -- a scalar int32 (uniform batch, the legacy path)
    or a (B,) vector of per-slot positions (continuous batching: each slot
    writes its cache row and masks its history independently).
    ``page_table`` (B, max_pages) switches the cache interpretation to paged
    pools (L, n_pages, page_size, K, hd) shared across slots (repro.infer.
    pages); the logical row space is then ``max_pages * page_size`` long.
    Returns (logits (B,V), caches, ssm_states)."""
    policy = as_policy(policy)
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    b = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        positions = pos[:, None]                            # (B, 1)
    else:
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    h = embed_tokens(params, token, cfg, positions=positions, dtype=dtype,
                     policy=policy)

    mask = None
    if cfg.family != "ssm":
        leaf = jax.tree_util.tree_leaves(caches)[0]
        if page_table is not None:             # (L, P, page, K, hd) pools
            kv_len = page_table.shape[1] * leaf.shape[2]
        else:
            kv_len = leaf.shape[2]             # (L, B, S, K, hd)
        if pos.ndim == 1:                                   # (B, 1, kv_len)
            mask = (jnp.arange(kv_len)[None, None, :]
                    <= pos[:, None, None])
        else:
            mask = (jnp.arange(kv_len) <= pos)[None, :]     # (1, kv_len)
    h, caches, ssm_states, _, _ = run_stack(
        params, h, cfg, policy=policy, rules=rules, positions=positions,
        mask=mask, caches=caches, cache_offset=pos, ssm_states=ssm_states,
        decode=True, emb0=h, page_table=page_table)
    h = apply_norm(h, params["final_norm"], cfg.norm)
    logits = logits_chunk(params, h, cfg, policy)[:, 0, :]
    return logits, caches, ssm_states
