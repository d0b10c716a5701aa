"""Mixture-of-Experts with capacity-based token dispatch.

Three execution modes, chosen by divisibility against the tensor axis:

* ``local``       : no mesh / tp==1.  Pure scatter-dispatch on the device.
* ``ep_alltoall`` : E % tp == 0 and tokens split over tp.  Tokens are
  sharded along the tensor axis for routing, dispatched to expert-owner ranks
  with ``all_to_all``, FFN'd, and returned (GShard/Switch pattern).  Used for
  training shapes (phi3.5-moe: one expert per rank on the 16-way axis).
* ``ep_masked``   : E % tp == 0 but too few tokens to split (decode).  Every
  rank holds its experts, dispatches the full (replicated) token set against
  its local experts only, and the combine is a psum.
* ``ff_sharded``  : E does not divide tp (granite's 40 experts on a 16-way
  axis).  Expert weights are tensor-sharded on d_ff inside each expert;
  dispatch is replicated across tp and the down-projection psums partials.

Gradients flow through gate weights via the softmax (standard top-k routing);
dropped tokens (beyond capacity) fall back to the residual stream.  A
Switch-style load-balance aux loss and router z-loss are returned.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.qpolicy import LinearCtx, as_policy
from repro.models.common import ACT_FNS, ParamSpec


def moe_spec(cfg) -> Dict[str, ParamSpec]:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "w_router": ParamSpec((d, e), ("embed", "expert"), "fan_in"),
        "w_gate": ParamSpec((e, d, ff), ("expert", "embed", "mlp"), "fan_in"),
        "w_up": ParamSpec((e, d, ff), ("expert", "embed", "mlp"), "fan_in"),
        "w_down": ParamSpec((e, ff, d), ("expert", "mlp", "embed"), "fan_in",
                            scale=1.0 / max(cfg.n_layers, 1)),
    }


def _route(x2: jnp.ndarray, w_router: jnp.ndarray, cfg, policy,
           ctx_router: LinearCtx
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router in fp32 (role ``router``; fp under from_recipe policies --
    quantizing the router is a beyond-paper ablation)."""
    logits = policy.linear(ctx_router, x2.astype(jnp.float32),
                           w_router.astype(jnp.float32))       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_logits, top_e = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top_logits, axis=-1)                # renormalized
    # Switch aux loss: E * sum_e fraction_routed_e * mean_prob_e
    sel = jax.nn.one_hot(top_e[:, 0], cfg.n_experts, dtype=jnp.float32)
    aux = cfg.n_experts * jnp.sum(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, top_e, aux, z_loss


def _dispatch_indices(top_e: jnp.ndarray, n_experts: int, capacity: int,
                      k: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Slot assignment with first-come-first-served capacity.

    Returns (slot (T*k,), keep (T*k,), token_idx (T*k,)); dropped pairs get
    the dummy slot n_experts*capacity.
    """
    t = top_e.shape[0]
    flat_e = top_e.reshape(-1)                                  # (T*k,)
    onehot = (flat_e[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                        # (T*k, E)
    mypos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = mypos < capacity
    slot = jnp.where(keep, flat_e * capacity + mypos, n_experts * capacity)
    token_idx = jnp.repeat(jnp.arange(t), k)
    return slot, keep, token_idx


def _expert_ffn(buf: jnp.ndarray, params, cfg, policy, layer,
                n_layers: int) -> jnp.ndarray:
    """buf: (E_local, C, d) -> (E_local, C, d).  vmapped policy linears so
    per-channel/per-token scales stay per-expert."""
    act = ACT_FNS[cfg.act]
    ctx_up = LinearCtx("mlp_up", layer, n_layers)
    ctx_down = LinearCtx("mlp_down", layer, n_layers)

    def one(xb, wg, wu, wd):
        g = policy.linear(ctx_up, xb, wg)
        u = policy.linear(ctx_up, xb, wu)
        return policy.linear(ctx_down, act(g) * u, wd)

    return jax.vmap(one)(buf, params["w_gate"], params["w_up"], params["w_down"])


def _local_moe(x2: jnp.ndarray, params, cfg, policy, capacity: int,
               layer, n_layers: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Capacity dispatch + expert FFN on one device's token set.  Used both
    standalone (no mesh) and as the per-shard body of the ff_sharded mode."""
    t, d = x2.shape
    e, k = cfg.n_experts, cfg.top_k
    gates, top_e, aux, z_loss = _route(x2, params["w_router"], cfg, policy,
                                       LinearCtx("router", layer, n_layers))
    slot, keep, token_idx = _dispatch_indices(top_e, e, capacity, k)

    rows = jnp.take(x2, token_idx, axis=0)                       # (T*k, d)
    buf = jnp.zeros((e * capacity + 1, d), x2.dtype)
    buf = buf.at[slot].set(rows, mode="drop", unique_indices=True)
    h = _expert_ffn(buf[:e * capacity].reshape(e, capacity, d), params, cfg,
                    policy, layer, n_layers)
    h = h.reshape(e * capacity, -1)
    out_rows = jnp.take(jnp.concatenate(
        [h, jnp.zeros((1, h.shape[-1]), h.dtype)], axis=0), slot, axis=0)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x2.dtype)
    y = jnp.zeros((t, h.shape[-1]), x2.dtype)
    y = y.at[token_idx].add(out_rows * w[:, None])
    return y, aux, z_loss


def _capacity(tokens: int, cfg) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(cap, cfg.top_k)


MAX_DISPATCH_TOKENS = 16384


def _local_moe_chunked(x2, params, cfg, policy, layer, n_layers):
    """Token-chunked dispatch: bounds the (E*C, d) scatter buffers at train
    shapes (capacity is per-chunk -- standard grouped dispatch semantics)."""
    t, d = x2.shape
    if t <= MAX_DISPATCH_TOKENS:
        return _local_moe(x2, params, cfg, policy, _capacity(t, cfg),
                          layer, n_layers)
    chunk = MAX_DISPATCH_TOKENS
    while t % chunk:
        chunk //= 2
    cap = _capacity(chunk, cfg)

    def body(_, xc):
        y, aux, z = _local_moe(xc, params, cfg, policy, cap, layer, n_layers)
        return None, (y, aux, z)

    body = jax.checkpoint(body, prevent_cse=False)
    xcs = x2.reshape(t // chunk, chunk, d)
    _, (ys, auxs, zs) = jax.lax.scan(body, None, xcs)
    return ys.reshape(t, d), jnp.mean(auxs), jnp.mean(zs)


def _alltoall_moe(x2, params, cfg, policy, tp_axis: str, layer, n_layers):
    """Per-shard body (tokens already split over tp_axis; expert weights
    already sharded over tp_axis): route locally, all_to_all to expert
    owners, FFN, all_to_all back, combine."""
    tp = jax.lax.axis_size(tp_axis)
    t_loc, d = x2.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // tp
    cap = _capacity(t_loc, cfg)

    gates, top_e, aux, z_loss = _route(x2, params["w_router"], cfg, policy,
                                       LinearCtx("router", layer, n_layers))
    slot, keep, token_idx = _dispatch_indices(top_e, e, cap, k)
    rows = jnp.take(x2, token_idx, axis=0)
    send = jnp.zeros((e * cap + 1, d), x2.dtype)
    send = send.at[slot].set(rows, mode="drop", unique_indices=True)
    send = send[:e * cap].reshape(tp, e_loc * cap, d)
    # (tp, rows, d) -> each rank receives its expert block from every source
    recv = jax.lax.all_to_all(send, tp_axis, split_axis=0, concat_axis=0,
                              tiled=False)                       # (tp, e_loc*cap, d)
    ffn_in = (recv.reshape(tp, e_loc, cap, d)
              .transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, d))
    # expert weights arrive pre-sharded: (e_loc, d, ff) per rank
    h = _expert_ffn(ffn_in, params, cfg, policy, layer,
                    n_layers)                                    # (e_loc, tp*cap, d)
    back = (h.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
            .reshape(tp, e_loc * cap, d))
    got = jax.lax.all_to_all(back, tp_axis, split_axis=0, concat_axis=0,
                             tiled=False).reshape(e * cap, d)
    got = jnp.concatenate([got, jnp.zeros((1, d), got.dtype)], axis=0)
    out_rows = jnp.take(got, slot, axis=0)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x2.dtype)
    y = jnp.zeros((t_loc, d), x2.dtype).at[token_idx].add(out_rows * w[:, None])
    return y, aux, z_loss


def moe_apply(params, x: jnp.ndarray, cfg, *,
              policy=None, rules=None, layer=None, n_layers: int = 0
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss, z_loss)."""
    policy = as_policy(policy)
    b, s, d = x.shape
    if rules is None or rules.tp_size == 1:
        y, aux, z = _local_moe_chunked(x.reshape(-1, d), params, cfg, policy,
                                       layer, n_layers)
        return y.reshape(b, s, d), aux, z

    mesh = rules.mesh
    dp_axes, tp_axis = rules.dp_axes, rules.tp_axis
    tp = rules.tp_size

    if tp_axis in dp_axes:
        # flat-FSDP mapping: every rank dispatches its own token slice
        # against the (boundary-gathered) full expert set -- no EP collective.
        # Shard the batch over the longest dp-axis prefix that divides it
        # (multi-pod: global_batch 256 < 512 chips -> the model axis ranks
        # replicate the dispatch; correct, compiles, mildly wasteful --
        # MoE archs prefer the TP/EP mapping anyway, see EXPERIMENTS §Perf).
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        use = []
        prod = 1
        for a in dp_axes:
            if b % (prod * sizes[a]) == 0:
                use.append(a)
                prod *= sizes[a]
            else:
                break
        dp_axes = tuple(use) if use else (dp_axes[0],)

        def body(xb, p):
            xl = xb.reshape(-1, d)
            y, aux, z = _local_moe_chunked(xl, p, cfg, policy, layer, n_layers)
            return y.reshape(xb.shape), aux, z

        in_specs = (P(dp_axes, None, None), {
            "w_router": P(None, None), "w_gate": P(None, None, None),
            "w_up": P(None, None, None), "w_down": P(None, None, None)})
        out_specs = (P(dp_axes, None, None), P(), P())
        y, aux, z = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs, check_vma=False)(
            x, {k: params[k] for k in
                ("w_router", "w_gate", "w_up", "w_down")})
        return y, jnp.mean(aux), jnp.mean(z)
    tokens_dp = (b // rules.dp_size) * s       # tokens per dp shard

    if cfg.n_experts % tp == 0 and s % tp == 0:
        # --- all-to-all expert parallelism (training shapes) --------------
        def body(xb, p):
            xl = xb.reshape(-1, d)
            y, aux, z = _alltoall_moe(xl, p, cfg, policy, tp_axis, layer,
                                      n_layers)
            return (y.reshape(xb.shape),
                    jax.lax.pmean(aux, tp_axis), jax.lax.pmean(z, tp_axis))

        in_specs = (P(dp_axes, tp_axis, None), {
            "w_router": P(None, None),
            "w_gate": P(tp_axis, None, None),
            "w_up": P(tp_axis, None, None),
            "w_down": P(tp_axis, None, None),
        })
        out_specs = (P(dp_axes, tp_axis, None), P(), P())
        y, aux, z = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs, check_vma=False)(
            x, {k: params[k] for k in
                ("w_router", "w_gate", "w_up", "w_down")})
        return y, jnp.mean(aux), jnp.mean(z)

    if cfg.n_experts % tp == 0:
        # --- masked EP (decode: tokens replicated over tp) -----------------
        e_loc = cfg.n_experts // tp
        cap = _capacity(b * s // rules.dp_size, cfg)

        def body(xb, p):
            xl = xb.reshape(-1, d)
            gates, top_e, aux, z = _route(xl, p["w_router"], cfg, policy,
                                          LinearCtx("router", layer, n_layers))
            my = jax.lax.axis_index(tp_axis)
            # keep only pairs routed to my expert block (weights arrive
            # pre-sharded: p["w_gate"] is (e_loc, d, ff) on this rank)
            rel = top_e - my * e_loc
            mine = (rel >= 0) & (rel < e_loc)
            loc_e = jnp.where(mine, rel, e_loc)     # e_loc = dummy expert
            slot, keep, token_idx = _dispatch_indices(
                loc_e, e_loc + 1, cap, cfg.top_k)
            keep = keep & mine.reshape(-1)
            slot = jnp.where(keep, slot, (e_loc + 1) * cap)
            rows = jnp.take(xl, token_idx, axis=0)
            buf = jnp.zeros(((e_loc + 1) * cap + 1, d), xl.dtype)
            buf = buf.at[slot].set(rows, mode="drop", unique_indices=True)
            h = _expert_ffn(buf[:e_loc * cap].reshape(e_loc, cap, d),
                            p, cfg, policy, layer,
                            n_layers).reshape(e_loc * cap, d)
            h = jnp.concatenate(
                [h, jnp.zeros((1 + cap, d), h.dtype)], axis=0)
            out_rows = jnp.take(h, jnp.minimum(slot, e_loc * cap + cap), axis=0)
            w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(xl.dtype)
            y = jnp.zeros((xl.shape[0], d), xl.dtype)
            y = y.at[token_idx].add(out_rows * w[:, None])
            y = jax.lax.psum(y, tp_axis)
            return (y.reshape(xb.shape), jax.lax.pmean(aux, tp_axis),
                    jax.lax.pmean(z, tp_axis))

        in_specs = (P(dp_axes, None, None), {
            "w_router": P(None, None), "w_gate": P(tp_axis, None, None),
            "w_up": P(tp_axis, None, None), "w_down": P(tp_axis, None, None)})
        out_specs = (P(dp_axes, None, None), P(), P())
        y, aux, z = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs, check_vma=False)(
            x, {k: params[k] for k in
                ("w_router", "w_gate", "w_up", "w_down")})
        return y, jnp.mean(aux), jnp.mean(z)

    # --- ff_sharded: experts do not divide tp (granite 40e on 16) ---------

    def body(xb, p):
        xl = xb.reshape(-1, d)
        y, aux, z = _local_moe_chunked(xl, p, cfg, policy, layer, n_layers)
        y = jax.lax.psum(y, tp_axis)
        return (y.reshape(xb.shape), jax.lax.pmean(aux, tp_axis),
                jax.lax.pmean(z, tp_axis))

    in_specs = (P(dp_axes, None, None), {
        "w_router": P(None, None),
        "w_gate": P(None, None, tp_axis),
        "w_up": P(None, None, tp_axis),
        "w_down": P(None, tp_axis, None)})
    out_specs = (P(dp_axes, None, None), P(), P())
    y, aux, z = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, check_vma=False)(
        x, {k: params[k] for k in ("w_router", "w_gate", "w_up", "w_down")})
    return y, jnp.mean(aux), jnp.mean(z)
