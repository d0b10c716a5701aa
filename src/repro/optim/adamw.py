"""AdamW with optionally-quantized moments (paper Section 4.4).

The moments are stored between steps in the representation selected by the
recipe (fp / fake-quantized fp / real int8+scales) and decoded for the update
-- exactly the paper's methodology ("the quantized values of each state are
stored until the next training iteration, then dequantized and used for
Adam's update").

Two update paths share those semantics:

* the reference **loop**: one Python iteration per leaf, decode -> update ->
  encode as unfused XLA ops (the bit-compared oracle, and the only path for
  fp/fake storage, non-blockwise moment codecs, and non-quantizable leaves);
* the fused **kernel** path (kernels/opt_update.py): quantizable leaves with
  blockwise int8-stored moments are flattened into padded (nblocks,
  block_size) buckets matching ``core.qadam``'s codec layout and the whole
  update runs as one Pallas launch per (dtype) bucket -- one HBM read and one
  write per buffer instead of ~6.  Default on TPU for ``state_storage="int"``;
  ``REPRO_FUSED_ADAM=1/0`` forces it either way (tests pin ``1`` to exercise
  the kernel in interpret mode on CPU).

Built from scratch (optax is not available in this environment).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qadam
from repro.core.qconfig import QuantRecipe


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 6e-4                 # paper Appendix A
    b1: float = 0.9
    b2: float = 0.95                 # nanoGPT-style (paper follows nanoGPT)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 300_000       # paper: 300k steps
    min_lr_ratio: float = 0.0        # cosine decays to ~0 (paper: lr < 1e-6)
    state_storage: str = "fake"      # fake (paper) | int (production int8)


class AdamState(NamedTuple):
    step: jnp.ndarray                # int32 scalar
    m1: Any                          # pytree: fp arrays or qadam.QState
    m2: Any


def lr_schedule(step: jnp.ndarray, cfg: OptConfig) -> jnp.ndarray:
    """Linear warmup + half-cycle cosine (paper Appendix A)."""
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    scale = cfg.min_lr_ratio + (1.0 - cfg.min_lr_ratio) * cos
    return cfg.lr * warm * scale


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def _clip_scale(gnorm: jnp.ndarray, max_norm: float) -> jnp.ndarray:
    """Global-norm clip factor (shared by clip_by_global_norm and the
    streamed scalar of the fused/loop update paths)."""
    return jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))


def clip_by_global_norm(grads, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    gn = global_norm(grads)
    scale = _clip_scale(gn, max_norm)
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale), grads), gn


def init_adam_state(params, recipe: Optional[QuantRecipe],
                    cfg: OptConfig) -> AdamState:
    recipe = recipe or QuantRecipe()
    m1 = jax.tree_util.tree_map(
        lambda p: qadam.init_state(p, recipe.adam_m1, cfg.state_storage),
        params)
    m2 = jax.tree_util.tree_map(
        lambda p: qadam.init_state(p, recipe.adam_m2, cfg.state_storage),
        params)
    return AdamState(step=jnp.zeros((), jnp.int32), m1=m1, m2=m2)


def fused_adam_enabled() -> bool:
    """Fused kernel path default: on where the kernel compiles (TPU);
    ``REPRO_FUSED_ADAM=1/0`` forces the choice either way (the loop stays the
    oracle; tests pin ``1`` to run the kernel in interpret mode on CPU)."""
    force = os.environ.get("REPRO_FUSED_ADAM", "")
    if force in ("0", "1"):
        return force == "1"
    return jax.default_backend() == "tpu"


def opt_path_desc(recipe, cfg: OptConfig) -> str:
    """One-word-ish description of the optimizer update path this (recipe,
    opt config, host) combination actually runs -- the ``opt=`` segment of
    ``train/step.train_path_summary``."""
    recipe = recipe or QuantRecipe()
    m1, m2 = recipe.adam_m1, recipe.adam_m2
    if m1 is None and m2 is None:
        return "fp-loop"
    if cfg.state_storage != "int":
        return "fake-loop"
    if qadam.fused_pair_eligible(m1, m2) and fused_adam_enabled():
        return f"int8-fused(b{m1.block_size})"
    return "int8-loop"


def _leaf_update(p, gf, m1, m2, lr, c1, c2, cfg: OptConfig):
    """Decoded-moment AdamW update for one leaf (shared math of both paths'
    reference semantics).  Returns (new_p, new_m1, new_m2, delta) with
    ``delta`` the applied fp32 parameter step (for the update_norm stat)."""
    m1 = cfg.b1 * m1 + (1.0 - cfg.b1) * gf
    m2 = cfg.b2 * m2 + (1.0 - cfg.b2) * jnp.square(gf)
    upd = (m1 / c1) / (jnp.sqrt(m2 / c2) + cfg.eps)
    pf = p.astype(jnp.float32)
    if cfg.weight_decay and p.ndim >= 2:
        upd = upd + cfg.weight_decay * pf
    delta = lr * upd
    return (pf - delta).astype(p.dtype), m1, m2, delta


def _fused_bucket(idxs: List[int], p_leaves, g_leaves, m1_leaves, m2_leaves,
                  clip_scale, lr, c1, c2, cfg: OptConfig, recipe):
    """Run one fused-kernel launch over the leaves in ``idxs`` (same param
    dtype, globally-shared moment specs) and scatter results back.  Returns
    (new_p, new_m1, new_m2) keyed by leaf index, plus the bucket's
    sum ||delta||^2."""
    from repro.kernels import opt_update as _ok      # lazy: pallas import

    m1_spec, m2_spec = recipe.adam_m1, recipe.adam_m2
    bs = m1_spec.block_size
    nblocks = []
    for i in idxs:
        (nb, _), _ = qadam.blockwise_state_shapes(p_leaves[i].shape, m1_spec)
        # codec invariant: stored states are already in blockwise layout
        assert m1_leaves[i].q.shape == (nb, bs), (m1_leaves[i].q.shape, nb, bs)
        assert m2_leaves[i].q.shape == (nb, bs), (m2_leaves[i].q.shape, nb, bs)
        nblocks.append(nb)

    g_cat = jnp.concatenate(
        [qadam.flatten_blocks(g_leaves[i].astype(jnp.float32), bs)
         for i in idxs])
    p_cat = jnp.concatenate(
        [qadam.flatten_blocks(p_leaves[i], bs) for i in idxs])
    cat = lambda part: jnp.concatenate([getattr(m, part)
                                        for m in (m1_leaves[i] for i in idxs)])
    cat2 = lambda part: jnp.concatenate([getattr(m, part)
                                         for m in (m2_leaves[i] for i in idxs)])
    q1, s1, z1 = cat("q"), cat("scale"), cat("zero")
    q2, s2, z2 = cat2("q"), cat2("scale"), cat2("zero")

    rows = g_cat.shape[0]
    br = _ok.tile_rows()
    pad = (-rows) % br
    if pad:
        # fully-padded rows: 0 payloads + 0 scales decode to 0, update to 0,
        # and the encode guard keeps their fresh scales finite (scale==0 is
        # only ever multiplied, never divided by).
        zpad = lambda a: jnp.pad(a, ((0, pad), (0, 0)))
        g_cat, p_cat = zpad(g_cat), zpad(p_cat)
        q1, s1, z1 = zpad(q1), zpad(s1), zpad(z1)
        q2, s2, z2 = zpad(q2), zpad(s2), zpad(z2)

    scalars = jnp.stack([
        clip_scale.astype(jnp.float32), lr.astype(jnp.float32),
        jnp.float32(cfg.b1), jnp.float32(cfg.b2), jnp.float32(cfg.eps),
        jnp.float32(cfg.weight_decay), c1.astype(jnp.float32),
        c2.astype(jnp.float32)])

    p_new, m1_new, m2_new, sumsq = _ok.fused_adamw_blocks(
        g_cat, p_cat, q1, s1, z1, q2, s2, z2, scalars,
        m1_codec=_ok.codec_of(m1_spec), m2_codec=_ok.codec_of(m2_spec),
        weight_decay=bool(cfg.weight_decay), block_rows=min(br, rows + pad))

    out_p, out_m1, out_m2 = {}, {}, {}
    off = 0
    for i, nb in zip(idxs, nblocks):
        sl = slice(off, off + nb)
        out_p[i] = qadam.unflatten_blocks(p_new[sl], p_leaves[i].shape)
        out_m1[i] = qadam.QState(m1_new[0][sl], m1_new[1][sl], m1_new[2][sl])
        out_m2[i] = qadam.QState(m2_new[0][sl], m2_new[1][sl], m2_new[2][sl])
        off += nb
    return out_p, out_m1, out_m2, sumsq


def adamw_update(params, grads, state: AdamState, cfg: OptConfig,
                 recipe: Optional[QuantRecipe] = None
                 ) -> Tuple[Any, AdamState, Dict[str, jnp.ndarray]]:
    """One AdamW step.  params fp32 master; grads any float dtype.
    Returns (new_params, new_state, stats)."""
    recipe = recipe or QuantRecipe()
    m1_spec, m2_spec = recipe.adam_m1, recipe.adam_m2
    gnorm = global_norm(grads)
    clip_scale = _clip_scale(gnorm, cfg.grad_clip)
    step = state.step + 1
    lr = lr_schedule(step, cfg)
    c1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    c2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m1_leaves = treedef.flatten_up_to(state.m1)
    m2_leaves = treedef.flatten_up_to(state.m2)
    n = len(p_leaves)

    fused_ok = (fused_adam_enabled() and cfg.state_storage == "int"
                and qadam.fused_pair_eligible(m1_spec, m2_spec))
    fused_idx = [i for i in range(n)
                 if fused_ok and qadam.quantizable(p_leaves[i])
                 and isinstance(m1_leaves[i], qadam.QState)
                 and isinstance(m2_leaves[i], qadam.QState)]

    new_p: List[Any] = [None] * n
    new_m1: List[Any] = [None] * n
    new_m2: List[Any] = [None] * n
    upd_sumsq = jnp.zeros((), jnp.float32)

    # --- fused path: one kernel launch per param dtype over all its leaves.
    buckets: Dict[str, List[int]] = {}
    for i in fused_idx:
        buckets.setdefault(str(p_leaves[i].dtype), []).append(i)
    for idxs in buckets.values():
        out_p, out_m1, out_m2, sumsq = _fused_bucket(
            idxs, p_leaves, g_leaves, m1_leaves, m2_leaves,
            clip_scale, lr, c1, c2, cfg, recipe)
        upd_sumsq = upd_sumsq + sumsq
        for i in idxs:
            new_p[i], new_m1[i], new_m2[i] = out_p[i], out_m1[i], out_m2[i]

    # --- reference loop: decode -> update -> encode, one leaf at a time.
    for i in range(n):
        if new_p[i] is not None:
            continue
        p, g = p_leaves[i], g_leaves[i]
        gf = g.astype(jnp.float32) * clip_scale
        m1 = qadam.decode(m1_leaves[i], m1_spec, p.shape)
        m2 = qadam.decode(m2_leaves[i], m2_spec, p.shape)
        new_p[i], m1, m2, delta = _leaf_update(p, gf, m1, m2, lr, c1, c2, cfg)
        upd_sumsq = upd_sumsq + jnp.sum(jnp.square(delta))
        new_m1[i] = qadam.encode(m1, m1_spec, cfg.state_storage)
        new_m2[i] = qadam.encode(m2, m2_spec, cfg.state_storage)

    stats = {"lr": lr, "grad_norm": gnorm,
             "update_norm": jnp.sqrt(upd_sumsq)}
    return (jax.tree_util.tree_unflatten(treedef, new_p),
            AdamState(step=step,
                      m1=jax.tree_util.tree_unflatten(treedef, new_m1),
                      m2=jax.tree_util.tree_unflatten(treedef, new_m2)),
            stats)


def lower_update_hlo(params, recipe, cfg: OptConfig, *,
                     donate: bool = True) -> str:
    """Compiled HLO text of one ``adamw_update`` on abstract (params, grads,
    state), with the optimizer state donated -- the module ``repro.lint``
    optimizer contracts analyze.  ``params`` may be real arrays or
    ``ShapeDtypeStruct``s (nothing is materialized)."""
    shapes = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    state = jax.eval_shape(lambda p: init_adam_state(p, recipe, cfg), shapes)
    grads = shapes

    def upd(p, g, st):
        return adamw_update(p, g, st, cfg, recipe)

    jitted = jax.jit(upd, donate_argnums=(2,) if donate else ())
    return jitted.lower(shapes, grads, state).compile().as_text()
