"""Shared model machinery: parameter specs, norms, rope, activations.

Parameters are plain nested dicts of jnp arrays.  Each module exposes a
``spec(cfg) -> {name: ParamSpec}`` describing shapes + logical sharding axes;
``init_from_spec`` materializes values and ``axes_from_spec`` the matching
logical-axes tree consumed by ``repro.parallel.sharding``.

Logical axis vocabulary (mapped to mesh axes by sharding rules):
  "embed"   : d_model dims            (FSDP candidate)
  "vocab"   : vocabulary              (TP)
  "mlp"     : feed-forward hidden     (TP)
  "heads"   : attention q-head dim    (TP)
  "kv"      : attention kv-head dim   (TP, may be smaller than axis)
  "expert"  : MoE expert dim          (EP)
  "inner"   : SSM inner dim           (TP)
  "layers"  : stacked scan dim        (never sharded)
  None      : replicated
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | scaled(fan_in)
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(key: jax.Array, spec: ParamSpec, dtype) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * spec.scale).astype(dtype)
    if spec.init == "fan_in":
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else spec.shape[-1]
        std = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
    raise ValueError(spec.init)


def init_from_spec(key: jax.Array, spec_tree: PyTree, dtype=jnp.float32) -> PyTree:
    leaves, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def axes_from_spec(spec_tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: s.axes, spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_layer_specs(spec_tree: PyTree, n_layers: int) -> PyTree:
    """Prepend a scan 'layers' dim to every ParamSpec (stacked-params scan)."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n_layers,) + s.shape, ("layers",) + s.axes,
                            s.init, s.scale),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Sharding-constraint hook.  ``rules`` is repro.parallel.sharding.Rules (or
# None on single-device paths); models call constrain(x, "batch", None, ...)
# with logical activation axes.
# ---------------------------------------------------------------------------

def constrain(x: jnp.ndarray, rules, *logical_axes) -> jnp.ndarray:
    if rules is None:
        return x
    return rules.constrain(x, logical_axes)


def cast_params(params: PyTree, dtype) -> PyTree:
    """Carrier-precision cast (bf16 AMP): float leaves only; int payloads and
    anything already matching pass through.  Prepared quantized weights
    (``QState`` payload + fp32 scale sidecars, see ``repro.infer.prepare``)
    are opaque: casting their scales to bf16 would change the dequant grid."""
    from repro.core.qadam import QState
    def cast(x):
        if isinstance(x, QState):
            return x
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype:
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(
        cast, params, is_leaf=lambda x: isinstance(x, QState))


# ---------------------------------------------------------------------------
# Normalization / activations / position embeddings.
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
            plus_one: bool = False) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:                      # gemma convention: weight stored as w-1
        w = w + 1.0
    return (y * w).astype(x.dtype)


def layernorm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("norm")
def apply_norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    if kind == "rmsnorm_p1":
        return rmsnorm(x, params["scale"], plus_one=True)
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    raise ValueError(kind)


def norm_spec(d: int, kind: str) -> Dict[str, ParamSpec]:
    if kind in ("rmsnorm",):
        return {"scale": ParamSpec((d,), ("embed",), "ones")}
    if kind == "rmsnorm_p1":
        return {"scale": ParamSpec((d,), ("embed",), "zeros")}
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones"),
                "bias": ParamSpec((d,), ("embed",), "zeros")}
    raise ValueError(kind)


ACT_FNS: Dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def rope(q: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0
         ) -> jnp.ndarray:
    """Rotary embedding.  q: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = q.shape[-1]
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    q1, q2 = q[..., :half], q[..., half:]
    qf1, qf2 = q1.astype(jnp.float32), q2.astype(jnp.float32)
    return jnp.concatenate(
        [qf1 * cos - qf2 * sin, qf2 * cos + qf1 * sin], axis=-1).astype(q.dtype)


def causal_mask(s_q: int, s_kv: int, q_offset: int = 0) -> jnp.ndarray:
    """Boolean (s_q, s_kv) mask: True = attend."""
    qpos = jnp.arange(s_q) + q_offset
    kpos = jnp.arange(s_kv)
    return kpos[None, :] <= qpos[:, None]


def prefix_lm_mask(s: int, prefix_len: int, s_kv: int = 0) -> jnp.ndarray:
    """PaliGemma-style: full attention within [0, prefix), causal after.
    ``s_kv`` widens the key axis for cache buffers (extra keys masked by
    causality since qpos < s <= kpos)."""
    s_kv = s_kv or s
    base = causal_mask(s, s_kv)
    qpos = jnp.arange(s)
    kpos = jnp.arange(s_kv)
    in_prefix = (qpos[:, None] < prefix_len) & (kpos[None, :] < prefix_len)
    return base | in_prefix
