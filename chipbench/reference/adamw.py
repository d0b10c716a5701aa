"""Plain AdamW (Loshchilov & Hutter 2019) with global-norm clipping and a
linear-warmup, half-cosine learning rate: the optimizer the traffic file
states, written out leaf by leaf in float32."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def lr_at(step: int, opt: Dict) -> float:
    """Learning rate of update number ``step`` (1-based)."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * frac))


#: the rules of weight decay a traffic file's ``optimizer.decay`` may name:
#: whether a leaf decays, from its path and stored shape.  Both references
#: stack their per-layer leaves under ``blocks`` along a leading layer axis.
DECAY_RULES = {
    # every leaf stored at rank 2 or more, the stacked per-layer biases and
    # norm gains included: the rule the program runs
    "every stored leaf of rank >= 2": lambda path, shape: len(shape) >= 2,
    # matrices only (nanoGPT's rule): a stacked per-layer leaf is a matrix
    # from rank 3 on
    "matrices only": lambda path, shape:
        len(shape) - path.startswith("blocks/") >= 2,
}


def decays(path: str, shape, rule: str) -> bool:
    """Whether the leaf at ``path`` (``blocks/attn/wq``) decays under
    ``rule``, one of :data:`DECAY_RULES`."""
    if rule not in DECAY_RULES:
        raise ValueError(f"unknown weight decay rule {rule!r}; have "
                         f"{sorted(DECAY_RULES)}")
    return DECAY_RULES[rule](path, tuple(shape))


def clip_scale(grads, max_norm: float):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree_util.tree_leaves(grads)))
    return jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))


def update(p, g, m, v, lr, c1, c2, opt: Dict, decay: bool):
    """One AdamW update of one leaf from its already clipped gradient, with
    the learning rate and bias corrections of :func:`schedule`; ``decay``
    says whether weight decay applies to it.  Returns (p, m, v)."""
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
    if opt["weight_decay"] and decay:
        upd = upd + opt["weight_decay"] * p
    return p - lr * upd, m, v


def schedule(step: int, opt: Dict):
    """(learning rate, 1 - b1**step, 1 - b2**step) of update ``step``."""
    return (lr_at(step, opt), 1.0 - opt["b1"] ** step,
            1.0 - opt["b2"] ** step)
