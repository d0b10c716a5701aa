"""pjit-able train / eval / serve step factories.

``make_train_step`` closes over (model, quantization policy, opt config,
sharding rules) and returns a pure function (state, batch, rng) ->
(state, metrics) suitable for jax.jit with in/out shardings -- the same
function is used by the CPU smoke tests, the real launcher, and the
multi-pod dry-run.

The ``recipe`` argument of every factory accepts the full policy surface:
None (fp), a legacy :class:`QuantRecipe`, a :class:`QuantPolicy`, or a
policy string -- all normalized via ``as_policy``.  The normalized policy's
``adam_m1``/``adam_m2`` feed the quantized optimizer states.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qadam
from repro.core.qpolicy import QuantPolicy, as_policy
from repro.models.model_api import Model
from repro.optim.adamw import (AdamState, OptConfig, adamw_update,
                               init_adam_state, opt_path_desc)


class TrainState(NamedTuple):
    params: Any                      # fp32 master weights
    opt: AdamState


#: Block-linear roles summarized by :func:`train_path_summary` (the matmuls
#: that dominate a train step; kv_cache/embed/lm_head are serving or
#: depth-less concerns).
_SUMMARY_ROLES = ("attn_qkv", "attn_out", "mlp_up", "mlp_down",
                  "ssm_in", "ssm_out")


def _path_desc(backend: str, caps, recipe=None) -> str:
    if backend == "fp":
        return "fp"
    if not caps:
        from repro.core.qlinear import residual_compressible
        specs = [] if recipe is None else \
            [s for s in (recipe.acts, recipe.weights) if s is not None]
        compressed = [residual_compressible(s) for s in specs]
        res = ("int8" if specs and all(compressed)
               else "mixed" if any(compressed) else "fp")
        return f"fake_quant(fwd=qdq,bwd=qdq,res={res})"
    bwd = "int8" if "bwd" in caps else "qdq"
    return f"{backend}(fwd=int8,bwd={bwd},res=int8)"


def train_path_summary(recipe, n_layers: int = 0,
                       opt_cfg: Optional[OptConfig] = None) -> str:
    """One-line description of the kernel path each block-linear role's train
    step actually runs: effective backend after fallback, which passes hit
    real quantized compute, and the custom-vjp residual codec.  Printed by
    the launcher and reported by benchmarks/train_throughput.py.

    Depth-banded policies resolve per layer: pass ``n_layers`` to enumerate
    the distinct per-depth paths ('/'-joined); without it the summary can
    only flag the role as depth-banded rather than misreport one band.

    Pass ``opt_cfg`` to also report the optimizer update path (``opt=``
    segment: fp/fake/int8 storage x fused-kernel vs reference loop)."""
    policy = as_policy(recipe)
    groups: Dict[str, list] = {}
    for role in _SUMMARY_ROLES:
        if policy.depth_sensitive(role):
            if n_layers:
                descs = sorted({_path_desc(
                    *policy.effective_backend(role, i, n_layers),
                    policy.resolve(role, i, n_layers).recipe)
                    for i in range(n_layers)})
                desc = "/".join(descs)
            else:
                desc = "depth-banded(pass n_layers)"
        else:
            desc = _path_desc(*policy.effective_backend(role),
                              policy.resolve(role).recipe)
        groups.setdefault(desc, []).append(role)
    summary = " ".join(f"{'+'.join(roles)}={desc}"
                       for desc, roles in groups.items())
    if opt_cfg is not None:
        summary += f" opt={opt_path_desc(policy, opt_cfg)}"
    return summary


def init_train_state(model: Model, key: jax.Array, recipe,
                     opt_cfg: OptConfig) -> TrainState:
    policy = as_policy(recipe)
    params = model.init_params(key, jnp.float32)
    return TrainState(params=params,
                      opt=init_adam_state(params, policy, opt_cfg))


def _health_err_spec(policy: QuantPolicy):
    """Spec the ``grad_qerr`` drift counter measures against: the policy
    default's gradient spec when one exists (that is the codec the backward
    actually injects), else its activation spec, else nothing."""
    r = policy.default
    if r is None:
        return None
    return r.grads if r.grads is not None else r.acts


def make_train_step(model: Model, recipe, opt_cfg: OptConfig, rules=None,
                    accum_steps: int = 1, faults=None, health: bool = False):
    """Gradient step with optional microbatch accumulation (accum_steps > 1
    splits the leading batch dim; gradients are averaged -- communication for
    the DP reduction is deferred to the last microbatch by XLA).

    ``faults`` (a ``train.faults.FaultPlan``) injects its planned gradient
    faults in-trace, keyed on the traced ``state.opt.step`` counter --
    bitwise no-op on every other step.  ``health=True`` adds the sentinel's
    quantization-health counters to the metrics (``grad_sat``: gradient
    mass exceeding the stored int8 Adam-moment scales; ``grad_qerr``:
    relative quantization error of the gradient under the policy's
    grad/act spec) -- one extra pass over the gradient leaves."""
    policy = as_policy(recipe)

    def constrain_like_params(tree, ref):
        """Pin a params-shaped tree to the parameter shardings: gradients
        then REDUCE-SCATTER to their FSDP shard instead of all-reducing
        (halves dW wire), and the bf16 cast lands BEFORE the per-layer
        weight all-gather (halves gather wire)."""
        if rules is None:
            return tree
        flat, treedef = jax.tree_util.tree_flatten(ref)
        flat_t = treedef.flatten_up_to(tree)
        flat_ax = treedef.flatten_up_to(model.axes)
        out = [jax.lax.with_sharding_constraint(
                   t, rules.sharding_for(t.shape, ax))
               for t, ax in zip(flat_t, flat_ax)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def loss_fn(params, batch, rng):
        from repro.models.common import cast_params
        with jax.named_scope("weights_cast"):
            compute_params = constrain_like_params(
                cast_params(params, jnp.bfloat16), params)
        loss, metrics = model.train_loss(compute_params, batch,
                                         policy=policy, rules=rules, rng=rng)
        return loss, metrics

    def grad_fn(params, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, rng)
        return (loss, metrics), constrain_like_params(grads, params)

    def train_step(state: TrainState, batch, rng
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(state.params, batch, rng)
        else:
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(state.params, mb, rng)
                return (jax.tree_util.tree_map(jnp.add, g_acc, g),
                        l_acc + l), None

            split = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), split)
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            metrics = {"ce": loss, "loss": loss}

        if faults is not None and faults.has_grad_faults():
            grads = faults.apply_grads(state.opt.step, grads)
        if health:
            from repro.core.diagnostics import grad_quant_health
            metrics = dict(metrics)
            metrics.update(grad_quant_health(
                grads, state.opt.m1, policy.adam_m1,
                _health_err_spec(policy), beta1=opt_cfg.b1))
        with jax.named_scope("optimizer"):
            new_params, new_opt, stats = adamw_update(
                state.params, grads, state.opt, opt_cfg, policy)
        metrics = dict(metrics)
        metrics.update(stats)
        return TrainState(new_params, new_opt), metrics

    return train_step


def lower_train_hlo(model: Model, recipe, opt_cfg: OptConfig, *,
                    batch_size: int = 2, seq_len: int = 33,
                    donate: bool = True) -> str:
    """Compiled HLO text of one full train step (fwd + bwd + optimizer) on
    abstract inputs, with the state donated as a real launcher would --
    the module ``repro.lint`` train contracts analyze.  Nothing is
    materialized: the state comes from ``jax.eval_shape``."""
    policy = as_policy(recipe)
    state = jax.eval_shape(
        lambda k: init_train_state(model, k, policy, opt_cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {"tokens": jax.ShapeDtypeStruct((batch_size, seq_len),
                                            jnp.int32)}
    step = make_train_step(model, policy, opt_cfg)
    jitted = jax.jit(step, donate_argnums=(0,) if donate else ())
    return jitted.lower(state, batch, None).compile().as_text()


def make_eval_step(model: Model, recipe, rules=None):
    policy = as_policy(recipe)

    def eval_step(params, batch):
        loss, metrics = model.train_loss(params, batch, policy=policy,
                                         rules=rules)
        return metrics
    return eval_step


# ---------------------------------------------------------------------------
# Sharding helpers for the full TrainState
# ---------------------------------------------------------------------------

def state_shardings(rules, model: Model, state_shapes: TrainState):
    """NamedSharding tree matching a TrainState's structure.  Optimizer
    moments mirror their parameter's logical axes when shapes match (fp/fake
    storage); int-codec QState subtrees carry the blockwise bucket layout
    (nblocks, block_size) of kernels/opt_update.py and FSDP-shard their
    leading block dim (payload AND scale/zero sidecars, so fused-kernel
    buckets concatenate shard-aligned) when it divides, else replicate."""
    if rules is None:
        return None
    flat_p, p_treedef = jax.tree_util.tree_flatten(state_shapes.params)
    flat_ax = p_treedef.flatten_up_to(model.axes)
    p_shard_leaves = [rules.sharding_for(p.shape, ax)
                      for p, ax in zip(flat_p, flat_ax)]
    p_shard = jax.tree_util.tree_unflatten(p_treedef, p_shard_leaves)

    def moments(tree):
        flat_m = p_treedef.flatten_up_to(tree)
        out = []
        for p, ax, mstate in zip(flat_p, flat_ax, flat_m):
            if isinstance(mstate, qadam.QState):
                # "embed" is the FSDP-mapped logical axis; sharding_for
                # drops it when the block count does not divide.
                out.append(qadam.QState(
                    q=rules.sharding_for(mstate.q.shape,
                                         ("embed",) + (None,)
                                         * (len(mstate.q.shape) - 1)),
                    scale=rules.sharding_for(mstate.scale.shape,
                                             ("embed",) + (None,)
                                             * (len(mstate.scale.shape) - 1)),
                    zero=rules.sharding_for(mstate.zero.shape,
                                            ("embed",) + (None,)
                                            * (len(mstate.zero.shape) - 1))))
            elif tuple(mstate.shape) == tuple(p.shape):
                out.append(rules.sharding_for(p.shape, ax))
            else:
                out.append(rules.replicated())
        return jax.tree_util.tree_unflatten(p_treedef, out)

    return TrainState(
        params=p_shard,
        opt=AdamState(step=rules.replicated(),
                      m1=moments(state_shapes.opt.m1),
                      m2=moments(state_shapes.opt.m2)))


def batch_shardings(rules, batch_specs):
    """DP-shard the leading batch dim where divisible, else replicate."""
    if rules is None:
        return None

    def one(s):
        if s.shape and s.shape[0] % rules.dp_size == 0:
            return rules.batch_sharding(len(s.shape))
        return rules.replicated()

    return jax.tree_util.tree_map(one, batch_specs)
