"""Trace-level lint: quantization-scale placement in abstract jaxprs.

The HLO rules see the compiled artifact; this rule sees the *algebra*.  A
quantized matmul ``y = (xq @ wq) * (x_scale * w_scale)`` is only a valid
factorization when every scale is constant along its operand's contracted
axis -- a per-channel scale that varies along the contraction cannot be
pulled out of the dot, and multiplying it in beforehand silently changes
what the kernel computes (and forces an fp dequant XLA may then fuse out of
sight of the HLO counters).

``check_scale_contraction(fn, *args)`` traces ``fn`` abstractly with
:func:`jax.make_jaxpr`, marks every ``QState.scale`` leaf in ``args`` as a
taint source whose taint is *the set of axes the scale varies along* (size-1
and scalar scales carry no axes -- per-tensor scales commute with the dot
and legitimately pass), propagates axis-taints through elementwise ops,
broadcasts, transposes, reshapes, reductions and nested jaxprs, and reports
a :class:`~repro.lint.rules.Finding` for every ``dot_general`` whose
operand is scale-tainted along a contracted dimension.

Propagation is conservative: an unrecognized primitive taints all
non-singleton output axes, so a violation cannot be laundered through an
exotic op; false positives would show up as failures of the positive
contract tests on the real paths, which pin the rule's precision.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Sequence, Set, Tuple

import jax
from jax.extend import core as jax_core

from repro.core.qadam import QState
from repro.lint.rules import Finding, Severity

AxisTaint = Set[int]  # axes of the value that vary because of a quant scale

RULE_ID = "scale-off-contracted-axis"


def _scale_mask(args) -> List[bool]:
    """Per-flattened-leaf mask: True where the leaf is a QState scale."""
    marked = jax.tree_util.tree_map(
        lambda x: QState(q=False, scale=True, zero=False)
        if isinstance(x, QState) else False,
        args, is_leaf=lambda x: isinstance(x, QState))
    return [bool(m) for m in jax.tree_util.tree_leaves(marked)]


def _aval_shape(v) -> Tuple[int, ...]:
    aval = getattr(v, "aval", None)
    return tuple(getattr(aval, "shape", ()) or ())


def _varying_axes(shape: Sequence[int]) -> AxisTaint:
    return {i for i, d in enumerate(shape) if d > 1}


def _get(taints: Dict[Any, AxisTaint], v) -> AxisTaint:
    if isinstance(v, jax_core.Literal):
        return set()
    return taints.get(v, set())


def _align_trailing(taint: AxisTaint, from_rank: int, to_rank: int) -> AxisTaint:
    """Map axis indices across a rank change under numpy trailing-axis
    broadcasting (rank-expand prepends axes)."""
    off = to_rank - from_rank
    return {a + off for a in taint if 0 <= a + off < to_rank}


def _elementwise(eqn, taints) -> AxisTaint:
    out_rank = len(_aval_shape(eqn.outvars[0]))
    merged: AxisTaint = set()
    for v in eqn.invars:
        merged |= _align_trailing(_get(taints, v), len(_aval_shape(v)), out_rank)
    return merged


def _sub_jaxprs(params) -> List[Tuple[Any, Any]]:
    """(jaxpr, consts) pairs found in an eqn's params, for call-like prims."""
    out = []
    for val in params.values():
        if isinstance(val, jax_core.ClosedJaxpr):
            out.append((val.jaxpr, val.consts))
        elif isinstance(val, jax_core.Jaxpr):
            out.append((val, []))
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, jax_core.ClosedJaxpr):
                    out.append((item.jaxpr, item.consts))
    return out


def _dot_findings(eqn, taints, ctx_name: str, idx: int) -> List[Finding]:
    (lc, rc), _ = eqn.params["dimension_numbers"]
    out: List[Finding] = []
    for side, v, contracted in (("lhs", eqn.invars[0], lc),
                                ("rhs", eqn.invars[1], rc)):
        bad = _get(taints, v) & set(contracted)
        if bad:
            shape = _aval_shape(v)
            out.append(Finding(
                Severity.ERROR, RULE_ID, f"dot_general#{idx}", ctx_name,
                f"{side} operand {shape} is scale-tainted along contracted "
                f"axis/axes {sorted(bad)}: a per-channel quant scale varying "
                "on the contraction was multiplied in before the dot, so the "
                "int8 factorization is invalid"))
    return out


def _dot_out_taint(eqn, taints) -> AxisTaint:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0], eqn.invars[1]
    lshape, rshape = _aval_shape(lhs), _aval_shape(rhs)
    lfree = [a for a in range(len(lshape)) if a not in lc and a not in lb]
    rfree = [a for a in range(len(rshape)) if a not in rc and a not in rb]
    # output layout: batch dims, lhs free dims, rhs free dims
    out: AxisTaint = set()
    lt, rt = _get(taints, lhs), _get(taints, rhs)
    for o, (la, ra) in enumerate(zip(lb, rb)):
        if la in lt or ra in rt:
            out.add(o)
    for o, a in enumerate(lfree, start=len(lb)):
        if a in lt:
            out.add(o)
    for o, a in enumerate(rfree, start=len(lb) + len(lfree)):
        if a in rt:
            out.add(o)
    return out


def _propagate(jaxpr, in_taints: List[AxisTaint], ctx_name: str,
               counter=None) -> Tuple[List[AxisTaint], List[Finding]]:
    """Run axis-taint dataflow over one jaxpr; returns outvar taints plus
    all dot_general findings (including from nested jaxprs)."""
    counter = counter if counter is not None else itertools.count()
    taints: Dict[Any, AxisTaint] = {}
    for v, t in zip(jaxpr.invars, in_taints):
        if t:
            taints[v] = set(t)
    findings: List[Finding] = []

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        invars = eqn.invars
        any_taint = any(_get(taints, v) for v in invars)

        if name == "dot_general":
            idx = next(counter)
            findings.extend(_dot_findings(eqn, taints, ctx_name, idx))
            out = _dot_out_taint(eqn, taints)
            if out:
                taints[eqn.outvars[0]] = out
            continue

        subs = _sub_jaxprs(eqn.params)
        if subs:
            # call-like primitive (pjit / custom_vjp / scan / cond ...):
            # align our operand taints with the sub-jaxpr's trailing invars
            # (leading invars may be consts/carry not present here).
            for sub, _consts in subs:
                n = len(sub.invars)
                ops = list(invars)[-n:] if len(invars) >= n else list(invars)
                sub_in = [set()] * (n - len(ops)) + [_get(taints, v) for v in ops]
                sub_out, sub_f = _propagate(sub, sub_in, ctx_name, counter)
                findings.extend(sub_f)
                for ov, t in zip(eqn.outvars, sub_out):
                    if t:
                        taints[ov] = taints.get(ov, set()) | t
            continue

        if not any_taint:
            continue

        if name == "broadcast_in_dim":
            bdims = eqn.params["broadcast_dimensions"]
            t = _get(taints, invars[0])
            taints[eqn.outvars[0]] = {bdims[a] for a in t if a < len(bdims)}
        elif name == "transpose":
            perm = eqn.params["permutation"]
            t = _get(taints, invars[0])
            taints[eqn.outvars[0]] = {perm.index(a) for a in t}
        elif name == "squeeze":
            dims = set(eqn.params["dimensions"])
            t = _get(taints, invars[0])
            taints[eqn.outvars[0]] = {
                a - sum(1 for d in dims if d < a) for a in t if a not in dims}
        elif name == "reshape":
            in_shape = _aval_shape(invars[0])
            out_shape = _aval_shape(eqn.outvars[0])
            t = _get(taints, invars[0])
            in_sig = [a for a, d in enumerate(in_shape) if d > 1]
            out_sig = [a for a, d in enumerate(out_shape) if d > 1]
            if ([in_shape[a] for a in in_sig] == [out_shape[a] for a in out_sig]):
                # pure size-1 axis insertion/removal: map positionally
                remap = dict(zip(in_sig, out_sig))
                taints[eqn.outvars[0]] = {remap[a] for a in t if a in remap}
            else:
                taints[eqn.outvars[0]] = _varying_axes(out_shape)
        elif name.startswith("reduce_"):
            axes = set(eqn.params.get("axes", ()))
            t = _get(taints, invars[0])
            taints[eqn.outvars[0]] = {
                a - sum(1 for d in axes if d < a) for a in t if a not in axes}
        elif name in ("slice", "dynamic_slice", "pad", "rev",
                      "convert_element_type", "copy", "stop_gradient",
                      "reduce_precision", "round", "clamp", "sort", "gather",
                      "dynamic_update_slice", "concatenate", "select_n",
                      "optimization_barrier"):
            out_rank = len(_aval_shape(eqn.outvars[0]))
            merged: AxisTaint = set()
            for v in invars:
                merged |= {a for a in _get(taints, v) if a < out_rank}
            for ov in eqn.outvars:
                taints[ov] = set(merged)
        else:
            # elementwise default + conservative catch-all: a tainted input
            # taints every non-singleton output axis it can align with.
            known_ew = _elementwise(eqn, taints)
            for ov in eqn.outvars:
                shape = _aval_shape(ov)
                taints[ov] = (known_ew & _varying_axes(shape)) or (
                    _varying_axes(shape) if not known_ew and any_taint
                    and name not in ("iota",) else known_ew)

    return [_get(taints, v) for v in jaxpr.outvars], findings


def check_scale_contraction(fn, *args, name: str = "<fn>") -> List[Finding]:
    """Trace ``fn(*args)`` abstractly and report every ``dot_general``
    contracting over an axis along which a ``QState.scale`` input varies.
    Returns ``[]`` when every scale stays off every contracted axis."""
    mask = _scale_mask(args)
    closed = jax.make_jaxpr(fn)(*args)
    leaves = jax.tree_util.tree_leaves(args)
    in_taints: List[AxisTaint] = []
    for leaf, is_scale in zip(leaves, mask):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        in_taints.append(_varying_axes(shape) if is_scale else set())
    # make_jaxpr flattens args in tree-leaf order, so invars align with mask
    if len(closed.jaxpr.invars) != len(in_taints):
        raise ValueError(
            f"invar/leaf mismatch tracing {name}: {len(closed.jaxpr.invars)} "
            f"invars vs {len(in_taints)} leaves")
    _, findings = _propagate(closed.jaxpr, in_taints, name)
    return findings
