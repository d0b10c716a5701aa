"""Rule registry for the quantization-invariant HLO analyzer.

Every fast path in this repo is only a win while its compiled module keeps a
structural shape: prepared-weights decode must hold zero in-trace weight
quant rounds, fused int8-KV decode must never dequantize the whole cache,
the int8 train step must actually emit integer MXU dots, donated buffers
must stay copy-free.  A silent fallback breaks none of the numeric tests --
the reference path computes the same values -- so these invariants are
checked *statically* here, over compiled HLO text.

A :class:`Rule` is a named, parameterized check ``(HloModule, **params) ->
[Finding]``; contracts (``lint/contracts.py``) bind rules to the real paths
with concrete parameters.  All rules scan only computations reachable from
ENTRY (``HloModule.reachable``): dead computations retained by the compiler
would otherwise mask zero-count assertions or inflate presence counts.

Adding a rule::

    @rule("my-rule", "one-line description")
    def _my_rule(mod: HloModule, *, threshold: int = 0) -> List[Finding]:
        ...yield findings...

and reference it from a contract via ``RuleSpec("my-rule", {...})``.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Dict, List, Optional, Sequence

from repro.lint.hlo_graph import (ALIASING_OPS, QUANT_LOCAL_OPS, HloModule,
                                  nbytes, nelems, operand_names, shape_of)


class Severity(enum.IntEnum):
    INFO = 0
    WARNING = 1
    ERROR = 2


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, anchored to an instruction when possible."""
    severity: Severity
    rule_id: str
    instr: Optional[str]            # instruction name, None for module-level
    computation: Optional[str]      # computation name, None for module-level
    message: str

    def format(self) -> str:
        where = ""
        if self.computation:
            where = f" [{self.computation}" + (
                f"::{self.instr}]" if self.instr else "]")
        return f"{self.severity.name:7s} {self.rule_id}{where}: {self.message}"


@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    description: str
    check: Callable[..., List[Finding]]


RULES: Dict[str, Rule] = {}


def rule(rule_id: str, description: str):
    def deco(fn):
        RULES[rule_id] = Rule(rule_id, description, fn)
        return fn
    return deco


def _finding(rule_id: str, msg: str, comp: Optional[str] = None,
             instr: Optional[str] = None,
             severity: Severity = Severity.ERROR) -> Finding:
    return Finding(severity, rule_id, instr, comp, msg)


# ---------------------------------------------------------------------------
# (1) no-weight-quant-rounds
# ---------------------------------------------------------------------------

@rule("no-weight-quant-rounds",
      "prepared-weights paths must contain zero in-trace quantize rounds")
def _no_weight_quant_rounds(mod: HloModule, *, max_rounds: int = 0,
                            prefix: str = "round-nearest") -> List[Finding]:
    """With weights stored as int8 payloads + scales, the compiled step must
    not re-quantize anything: every ``round-nearest*`` op on the live path
    is a weight (or activation) being quantized in-trace -- the exact cost
    preparation paid once to remove."""
    hits = [(comp, ins) for comp, ins in mod.live_instrs()
            if ins.op.startswith(prefix)]
    if len(hits) <= max_rounds:
        return []
    return [_finding("no-weight-quant-rounds",
                     f"in-trace quant round {ins.op} "
                     f"({len(hits)} total, contract allows {max_rounds})",
                     comp, ins.name)
            for comp, ins in hits]


# ---------------------------------------------------------------------------
# (2) no-whole-cache-dequant
# ---------------------------------------------------------------------------

@rule("no-whole-cache-dequant",
      "fused int8-KV decode must not convert large s8 buffers to fp")
def _no_whole_cache_dequant(mod: HloModule, *, min_elems: int = 4096,
                            from_dtype: str = "s8",
                            to_dtypes: Sequence[str] = ("f32", "bf16", "f16"),
                            dims: Optional[Sequence[int]] = None,
                            ) -> List[Finding]:
    """The fused decode kernels fold dequant scales in-register; a ``convert
    s8 -> fp`` at (or above) cache-buffer size means the whole quantized
    cache is being materialized in fp -- the dequant-on-read fallback.
    Size-thresholded: scalar / per-row converts (sampling temperature, the
    freshly decoded row) are part of the contract and pass.  ``dims`` pins
    the rule to one buffer shape (the (B, S, kv_heads, head_dim) cache):
    other large s8 converts -- e.g. the documented dequant-matmul fallback
    for stacked prepared-weight payloads -- are a different path's business.
    """
    out: List[Finding] = []
    for comp, ins in mod.live_instrs():
        if ins.op != "convert":
            continue
        res_dtype, res_dims = shape_of(ins.type_str)
        if res_dtype not in to_dtypes or nelems(ins.type_str) < min_elems:
            continue
        if dims is not None and res_dims != tuple(dims):
            continue
        opnds = mod.operand_types(comp, ins)
        if opnds and opnds[0][0] == from_dtype:
            out.append(_finding(
                "no-whole-cache-dequant",
                f"whole-buffer dequantize: convert {from_dtype}"
                f"{list(opnds[0][1])} -> {ins.type_str.strip()} "
                f"({nelems(ins.type_str)} elems >= {min_elems})",
                comp, ins.name))
    return out


# ---------------------------------------------------------------------------
# (3) int8-compute-present
# ---------------------------------------------------------------------------

@rule("int8-compute-present",
      "quantized train/backward HLO must hold real integer MXU dots")
def _int8_compute_present(mod: HloModule, *, min_dots: int = 1,
                          result_type: str = "s32") -> List[Finding]:
    """An int8 x int8 dot accumulates to s32.  Fewer s32-result dots than
    the contract's floor means some matmul silently fell back to an fp
    einsum over dequantized operands -- numerically near-identical, none of
    the efficiency."""
    n = sum(1 for _, ins in mod.live_instrs()
            if ins.op == "dot"
            and ins.type_str.strip().lstrip("(").startswith(result_type))
    if n >= min_dots:
        return []
    return [_finding("int8-compute-present",
                     f"only {n} {result_type}-result dot(s) on the live "
                     f"path, contract requires >= {min_dots} (a quantized "
                     "matmul fell back to fp)")]


# ---------------------------------------------------------------------------
# (4) copy-free-aliasing
# ---------------------------------------------------------------------------

@rule("copy-free-aliasing",
      "no copy of a donated input buffer (input_output_alias must hold)")
def _copy_free_aliasing(mod: HloModule, *, min_bytes: int = 1024
                        ) -> List[Finding]:
    """Donated buffers (decode state, fused-AdamW moment buckets) are
    updated in place; when XLA cannot prove the alias it inserts a
    defensive whole-buffer copy -- per step, erasing the one-read-one-write
    schedule.  Flags ``copy``/``copy-start`` in ENTRY whose operand chain
    roots at a donated parameter through aliasing ops only (tuple element
    extraction, bitcasts...).  ``min_bytes`` skips scalar bookkeeping copies
    (step counters, rng keys)."""
    if mod.entry is None:
        return []
    donated = mod.donated_params()
    if not donated:
        return []
    out: List[Finding] = []
    for ins in mod.comps[mod.entry]:
        if ins.op not in ("copy", "copy-start"):
            continue
        if nbytes(ins.type_str) < min_bytes:
            continue
        for producer in mod.walk_back(mod.entry, ins, through=ALIASING_OPS):
            pnum = mod.param_number(producer)
            if pnum in donated:
                out.append(_finding(
                    "copy-free-aliasing",
                    f"{ins.op} of {nbytes(ins.type_str)} bytes roots at "
                    f"donated parameter {pnum} ({producer.name}): the "
                    "input/output alias degraded to a defensive copy",
                    mod.entry, ins.name))
                break
    return out


# ---------------------------------------------------------------------------
# (5) double-quantize
# ---------------------------------------------------------------------------

@rule("double-quantize",
      "no value quantized twice on one elementwise dataflow path")
def _double_quantize(mod: HloModule, *, prefix: str = "round-nearest"
                     ) -> List[Finding]:
    """Two quant rounds with only elementwise/scaling ops between them mean
    the same tensor was quantized twice (qdq of an already-quantized value:
    double rounding error AND double cost).  A dot / reduce / scatter
    between the rounds computes a genuinely new value and legitimately
    re-quantizes, so the walk stops there."""
    out: List[Finding] = []
    for comp, ins in mod.live_instrs():
        if not ins.op.startswith(prefix):
            continue
        for producer in mod.walk_back(comp, ins, through=QUANT_LOCAL_OPS):
            if producer.op.startswith(prefix):
                out.append(_finding(
                    "double-quantize",
                    f"{ins.name} re-quantizes a value already rounded by "
                    f"{producer.name} (elementwise-only path between them)",
                    comp, ins.name))
                break
    return out


# ---------------------------------------------------------------------------
# (6) no-large-gather
# ---------------------------------------------------------------------------

@rule("no-large-gather",
      "paged decode must not gather more pages than a slot's live range")
def _no_large_gather(mod: HloModule, *, min_elems: int,
                     dtype: str = "s8",
                     dims: Optional[Sequence[int]] = None) -> List[Finding]:
    """Paged decode touches at most ``ceil(pos/page_size)`` physical pages
    per slot -- a gather / dynamic-slice whose *result* reaches the size of
    every slot's full logical KV view means the page indirection collapsed
    into a materialized whole-cache gather (paging's memory win gone, and a
    (B, maxp*page, ...) fp copy usually follows).  Size-thresholded on the
    result so the fused kernel's per-tile page DMAs (one page each) pass;
    ``dims`` pins the rule to the per-slot logical view shape
    (B, maxp, page, kv_heads, head_dim) so the layer scan's per-layer
    stacked-buffer slices (leading dim 1, a different axis entirely) are
    not mistaken for it."""
    out: List[Finding] = []
    for comp, ins in mod.live_instrs():
        if ins.op not in ("gather", "dynamic-slice"):
            continue
        res_dtype, res_dims = shape_of(ins.type_str)
        if res_dtype != dtype or nelems(ins.type_str) < min_elems:
            continue
        if dims is not None and res_dims != tuple(dims):
            continue
        out.append(_finding(
            "no-large-gather",
            f"{ins.op} materializes {ins.type_str.strip()} "
            f"({nelems(ins.type_str)} elems >= {min_elems}): whole-cache "
            "page gather on the paged decode path",
            comp, ins.name))
    return out


# ---------------------------------------------------------------------------
# op-count: the generic parameterized counter (replaces ad-hoc test asserts)
# ---------------------------------------------------------------------------

@rule("op-count",
      "bounded count of ops by prefix (and optional result-type prefix)")
def _op_count(mod: HloModule, *, op_prefix: str,
              result_type: Optional[str] = None,
              min_count: int = 0, max_count: Optional[int] = None
              ) -> List[Finding]:
    """Structured replacement for raw ``count_ops`` assertions: a contract
    states bounds, a violation reports the live count."""
    n = 0
    for _, ins in mod.live_instrs():
        if not ins.op.startswith(op_prefix):
            continue
        if (result_type is not None and not
                ins.type_str.strip().lstrip("(").startswith(result_type)):
            continue
        n += 1
    want = (f">= {min_count}" if max_count is None else
            f"in [{min_count}, {max_count}]")
    if n < min_count or (max_count is not None and n > max_count):
        tt = f" (result {result_type})" if result_type else ""
        return [_finding("op-count",
                         f"{n} live {op_prefix!r}{tt} op(s), contract "
                         f"requires {want}")]
    return []


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RuleSpec:
    """One rule binding inside a contract: rule id, parameters, and the
    severity its findings report at."""
    rule_id: str
    params: Dict = dataclasses.field(default_factory=dict)
    severity: Severity = Severity.ERROR

    def run(self, mod: HloModule) -> List[Finding]:
        found = RULES[self.rule_id].check(mod, **self.params)
        return [dataclasses.replace(f, severity=self.severity)
                for f in found]


def run_rules(hlo, specs: Sequence[RuleSpec]) -> List[Finding]:
    """Check one compiled module (text or :class:`HloModule`) against a list
    of rule bindings; returns all findings, most severe first."""
    mod = hlo if isinstance(hlo, HloModule) else HloModule(hlo)
    out: List[Finding] = []
    for spec in specs:
        out.extend(spec.run(mod))
    return sorted(out, key=lambda f: -int(f.severity))
