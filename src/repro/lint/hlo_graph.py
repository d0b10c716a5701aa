"""Analyzed view of one compiled HLO module for the rule engine.

:class:`HloModule` wraps ``parallel.hlo_count.parse_module`` output with the
graph facts every rule needs and no rule should re-derive:

* **reachability** -- the set of computations reachable from ENTRY.  Rules
  only fire on live code: compiled modules can retain dead computations
  (DCE'd branches, unused fusions) whose ops never execute;
* **donated parameters** -- ENTRY parameter numbers listed in the module
  header's ``input_output_alias`` map (``jax.jit(..., donate_argnums=...)``).
  The copy-free-aliasing rule checks no ``copy`` roots at one of these;
* **dataflow** -- per-computation def maps plus bounded backward walks over
  operand chains, with an op filter so rules can ask "does this value reach
  a quant round through elementwise ops only" without crossing a matmul.

Everything here is text-level static analysis: no jax tracing, no
compilation -- golden ``tests/fixtures/hlo`` modules exercise it directly.
"""
from __future__ import annotations

import math
import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from repro.parallel.hlo_count import (Instr, _DTYPE_BYTES, _OPERAND, _SHAPE,
                                      entry_name, parse_module,
                                      reachable_computations)

#: ``input_output_alias={ {0}: (0, {}, may-alias), {1,0}: (2, {}, ...) }`` --
#: one ``{output_index}: (param_number, {param_index}, kind)`` entry per
#: donated buffer; we need the param numbers.
_ALIAS_ENTRY = re.compile(r"\{[\d,\s]*\}\s*:\s*\((\d+),")


def _alias_blob(header: str) -> str:
    """The brace-balanced body of ``input_output_alias={...}`` (nested
    ``{output_index}`` / ``{param_index}`` braces defeat a regex)."""
    key = "input_output_alias={"
    start = header.find(key)
    if start < 0:
        return ""
    depth, i = 1, start + len(key)
    while i < len(header) and depth:
        depth += {"{": 1, "}": -1}.get(header[i], 0)
        i += 1
    return header[start + len(key):i - 1]

#: Ops that forward a buffer (or a view of one) without computing new values:
#: a copy whose operand chain crosses only these still copies the *donated*
#: bytes.  Anything else (fusion, dot, elementwise) produces a fresh buffer.
ALIASING_OPS = frozenset({
    "parameter", "copy", "copy-start", "copy-done", "bitcast", "tuple",
    "get-tuple-element", "optimization-barrier", "transpose", "reshape",
})

#: Elementwise / shape-preserving ops a quantize-round chain may cross; a
#: dot / reduce / scatter between two rounds means a genuinely new value was
#: computed, not the same tensor quantized twice.
QUANT_LOCAL_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "clamp",
    "select", "compare", "convert", "bitcast-convert", "broadcast",
    "reshape", "transpose", "bitcast", "copy", "negate", "abs", "sign",
    "floor", "ceil", "power", "exponential", "log", "tanh", "rsqrt", "sqrt",
})


def shape_of(type_str: str) -> Tuple[Optional[str], Tuple[int, ...]]:
    """(dtype, dims) of the first array shape in an HLO type string, or
    (None, ()) for token/opaque types."""
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype in _DTYPE_BYTES:
            return dtype, tuple(int(d) for d in dims.split(",") if d)
    return None, ()


def nelems(type_str: str) -> int:
    _, dims = shape_of(type_str)
    return int(math.prod(dims)) if dims else 1


def nbytes(type_str: str) -> int:
    dtype, dims = shape_of(type_str)
    if dtype is None:
        return 0
    return _DTYPE_BYTES[dtype] * (int(math.prod(dims)) if dims else 1)


def operand_head(ins: Instr) -> str:
    """The operand-list text of an instruction: ``rest`` up to the paren that
    closes the op's argument list.  Paren-balanced, not a naive split --
    tuple-typed operands (``get-tuple-element((f32[2], s8[4]) %t), index=0``)
    nest parens inside the list."""
    depth = 1
    for i, ch in enumerate(ins.rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return ins.rest[:i]
    return ins.rest


def operand_names(ins: Instr) -> List[str]:
    """Instruction-operand names: ``%refs`` in the operand list only
    (computation references like ``to_apply=%region`` live after the operand
    list's closing paren and must not leak into dataflow walks)."""
    return _OPERAND.findall(operand_head(ins))


def operand_types(ins: Instr) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) per operand, read from the inline-typed operand list."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(operand_head(ins)) if d in _DTYPE_BYTES]


class HloModule:
    """Parsed + analyzed compiled module (see module docstring)."""

    def __init__(self, hlo: str):
        self.text = hlo
        self.comps: Dict[str, List[Instr]] = parse_module(hlo)
        self.entry: Optional[str] = entry_name(self.comps)
        self.reachable: List[str] = reachable_computations(self.comps)
        self._defs: Dict[str, Dict[str, Instr]] = {}

    # -- structure ---------------------------------------------------------

    def live_instrs(self) -> Iterable[Tuple[str, Instr]]:
        """(computation, instr) over reachable computations only."""
        for name in self.reachable:
            for ins in self.comps[name]:
                yield name, ins

    def defs(self, comp: str) -> Dict[str, Instr]:
        """name -> defining Instr within one computation."""
        if comp not in self._defs:
            self._defs[comp] = {i.name: i for i in self.comps.get(comp, [])}
        return self._defs[comp]

    def donated_params(self) -> Set[int]:
        """ENTRY parameter numbers donated via input_output_alias."""
        header = self.text.splitlines()[0] if self.text else ""
        return {int(p) for p in _ALIAS_ENTRY.findall(_alias_blob(header))}

    def operand_types(self, comp: str, ins: Instr
                      ) -> List[Tuple[str, Tuple[int, ...]]]:
        """(dtype, dims) per operand: inline-typed operand lists are read as
        they stand; bare ``%name`` operands (the compiled-module printer's
        form) resolve through their defining instruction in ``comp``."""
        inline = operand_types(ins)
        if inline:
            return inline
        defs = self.defs(comp)
        shapes = [shape_of(defs[n].type_str) for n in operand_names(ins)
                  if n in defs]
        return [(d, dims) for d, dims in shapes if d is not None]

    # -- dataflow ----------------------------------------------------------

    def walk_back(self, comp: str, ins: Instr,
                  through: FrozenSet[str]) -> List[Instr]:
        """Transitive operand producers of ``ins`` within ``comp``, walking
        only *through* instructions whose op is in ``through`` (the
        frontier instructions themselves -- where the walk stopped -- are
        included in the result, so callers can inspect what the chain hit)."""
        seen: Set[str] = set()
        out: List[Instr] = []
        frontier = list(operand_names(ins))
        defs = self.defs(comp)
        while frontier:
            name = frontier.pop()
            if name in seen:
                continue
            seen.add(name)
            producer = defs.get(name)
            if producer is None:
                continue
            out.append(producer)
            if producer.op in through:
                frontier.extend(operand_names(producer))
        return out

    def param_number(self, ins: Instr) -> Optional[int]:
        if ins.op != "parameter":
            return None
        m = re.match(r"(\d+)\)", ins.rest.strip())
        return int(m.group(1)) if m else None
