"""Device time by the program's own names: component and phase.

The program names its work with ``jax.named_scope``; JAX writes the scope
path of every operation into the compiled program's text, as
``metadata={op_name="jit(train_step)/jvp(layers)/while/body/.../qdq/..."}``.
A scope entered at a transformation's boundary appears inside the
transformation's name (``jvp(norm)``, ``transpose(jvp(layers))``); a
jitted function appears as ``jit(<function>)``, which names no scope.

Attribution of one executed instruction:

* its ``op_name``: the instruction's own metadata, else (a fusion or a
  custom call without it) that of the root of the computation it calls,
  or where that root is one XLA added (a convert, unnamed), of the
  nearest named instruction before it; so a fusion counts under the scope
  of its root operation.  An instruction XLA added with no name at all
  (a copy, a rewritten reduction, a broadcast constant) takes that of its
  first named operand, else of its first named user, else of the
  instruction whose computation holds it (a loop's);
* component: ``optimizer`` where the path holds that scope (the whole
  update is the optimizer's, its moment codec too), else the innermost
  scope of :data:`COMPONENTS` on the path, or of the extra names the
  configuration lists under ``scopes``, matched as a whole name; an
  instruction with none is ``unattributed``;
* phase: ``optimizer`` inside that scope; ``recompute`` where the path
  holds ``rematted_computation`` (JAX's name for work that
  ``jax.checkpoint`` redoes in the backward); ``backward`` inside a
  ``transpose(...)``; ``forward`` otherwise.

The names come from the step compiled afresh (:func:`compiled_texts`),
mapped instruction by instruction onto the executable that ran, which
JAX's persistent cache may have returned with an older build's names.

:func:`partition` splits a traced window into parts that sum to it: each
operation's device time goes to its (component, phase), an idle gap inside
a program's execution to the (component, phase) of the operation that
ends it (what the device waited to start), and idle time outside any
program to :data:`BETWEEN`.
"""
from __future__ import annotations

import dataclasses
import json
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import registry
from chipbench import trace as cbtrace

#: the program's scope names, one per component of the train step
COMPONENTS = ("embed", "layers", "norm", "attention", "linear", "qdq",
              "head", "weights_cast", "optimizer")
PHASES = ("forward", "backward", "recompute", "optimizer")
#: names JAX itself puts on the path, which no configuration may claim
JAX_NAMES = frozenset({"while", "body", "checkpoint", "rematted_computation",
                       "jvp", "transpose"})
UNATTRIBUTED = "unattributed"
#: idle time with no program executing
BETWEEN = "between programs"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=(%?[\w.\-]+)|"
                     r"\bbranch_computations=\{([^}]*)\}")


def _split(path: str) -> List[str]:
    """Components of a name path, split at the slashes outside brackets."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return [c for c in out if c]


def _names(path: str) -> List[str]:
    """Every name on the path, outermost first, with transformations
    unwrapped: ``transpose(jvp(layers))`` gives ``layers``.  ``jit(f)``
    gives nothing: f names a function, not a scope."""
    out = []
    for comp in _split(path):
        m = _WRAPPED.match(comp)
        if m is None:
            out.append(comp)
        elif m.group(1) != "jit":
            out.extend(_names(m.group(2)))
    return out


def components(extra: Sequence[str] = ()) -> Tuple[str, ...]:
    """:data:`COMPONENTS` and a configuration's extra scope names; a name
    that JAX itself puts on paths is refused."""
    taken = sorted(JAX_NAMES.intersection(extra))
    if taken:
        raise registry.BenchError(
            f"scope names {taken} are JAX's own and name no component")
    return COMPONENTS + tuple(n for n in extra if n not in COMPONENTS)


def classify(op_name: str, extra: Sequence[str] = ()) -> Tuple[str, str]:
    """(component, phase) of one operation's name path; ``extra`` are the
    configuration's own component names (its ``scopes``)."""
    plain = _names(op_name)
    if "optimizer" in plain:
        return "optimizer", "optimizer"
    comps = components(extra)
    comp = next((n for n in reversed(plain) if n in comps), UNATTRIBUTED)
    if "rematted_computation" in plain:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return comp, phase


@dataclasses.dataclass
class Program:
    """What the compiled text says of each instruction."""
    op_names: Dict[str, str]       # instruction -> op_name ('' where none)
    instructions: int              # instruction lines, all computations
    fusions: int
    custom_calls: int


def read_program(hlo_text: str) -> Program:
    """Instruction name -> ``op_name`` for every instruction of a compiled
    program's text, by the rules of the module docstring."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}      # computation -> its root's op_name
    operands: Dict[str, List[str]] = {}
    home: Dict[str, Optional[str]] = {}   # instruction -> its computation
    callers: Dict[str, str] = {}    # computation -> an instruction calling it
    comp = None
    last = ""                       # the last op_name seen in ``comp``
    n = fusions = customs = 0
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
            m = _COMPUTATION.match(line)
            comp, last = (m.group(1) if m else None), ""
            continue
        m = cbtrace._INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        n += 1
        fusions += " fusion(" in rest
        customs += " custom-call(" in rest
        op = _OP_NAME.search(rest)
        own[name] = op.group(1) if op else ""
        last = own[name] or last
        operands[name] = _operands(rest)
        home[name] = comp
        c = _CALLS.search(rest)
        if c:
            calls[name] = c.group(1)
        for one, branches in _CALLED.findall(rest):
            for callee in [one] if one else _OPERAND.findall(branches):
                callers.setdefault(callee.lstrip("%"), name)
        if line.lstrip().startswith("ROOT") and comp is not None:
            # a root XLA added (a convert) carries no name: the text lists
            # operands before users, so the last name seen is the nearest
            roots[comp] = last
    names: Dict[str, str] = {}
    for name, op in own.items():          # in text order: operands first
        if not op and name in calls:
            op = roots.get(calls[name], "")
        if not op:
            op = next((names[a] for a in operands.get(name, ())
                       if names.get(a)), "")
        names[name] = op

    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for a in ops:
            users.setdefault(a, []).append(name)

    def borrowed(name: str) -> str:
        """The nearest name among the instruction's users and the caller
        of its computation, and theirs in turn (breadth first)."""
        queue, seen = [name], {name}
        for cur in queue:
            for other in users.get(cur, []) + [callers.get(home.get(cur))]:
                if other and other not in seen:
                    if names.get(other):
                        return names[other]
                    seen.add(other)
                    queue.append(other)
        return ""

    for name in names:
        names[name] = names[name] or borrowed(name)
    return Program(names, n, fusions, customs)


def _operands(rest: str) -> List[str]:
    """The operand names of an instruction's text after ``=``."""
    m = _OPCODE.search(rest)
    if not m:
        return []
    depth, i = 1, m.end()
    while i < len(rest) and depth:
        depth += (rest[i] == "(") - (rest[i] == ")")
        i += 1
    return _OPERAND.findall(rest[m.end():i])


@dataclasses.dataclass
class Partition:
    """A traced window split by what the device did, in seconds averaged
    over the chips; the parts sum to ``window_s``."""
    window_s: float
    device: Dict[Tuple[str, str], float]      # (component, phase) -> busy
    idle_before: Dict[Tuple[str, str], float]  # in-program idle, by the op
    between: float                            # idle outside any program
    top_unattributed: List[Tuple[str, float]]

    def component_s(self, comp: str) -> Optional[float]:
        """Busy and waited-on seconds of one component; None where none of
        its operations ran."""
        if not any(c == comp for c, _ in self.device):
            return None
        return sum(v for (c, _), v in self.device.items() if c == comp) + \
            sum(v for (c, _), v in self.idle_before.items() if c == comp)

    def phase_s(self, phase: str) -> Optional[float]:
        if not any(p == phase for _, p in self.device):
            return None
        return sum(v for (_, p), v in self.device.items() if p == phase) + \
            sum(v for (_, p), v in self.idle_before.items() if p == phase)

    def share(self, seconds: Optional[float]) -> Optional[float]:
        """Percent of the window."""
        return None if seconds is None else 100.0 * seconds / self.window_s

    def total_s(self) -> float:
        return (sum(self.device.values()) + sum(self.idle_before.values())
                + self.between)

    def breakdown(self) -> Dict[str, Dict[str, float]]:
        """``device_scopes``: busy seconds by ``component/phase``;
        ``idle_before``: in-program idle seconds by the component of the
        operation that ends the gap, with idle outside any program under
        :data:`BETWEEN`."""
        idle: Dict[str, float] = {}
        for (c, _), v in self.idle_before.items():
            idle[c] = idle.get(c, 0.0) + v
        idle[BETWEEN] = self.between
        order = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
        return {"device_scopes": order({f"{c}/{p}": v for (c, p), v
                                        in self.device.items()}),
                "idle_before": order(idle)}


def partition(red: cbtrace.Reduction, program: Program, top: int = 5,
              extra: Sequence[str] = ()) -> Partition:
    """Split ``red``'s window by component and phase (module docstring);
    ``extra`` as for :func:`classify`."""
    keys: Dict[str, Tuple[str, str]] = {}

    def key(name: str) -> Tuple[str, str]:
        if name not in keys:
            keys[name] = classify(program.op_names.get(name, ""), extra)
        return keys[name]

    device: Dict[Tuple[str, str], float] = {}
    idle: Dict[Tuple[str, str], float] = {}
    between = 0.0
    unnamed: Dict[str, float] = {}
    add = lambda d, k, ns: d.__setitem__(k, d.get(k, 0.0) + ns / 1e9)
    for plane, ops in red.chips.items():
        covered = red.window[0]
        for o in sorted(ops, key=lambda o: (o.start, o.end)):
            k = key(o.name)
            if o.start > covered:
                gap = o.start - covered
                if red.program_at(plane, (covered + o.start) // 2):
                    add(idle, k, gap)
                else:
                    between += gap / 1e9
            start = max(o.start, covered)
            if o.end > start:
                add(device, k, o.end - start)
                if k[0] == UNATTRIBUTED:
                    add(unnamed, o.name, o.end - start)
            covered = max(covered, o.end)
        if red.window[1] > covered:
            between += (red.window[1] - covered) / 1e9
    n = max(len(red.chips), 1)
    scale = lambda d: {k: v / n for k, v in d.items()}
    worst = sorted(unnamed.items(), key=lambda kv: -kv[1])[:top]
    return Partition(red.window_s, scale(device), scale(idle), between / n,
                     [(k, v / n) for k, v in worst])


def signature(hlo_text: str) -> List[str]:
    """Result type and opcode of every instruction, in text order: what
    two compilations of one program share whatever their names and
    metadata."""
    out = []
    for line in hlo_text.splitlines():
        m = cbtrace._INSTR.match(line)
        if m:
            op = _OPCODE.search(m.group(2))
            out.append(m.group(2)[:op.end()] if op else m.group(2))
    return out


def renamed(ran: str, named: str) -> Optional[Program]:
    """The program that ran, its instructions under their own names, with
    each one's ``op_name`` taken from ``named``: the same program compiled
    again, instruction for instruction.  None where the two differ."""
    if signature(ran) != signature(named):
        return None
    prog, names = read_program(ran), read_program(named)
    return dataclasses.replace(prog, op_names=dict(zip(
        prog.op_names, names.op_names.values())))


def _compile_step(cell, cache: bool) -> str:
    """The cell's step compiled again the way the cell compiled it: with
    ``cache`` on, as the window's run got it (a hit in JAX's persistent
    cache); with it off, afresh from the code."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    if not cache:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
    try:
        cell.setup()
        return cell.step.as_text()
    finally:
        if hasattr(cell, "step"):
            del cell.step
        if not cache:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def compiled_texts(ctx: cbtrace.Context) -> Tuple[str, str]:
    """(ran, named): the compiled text of the program the window ran, and
    of the same step compiled afresh.  JAX keys its persistent cache on a
    program without its metadata, so an executable loaded from it carries
    the names of whichever build stored it; only a fresh compile carries
    the names of the code that ran.  ``ran`` is the reduction's
    ``hlo_text`` where it holds one, else the training cell's step compiled
    again with the cache; with no cell to compile, both are ``ran``."""
    ran = getattr(ctx.red, "hlo_text", "")
    cell = ctx.res.get("cell")
    if cell is None or not hasattr(cell, "setup"):
        return ran, ran
    ran = ran or _compile_step(cell, cache=True)
    return ran, _compile_step(cell, cache=False)


def of_context(ctx: cbtrace.Context) -> Optional[Partition]:
    """The partition of a training cell's traced window, computed once per
    context (the readers share it) and reported on standard error
    (``chipbench.scopes`` and a JSON object); None where there is nothing
    to read, or where the compiled texts do not match the program the
    window ran."""
    if ctx.mix.get("kind") != "train" or not ctx.red.chips:
        return None
    if not hasattr(ctx, "_scopes"):
        ctx._scopes = _read(ctx)
    return ctx._scopes


def _read(ctx: cbtrace.Context) -> Optional[Partition]:
    ran, named = compiled_texts(ctx)
    prog = renamed(ran, named) if ran else None
    if prog is None or set(cbtrace.kernel_calls(ran)) != set(ctx.red.calls):
        if ran:
            print("chipbench.scopes: the compiled texts do not match the "
                  "program the window ran; nothing read", file=sys.stderr,
                  flush=True)
        return None
    extra = ctx.conf.get("scopes", [])
    part = partition(ctx.red, prog, extra=extra)
    report = dict(part.breakdown(), window_s=part.window_s,
                  top_unattributed=part.top_unattributed,
                  instructions=prog.instructions, fusions=prog.fusions,
                  custom_calls=prog.custom_calls,
                  ran_carried_names=any(
                      classify(op, extra)[0] != UNATTRIBUTED for op in
                      read_program(ran).op_names.values()))
    print("chipbench.scopes " + json.dumps(report), file=sys.stderr,
          flush=True)
    return part
