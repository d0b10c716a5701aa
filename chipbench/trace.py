"""From a profiler trace to metrics.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  A device plane (``/device:TPU:<n>``) has a line ``XLA Ops``
whose events are the executed HLO instructions, named by their text
(``%fusion.12 = bf16[...] fusion(...)``); control-flow instructions such
as ``while`` enclose the instructions of their bodies.  Host planes hold
the benchmark's own spans (``jax.profiler.TraceAnnotation`` named
``chipbench.*``) on the thread that opened them.  Host and device events
share one clock in the file.

The reduction:

* the window is the benchmark's ``chipbench.window`` span;
* busy time is the union of the innermost device operations inside the
  window, averaged over the chips that ran any;
* a Pallas kernel is known by its instruction: the compiled program's text
  names the kernel function in each ``tpu_custom_call``'s body
  (:func:`kernel_calls`), with the shapes of its operands and results;
* idle gaps are the stretches of the window with no device operation,
  labelled by the innermost benchmark span open at their middle, and by
  the program (``XLA Modules``) whose execution holds them, where one
  does: such a gap lies between operations of one program, where the
  host has nothing to hand the device.
"""
from __future__ import annotations

import base64
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
_KERNEL = re.compile(rb"(_?[A-Za-z][A-Za-z0-9_]*_kernel)(?![A-Za-z0-9_.])")


@dataclasses.dataclass(frozen=True)
class Call:
    """One Pallas kernel instruction of a compiled program."""
    kernel: str
    outputs: Tuple[Tuple[str, Tuple[int, ...]], ...]
    operands: Tuple[Tuple[str, Tuple[int, ...]], ...]

    @property
    def nbytes(self) -> float:
        """Bytes of every operand read once and every result written once."""
        tot = 0
        for dt, shape in self.operands + self.outputs:
            n = _DTYPE_BYTES[dt]
            for d in shape:
                n *= d
            tot += n
        return float(tot)


def _shapes(text: str) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    return tuple((dt, tuple(int(x) for x in dims.split(",") if x))
                 for dt, dims in _SHAPE.findall(text))


def kernel_calls(hlo_text: str) -> Dict[str, Call]:
    """Instruction name -> :class:`Call` for every ``tpu_custom_call`` in a
    compiled program's text.  The kernel's name is the first identifier
    ending in ``_kernel`` in the kernel body (the Pallas kernel function's
    name, which its source locations carry)."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        head = rest.partition(" custom-call(")[0]
        # compiled text names operands bare (``%copy.3``); their shapes
        # are in the custom call's layout constraints, in order
        cons = _OPERANDS.search(line)
        operands = cons.group(1) if cons else ""
        body = re.search(r'body\\?":\\?"([A-Za-z0-9+/=]+)', line)
        kernel = "unknown_kernel"
        if body:
            found = _KERNEL.search(base64.b64decode(body.group(1) + "=="))
            if found:
                kernel = found.group(1).decode()
        out[name] = Call(kernel, _shapes(head), _shapes(operands))
    return out


@dataclasses.dataclass
class Op:
    name: str          # instruction name, e.g. ``fusion.12``
    start: int         # ns
    end: int


def _instr_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")


def _innermost(ops: List[Op]) -> List[Op]:
    """Drop every operation that encloses another (a loop, a call): what
    is left ran on the device in its own right."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    keep = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue
        keep.append(o)
    return keep


def _union(ops: List[Op]) -> List[Tuple[int, int]]:
    spans: List[List[int]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if spans and o.start <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], o.end)
        else:
            spans.append([o.start, o.end])
    return [(a, b) for a, b in spans]


@dataclasses.dataclass
class Reduction:
    """What one traced window holds."""
    window: Tuple[int, int]
    chips: Dict[str, List[Op]]               # device plane -> innermost ops
    spans: List[Op]                          # chipbench.* host spans
    calls: Dict[str, Call]
    modules: Dict[str, List[Op]] = dataclasses.field(default_factory=dict)
    #: device plane -> executions of whole programs (``XLA Modules``),
    #: named without the hash, e.g. ``jit_train_step``

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.chips:
            return 0.0
        tot = sum(sum(b - a for a, b in _union(ops))
                  for ops in self.chips.values())
        return tot / len(self.chips) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_ops(self, kernels) -> List[Tuple[Op, Call]]:
        """Every execution of a Pallas kernel named in ``kernels``, with
        its instruction's shapes."""
        out = []
        for ops in self.chips.values():
            for o in ops:
                c = self.calls.get(o.name)
                if c is not None and c.kernel in kernels:
                    out.append((o, c))
        return out

    def label(self, t: int) -> str:
        """The innermost benchmark span open at time ``t``."""
        best: Optional[Op] = None
        for s in self.spans:
            if s.start <= t <= s.end and (best is None
                                          or s.end - s.start
                                          < best.end - best.start):
                best = s
        return best.name if best else "outside any chipbench span"

    def program_at(self, plane: str, t: int) -> Optional[str]:
        """The program executing on ``plane`` at time ``t``, if any."""
        for m in self.modules.get(plane, ()):
            if m.start <= t <= m.end:
                return m.name
        return None

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time (kernels by kernel
        name, others by instruction), and idle time by what the host was
        doing, both in seconds."""
        per: Dict[str, float] = {}
        for ops in self.chips.values():
            for o in ops:
                c = self.calls.get(o.name)
                key = f"pallas:{c.kernel}" if c else o.name
                per[key] = per.get(key, 0.0) + (o.end - o.start) / 1e9
        gaps: Dict[str, float] = {}
        for plane, ops in self.chips.items():
            prev = self.window[0]
            for a, b in _union(ops) + [(self.window[1], self.window[1])]:
                if a > prev:
                    mid = (prev + a) // 2
                    lab = self.label(mid)
                    prog = self.program_at(plane, mid)
                    if prog:
                        lab = f"{lab} (inside {prog})"
                    gaps[lab] = gaps.get(lab, 0.0) + (a - prev) / 1e9
                prev = max(prev, b)
        n = max(len(self.chips), 1)
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / n] for k, v in order(per)],
                "idle_gaps": [[k, v / n] for k, v in order(gaps)]}


def reduce_file(path: str, hlo_text: str = "") -> Reduction:
    """Reduce one ``.xplane.pb``.  ``hlo_text`` is the compiled text of the
    programs the window ran, for the names of their kernels."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    spans: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[plane.name] = [
                        Op(_instr_name(e.name), int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        Op(e.name.split("(", 1)[0], int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append(Op(e.name, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns)))
    win = [s for s in spans if s.name == "chipbench.window"]
    if win:
        window = (win[0].start, win[-1].end)
    else:
        allops = [o for ops in chips.values() for o in ops]
        window = (min(o.start for o in allops), max(o.end for o in allops))
    clipped = {}
    for plane, ops in chips.items():
        inside = [Op(o.name, max(o.start, window[0]), min(o.end, window[1]))
                  for o in ops if o.end > window[0] and o.start < window[1]]
        if inside:
            clipped[plane] = _innermost(inside)
    mods = {plane: [o for o in ops if o.end > window[0]
                    and o.start < window[1]]
            for plane, ops in modules.items()}
    return Reduction(window, clipped, spans, kernel_calls(hlo_text), mods)


class Window:
    """Brackets a cell's measured window: counts the traces and compiles
    JAX reports inside it (there should be none), and with ``trace`` on
    profiles it into a fresh directory under ``TMPDIR``.  ``hlo_text`` is
    set by the cell before :meth:`reduce`."""

    def __init__(self, trace: bool):
        import jax
        self.trace = trace
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
            else None
        self.hlo_text = ""
        self.compiles = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **kw) -> None:
        if self._open and event.startswith("/jax/core/compile/"):
            self.compiles += 1

    def start(self) -> None:
        import jax
        if self.trace:
            jax.profiler.start_trace(self.dir)
        self._open = True

    def stop(self) -> None:
        import jax
        self._open = False
        if self.trace:
            jax.profiler.stop_trace()

    def reduce(self) -> Reduction:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return reduce_file(files[0], self.hlo_text)

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets: the trace's reduction, the
    cell's configuration, traffic and run results, and the device kind."""
    red: Reduction
    conf: Dict
    mix: Dict
    res: Dict
    device_kind: str
