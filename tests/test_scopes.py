"""The train step names its work for the device trace.

Every layer boundary of the train path sets a ``jax.named_scope``
(``embed``, ``layers``, ``norm``, ``attention``, ``linear``, ``qdq``,
``head``, ``weights_cast``, ``optimizer``); JAX writes the scope path into
each compiled instruction's ``op_name``, and the benchmark's reduction
(``chipbench/scopes.py``) reads device time by those names.  These tests
compile a tiny train step on the CPU (gpt2 smoke config; the int8 policy
of the ``gpt2s-pretrain-int8`` cell and the fp baseline) and hold every
instruction the device would execute to a known component and phase.
"""
import re

import jax
import pytest

from chipbench import scopes
from repro.configs import get_smoke_config
from repro.core import quantizer
from repro.models import build_model
from repro.optim import OptConfig
from repro.train.step import lower_train_hlo

INT8 = "*=w8c+a8t+g8t+m1:8c-b128+m2:8c-asym-b128-sqrt@int8_pallas"
#: name -> (policy, moment storage, components its step must hold)
PROGRAMS = {
    "int8": (INT8, "int", {"embed", "layers", "norm", "attention", "linear",
                           "qdq", "head", "weights_cast", "optimizer"}),
    "fp": ("*=fp", "fake", {"embed", "layers", "norm", "attention",
                            "linear", "head", "optimizer"}),
}
_EXECUTED = ("fusion", "dot", "custom-call", "convolution")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")


def _compile(name: str) -> str:
    policy, storage, _ = PROGRAMS[name]
    model = build_model(get_smoke_config("gpt2-small"))
    return lower_train_hlo(model, policy, OptConfig(state_storage=storage))


@pytest.fixture(scope="module")
def compiled():
    return {name: _compile(name) for name in PROGRAMS}


def executed(hlo_text: str):
    """(instruction, opcode) of every fusion, dot and custom call in the
    entry computation and the bodies of its loops and conditionals."""
    body: dict = {}
    comp = entry = None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            entry = comp if line.startswith("ENTRY") else entry
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", line)
        if m and comp:
            body.setdefault(comp, []).append((m.group(1), m.group(2)))
    todo, seen, out = [entry], {entry}, []
    while todo:
        for name, rest in body.get(todo.pop(), []):
            op = _OPCODE.search(rest).group(1)
            if op in _EXECUTED:
                out.append((name, op))
            called = re.findall(r"\bbody=%?([\w.\-]+)", rest)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", rest):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
    return out


def problems(hlo_text: str, want: set) -> list:
    """Every way the compiled step falls short of its names."""
    prog = scopes.read_program(hlo_text)
    found, out = set(), []
    for name, op in executed(hlo_text):
        path = prog.op_names[name]
        comp, phase = scopes.classify(path)
        found.add((comp, phase))
        if comp == scopes.UNATTRIBUTED:
            out.append(f"{op} {name} has no known scope: {path!r}")
    # every rounding, fused or not, is quantization -- but for those inside
    # a Pallas kernel (the int8 backward kernels quantize g in their grid),
    # which is one instruction on the chip, interpreted op by op here
    for name, path in prog.op_names.items():
        comp, _ = scopes.classify(path)
        if (path.endswith("/round") and comp not in ("qdq", "optimizer")
                and "_kernel/" not in path):
            out.append(f"rounding {name} counts under {comp}: {path!r}")
    comps = {c for c, _ in found}
    for c in sorted(want - comps):
        out.append(f"no instruction under {c}")
    if "qdq" not in want and "qdq" in comps:
        out.append("the fp step quantizes")
    return out


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_every_executed_instruction_is_named(compiled, name):
    assert executed(compiled[name])
    assert problems(compiled[name], PROGRAMS[name][2]) == []


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_recompute_and_backward_told_apart(compiled, name):
    prog = scopes.read_program(compiled[name])
    found = {scopes.classify(prog.op_names[n]) for n, _ in
             executed(compiled[name])}
    # the layer scan's body is rematerialized: its norms run forward,
    # again as recomputation, and transposed in the backward
    for part in ("norm", "attention"):
        for phase in ("forward", "recompute", "backward"):
            assert (part, phase) in found, (part, phase)
    assert ("optimizer", "optimizer") in found
    assert {p for _, p in found} == set(scopes.PHASES)


@pytest.mark.parametrize("path, want", [
    ("jit(train_step)/jvp(layers)/while/body/closed_call/norm/mul",
     ("norm", "forward")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/linear/linear/qdq/jit(round)/round",
     ("qdq", "recompute")),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/linear/linear/_int8_matmul_nt_kernel", ("linear",
                                                         "backward")),
    ("jit(train_step)/optimizer/qdq/jit(round)/round",
     ("optimizer", "optimizer")),
    ("jit(train_step)/jvp(weights_cast)/convert_element_type",
     ("weights_cast", "forward")),
    ("jit(train_step)/jit(norm)/mul", (scopes.UNATTRIBUTED, "forward")),
    ("", (scopes.UNATTRIBUTED, "forward")),
])
def test_classify(path, want):
    assert scopes.classify(path) == want


def _drop_scope(monkeypatch, name):
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda n: real(
        "unnamed" if n == name else n))


@pytest.mark.parametrize("removed", ["quantize_int", "optimizer",
                                     "attention"])
def test_removing_a_scope_is_caught(monkeypatch, removed):
    if removed == "quantize_int":
        # the int8 linears' quantize, unscoped: its rounding lands under
        # ``linear``
        bare = quantizer.quantize_int.__wrapped__
        from repro.core import qlinear
        from repro.kernels import ops
        monkeypatch.setattr(qlinear, "quantize_int", bare)
        monkeypatch.setattr(ops, "quantize_int", bare)
    else:
        _drop_scope(monkeypatch, removed)
    found = problems(_compile("int8"), PROGRAMS["int8"][2])
    assert found
    if removed == "quantize_int":
        assert any(p.startswith("rounding") and "linear" in p
                   for p in found)
    else:
        assert f"no instruction under {removed}" in found
