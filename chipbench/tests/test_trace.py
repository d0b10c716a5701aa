"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps.

``data/tiny.xplane.pb`` was recorded on one TPU v5e chip by
``data/record_trace.py``: three calls of a program that runs the int8
matmul Pallas kernel and an XLA reduction, each followed by a 20 ms host
sleep inside a ``chipbench.wait`` span; ``data/tiny_hlo.txt`` is that
program's compiled text.  The tests prove that the reduction finds the
device plane, the window, the kernel by its name and shapes, and labels
the idle time by the span the host was in.  They read the file; they need
no chip.
"""
import pathlib

import pytest

from chipbench import flops, peaks, registry
from chipbench import trace as cbtrace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def red():
    hlo = (DATA / "tiny_hlo.txt").read_text()
    return cbtrace.reduce_file(str(DATA / "tiny.xplane.pb"), hlo)


def test_kernel_named_from_the_compiled_text():
    calls = cbtrace.kernel_calls((DATA / "tiny_hlo.txt").read_text())
    kernels = [c for c in calls.values()
               if c.kernel == "_int8_matmul_kernel"]
    assert len(kernels) == 1
    c = kernels[0]
    assert c.operands[:2] == (("s8", (512, 512)), ("s8", (512, 256)))
    assert c.outputs == (("bf16", (512, 256)),)
    assert registry.kernel_ops(c.kernel).ops(c) == 2 * 512 * 512 * 256


def test_window_and_busy_time(red):
    assert len(red.chips) == 1
    assert 0.06 < red.window_s < 5.0          # three 20 ms sleeps and more
    assert 0.0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share() < 1.0


def test_kernel_time_and_roofline(red):
    runs = red.kernel_ops(("_int8_matmul_kernel",))
    # the device's part of the profile began after the first call had run:
    # two of the three calls are in the file
    assert len(runs) == 2
    spent = sum(o.end - o.start for o, _ in runs) / 1e9
    least = sum(flops.kernel_least_time(c, "TPU v5 lite") for _, c in runs)
    assert 0.0 < peaks.share(least, spent) <= 105.0


def test_breakdown_labels_idle_time_by_host_span(red):
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert "pallas:_int8_matmul_kernel" in dict(bd["device_ops"])
    gaps = dict(bd["idle_gaps"])
    assert max(gaps, key=gaps.get) == "chipbench.wait"
    assert gaps["chipbench.wait"] >= 0.055     # the three sleeps


def test_innermost_and_union_on_nested_ops():
    Op = cbtrace.Op
    ops = [Op("while.1", 0, 100), Op("fusion.1", 10, 40),
           Op("fusion.2", 50, 90), Op("copy.1", 120, 130)]
    inner = cbtrace._innermost(ops)
    assert [o.name for o in inner] == ["fusion.1", "fusion.2", "copy.1"]
    assert cbtrace._union(inner) == [(10, 40), (50, 90), (120, 130)]
    red = cbtrace.Reduction((0, 200), {"/device:TPU:0": inner},
                            [Op("chipbench.wait", 90, 125)], {})
    assert red.busy_s == pytest.approx(80e-9)
    gaps = dict(red.breakdown()["idle_gaps"])
    assert gaps["chipbench.wait"] == pytest.approx(30e-9)
    assert gaps["outside any chipbench span"] == pytest.approx(90e-9)


def test_idle_inside_a_program_is_told_from_idle_between_programs():
    Op = cbtrace.Op
    plane = "/device:TPU:0"
    ops = [Op("fusion.1", 0, 40), Op("fusion.2", 60, 100),
           Op("fusion.1", 130, 170)]
    mods = {plane: [Op("jit_step", 0, 100), Op("jit_step", 130, 170)]}
    red = cbtrace.Reduction((0, 170), {plane: ops},
                            [Op("chipbench.wait", 0, 170)], {}, mods)
    gaps = dict(red.breakdown()["idle_gaps"])
    assert gaps == {"chipbench.wait (inside jit_step)": pytest.approx(20e-9),
                    "chipbench.wait": pytest.approx(30e-9)}
    assert red.program_at(plane, 110) is None
