"""Device time by the program's own names (``chipbench/scopes.py``).

``data/tiny_scoped.xplane.pb`` was recorded on one TPU v5e chip by
``data/record_scoped_trace.py``: three steps of a train step in the
program's names (a ``layers`` scan that ``jax.checkpoint`` recomputes,
``norm``, the program's int8 linear under ``linear`` and ``qdq``, a
``head`` and an ``optimizer`` update), each followed by a 20 ms host sleep;
``data/tiny_scoped_hlo.txt`` is that program's compiled text.  The tests
read the files; they need no chip.  The last test holds the reduction's
older outputs to what they were before the names came, on the older
``data/tiny.xplane.pb``.
"""
import pathlib
import re

import pytest

from chipbench import registry, run, scopes, train
from chipbench import trace as cbtrace
from chipbench.tests import tiny

DATA = pathlib.Path(__file__).parent / "data"
TRAIN = {"kind": "train"}
NEW = ("qdq_share.train", "optimizer_share.train", "recompute_share.train")


def _reduce(stem: str):
    hlo = (DATA / f"{stem}_hlo.txt").read_text()
    red = cbtrace.reduce_file(str(DATA / f"{stem}.xplane.pb"), hlo)
    red.hlo_text = hlo
    return red


@pytest.fixture(scope="module")
def red():
    return _reduce("tiny_scoped")


@pytest.fixture(scope="module")
def part(red):
    return scopes.partition(red, scopes.read_program(red.hlo_text))


def test_partition_sums_to_the_window(red, part):
    assert part.window_s == red.window_s
    assert part.total_s() == pytest.approx(red.window_s, rel=1e-12)
    assert sum(part.device.values()) == pytest.approx(red.busy_s,
                                                      rel=1e-12)
    # the sleeps: idle with no program executing
    gaps = dict(red.breakdown()["idle_gaps"])
    assert part.between == pytest.approx(gaps["chipbench.wait"], rel=1e-12)
    assert sum(part.idle_before.values()) == pytest.approx(
        gaps["chipbench.wait (inside jit_step)"], rel=1e-9)


def test_seconds_by_component_and_phase(part):
    bd = part.breakdown()
    want = {"linear/backward": 693.36e-6, "linear/forward": 217.494e-6,
            "qdq/forward": 25.34e-6, "optimizer/optimizer": 20.674e-6,
            "qdq/backward": 19.763e-6, "qdq/recompute": 14.846e-6,
            "norm/backward": 13.401e-6, "norm/forward": 10.0e-6,
            "unattributed/forward": 8.368e-6, "norm/recompute": 3.268e-6,
            "layers/backward": 2.943e-6, "head/forward": 2.048e-6,
            "layers/forward": 1.417e-6}
    assert bd["device_scopes"] == pytest.approx(want, abs=1e-12)
    assert list(bd["device_scopes"]) == list(want)      # largest first
    assert bd["idle_before"]["between programs"] == pytest.approx(
        0.065917473, abs=1e-12)
    assert bd["idle_before"]["qdq"] == pytest.approx(173e-9, abs=1e-12)
    assert part.component_s("head") == pytest.approx(2.05e-6, abs=1e-12)
    assert part.component_s("embed") is None
    # the recomputed norms and quantizers, and the 85 ns the device
    # waited for them
    assert part.phase_s("recompute") == pytest.approx(18.199e-6, abs=1e-12)


def test_kernels_count_under_linear(red):
    """Each int8 kernel's instruction is a ``linear``: the forward kernel
    in the forward, the two transposed kernels in the backward (the
    weight-gradient kernel runs as a fusion around its call)."""
    prog = scopes.read_program(red.hlo_text)
    seen = {}
    for o in red.chips["/device:TPU:0"]:
        c = red.calls.get(o.name)
        kernel = c.kernel if c else o.name.rsplit(".", 1)[0]
        if kernel.startswith("_int8_matmul"):
            seen[kernel] = scopes.classify(prog.op_names[o.name])
    assert seen == {"_int8_matmul_kernel": ("linear", "forward"),
                    "_int8_matmul_nt_kernel": ("linear", "backward"),
                    "_int8_matmul_tn_kernel": ("linear", "backward")}


def test_readers_read_the_partition(red, part):
    ctx = cbtrace.Context(red, {}, TRAIN, {}, "TPU v5 lite")
    got = {n: registry.metric_reader(n).read(ctx) for n in NEW}
    assert got == {
        "qdq_share.train": part.share(part.component_s("qdq")),
        "optimizer_share.train": part.share(part.component_s("optimizer")),
        "recompute_share.train": part.share(part.phase_s("recompute"))}
    assert all(0.0 < v < 100.0 for v in got.values())
    assert got["qdq_share.train"] == pytest.approx(
        100 * (59.949e-6 + 0.173e-6) / red.window_s, rel=1e-6)
    serve = cbtrace.Context(red, {}, {"kind": "serve"}, {}, "TPU v5 lite")
    assert [registry.metric_reader(n).read(serve) for n in NEW] == \
        [None] * 3


def test_readers_find_nothing_in_a_program_without_names():
    """The older trace's program sets no scope: every operation is
    unattributed, so the component readers return None."""
    ctx = cbtrace.Context(_reduce("tiny"), {}, TRAIN, {}, "TPU v5 lite")
    assert [registry.metric_reader(n).read(ctx) for n in NEW] == \
        [None] * 3
    assert scopes.of_context(ctx).component_s(scopes.UNATTRIBUTED) > 0


def test_step_compiled_again_is_the_step_that_ran():
    """Without compiled text on the reduction, the readers compile the
    cell's step again the way the cell did, with the persistent cache and
    without it, and get the same program: the same instructions under the
    same names (on the chip the cache returns the very executable; here
    only the Python stack frames in the metadata differ)."""
    _, _, conf, mix, lim = tiny.cell("gpt2s-pretrain-int8")
    cell = train.TrainCell(conf, mix, lim)
    cell.setup()
    ran = scopes.read_program(cell.step.as_text())
    del cell.step
    red = cbtrace.Reduction((0, 1), {}, [], {})
    ctx = cbtrace.Context(red, conf, mix, {"cell": cell}, "cpu")
    again, fresh = scopes.compiled_texts(ctx)
    assert scopes.read_program(again) == ran and len(ran.op_names) > 1000
    assert scopes.renamed(again, fresh) == ran
    assert not hasattr(cell, "step")


def test_names_of_a_stale_executable_come_from_the_fresh_compile(red):
    """An executable from the persistent cache may carry an older build's
    instruction names and no scopes; the fresh compile's names map onto it
    instruction by instruction, and a program that differs maps not."""
    fresh = red.hlo_text
    stale = re.sub(r'op_name="[^"]*"', 'op_name=""',
                   re.sub(r"%([\w.\-]+)", r"%\1_old", fresh))
    want = scopes.read_program(fresh).op_names
    got = scopes.renamed(stale, fresh)
    assert got.op_names == {f"{k}_old": v for k, v in want.items()}
    assert scopes.read_program(stale).op_names != got.op_names
    shorter = "\n".join(line for line in stale.splitlines()
                        if "%copy.34_old = " not in line)
    assert scopes.renamed(shorter, fresh) is None


def test_older_outputs_unchanged():
    """``breakdown()`` and the four older readers on the older trace read
    what they read before the program had names."""
    red = cbtrace.reduce_file(str(DATA / "tiny.xplane.pb"),
                              (DATA / "tiny_hlo.txt").read_text())
    assert red.breakdown() == {
        "device_ops": [["pallas:_int8_matmul_kernel", 1.9217e-05],
                       ["copy", 1.3569999999999999e-06],
                       ["multiply_reduce_fusion", 9.98e-07],
                       ["copy-done", 2.5e-08],
                       ["copy-start", 6.000000000000001e-09],
                       ["copy-start.1", 5e-09], ["copy-done.1", 5e-09]],
        "idle_gaps": [["chipbench.wait", 0.065000841],
                      ["chipbench.wait (inside jit__lambda)",
                       1.4000000000000005e-08]]}
    bench = registry.benchmark()
    conf = registry.config(bench, "gpt2-small")
    mix = registry.traffic("pretrain-int8-s1024")
    res = {"window": {"tokens": 512}}
    ctx = cbtrace.Context(red, conf, mix, res, "TPU v5 lite")
    got = {n: registry.metric_reader(n).read(ctx) for n in (
        "device_idle_share.train", "step_mfu.train",
        "int8_matmul_roofline.train", "adamw_roofline.train")}
    assert got == {"device_idle_share.train": 99.9667607203098,
                   "step_mfu.train": 2.399364209070837,
                   "int8_matmul_roofline.train": 8.367032064799666,
                   "adamw_roofline.train": None}


def test_result_line_carries_the_component_shares(red, part):
    """The traced run's ``breakdown`` adds the readers' partition to the
    reduction's lists, in their form: at most ten ``[name, seconds]``,
    largest first."""
    bench = registry.benchmark()
    ctx = cbtrace.Context(red, registry.config(bench, "gpt2-small"),
                          registry.traffic("pretrain-int8-s1024"),
                          {"window": {"tokens": 512}}, "TPU v5 lite")
    metrics, bd = run.read_trace(bench, "gpt2s-pretrain-int8", ctx)
    assert "qdq_share.train" in metrics and "step_mfu.train" in metrics
    assert list(bd) == ["device_ops", "idle_gaps", "device_scopes",
                        "idle_before"]
    assert bd["device_ops"] == red.breakdown()["device_ops"]
    want = part.breakdown()
    for key in ("device_scopes", "idle_before"):
        assert bd[key] == [[k, v] for k, v in want[key].items()][:10]
    assert bd["device_scopes"][0] == ["linear/backward",
                                      pytest.approx(693.36e-6, abs=1e-12)]
    # a context whose readers split nothing adds nothing
    serve = cbtrace.Context(red, {}, {"kind": "serve"}, {}, "TPU v5 lite")
    assert list(run.read_trace(bench, "gpt2s-pretrain-int8", serve)[1]) == \
        ["device_ops", "idle_gaps"]


@pytest.mark.parametrize("extra", [["while"], ["router", "transpose"],
                                   ["rematted_computation"]])
def test_jax_names_are_refused_as_components(extra):
    with pytest.raises(registry.BenchError, match="JAX's own"):
        scopes.classify("jit(f)/layers/router/dot", extra)


def test_configuration_names_join_the_components():
    path = "jit(f)/jvp(layers)/while/body/router/dot"
    assert scopes.classify(path) == ("layers", "forward")
    assert scopes.classify(path, ["router"]) == ("router", "forward")
    # the innermost name wins, whichever list it comes from
    assert scopes.classify(path + "/linear/qdq", ["router"]) == \
        ("qdq", "forward")
