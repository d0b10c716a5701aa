"""The training window's own accounting, the verdict on the numbers
compared, and the program's switches set only while it is traced."""
import math
import os

import jax.numpy as jnp
import pytest

from chipbench import train
from chipbench.tests import tiny


@pytest.mark.parametrize("loss,failed", [(1.5, False), (math.nan, True)])
def test_window_reads_losses_back_after_it_closes(loss, failed):
    _, _, conf, mix, lim = tiny.cell("gpt2s-pretrain-bf16")
    cell = train.TrainCell(conf, mix, lim)
    cell.state = jnp.zeros(())
    calls = []

    def call():
        calls.append(1)
        return {"loss": jnp.asarray(loss, jnp.float32)}
    cell._call = call
    win = cell.window(0.05)
    assert win["steps"] == len(calls) >= 1
    assert win["failed"] == (win["steps"] if failed else 0)
    assert win["tokens"] == win["steps"] * mix["batch"] * mix["seq"]
    assert win["seconds"] >= 0.05


def test_verdict_holds_each_number_to_its_limit():
    lim = {"loss_gap": 0.01, "grad_gap": 0.1}
    ok, checks = train.verdict({"loss_gap": 0.005, "grad_gap": 0.1}, lim)
    assert ok and list(checks) == ["loss_gap", "grad_gap",
                                   "window_nonfinite_losses"]
    assert checks["grad_gap"] == {"value": 0.1, "limit": 0.1}
    assert not train.verdict({"loss_gap": 0.02, "grad_gap": 0.0}, lim)[0]
    assert not train.verdict({"loss_gap": math.nan, "grad_gap": 0.0},
                             lim)[0]
    assert not train.verdict({"loss_gap": 0.0, "grad_gap": 0.0}, lim, 1)[0]


def test_program_switches_are_restored_after_set_up(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_ADAM", raising=False)
    before = dict(os.environ)
    _, _, conf, mix, lim = tiny.cell("gpt2s-pretrain-bf16")
    mix = dict(mix, program_env={"REPRO_FUSED_ADAM": "0"})
    seen = []
    real = train.program

    def spy(c, m):
        seen.append(os.environ.get("REPRO_FUSED_ADAM"))
        return real(c, m)
    monkeypatch.setattr(train, "program", spy)
    cell = train.TrainCell(conf, mix, lim)
    cell.setup()
    assert seen == ["0"]
    assert dict(os.environ) == before
