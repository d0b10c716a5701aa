"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run
(set-up, window, reference, comparison) at a tiny size on the CPU, with a
fault planted underneath.  Training: the compiled step returns its state
unchanged, or sees only the first half of each batch (the mean is then
taken over the rest).  One chip has no exchange between chips to leave
out, and no cell serves tokens that could be altered.
"""
import json

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny

CASES = [("gpt2s-pretrain-int8", "unchanged"),
         ("gpt2s-pretrain-int8", "half_batch"),
         ("gpt2s-pretrain-bf16", "unchanged"),
         ("gpt2s-pretrain-bf16", "half_batch")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_train_fault_is_not_correct(workload, fault):
    bench, cell, conf, mix, lim = tiny.cell(workload)
    line = run.execute(tiny.args(workload), bench, cell, conf, mix, lim,
                       jax.devices(), fault=fault)
    res = json.loads(line)
    assert res["correct"] is False, res["checks"]
    assert list(res)[-1] == "checks"
    worst = max(c["value"] / c["limit"] for c in res["checks"].values()
                if c["limit"])
    assert worst > 1.0

