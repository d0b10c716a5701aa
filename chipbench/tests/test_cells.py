"""The int8 training cells at their tiny cuts (each configuration's
reference's ``tiny``) run end to end on the CPU through ``run.execute``,
program against reference, and come out correct under the cells' own
limits.  The bfloat16 cell's limits hold at its own width only (see
``test_control``), so it is not among them."""
import json

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny


@pytest.mark.parametrize("workload", ["yi6b-pretrain-int8",
                                      "gpt2s-pretrain-int8"])
def test_tiny_cell_is_correct(workload):
    bench, cell, conf, mix, lim = tiny.cell(workload)
    res = json.loads(run.execute(tiny.args(workload), bench, cell, conf,
                                 mix, lim, jax.devices()))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
