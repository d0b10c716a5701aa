"""Each cell's control separates from the sound program, at a size a test
run holds.

The control is the cell's computation one precision step down: for the
int8 training cells the program's own 4-bit path (weights, activations,
gradients and both Adam moments), for the bfloat16 training cell the
program's int8 path.  On the chip the same controls set each limit's upper
reading (``python3 -m chipbench.calibrate``); here they run tiny on the CPU
and must read at least three times what the sound program reads on one of
the numbers.  The limits themselves hold at the cell's own size only: at a
width of 64 or 128 the sound program's rounding is larger than at 768.
"""
import json

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny

#: the widths at which the control's separation shows on the CPU: bf16
#: rounding weighs more in a width-64 model than int8's does
TRAIN = [("gpt2s-pretrain-int8", 64), ("gpt2s-pretrain-bf16", 128)]


def _train(workload, d, control: bool):
    bench, cell, conf, mix, lim = tiny.cell(workload, d)
    if control:
        mix = dict(mix, policy=lim["control_policy"])
    return json.loads(run.execute(tiny.args(workload), bench, cell, conf,
                                  mix, lim, jax.devices()))


@pytest.mark.parametrize("workload,d", TRAIN)
def test_train_control_separates(workload, d):
    ctl = _train(workload, d, control=True)
    ok = _train(workload, d, control=False)
    ratio = max(ctl["checks"][k]["value"] / ok["checks"][k]["value"]
                for k in ("loss_gap", "grad_gap", "change_gap"))
    assert ratio >= 3.0, (ctl["checks"], ok["checks"])

