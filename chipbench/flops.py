"""Operations and bytes, computed from shapes.

Model operations per token come from each configuration's own plain
reference (``reference/<name>.py``, its ``train_ops_per_token(conf,
seq)``), computed from the keys of the configuration file, never from the
program's configuration.  Every reference counts by one convention:

* 6 operations per weight of every matrix a token passes through
  (forward, and two in the backward);
* of routed experts, only the ``top_k`` a token is sent to; shared
  experts all count;
* attention scores and their use: 6 * L * s * h * (d_qk + d_v) for
  context s, heads h and the widths of a query/key head and a value
  head, which is 12 * L * s * h * hd where the two are equal;
* nothing counted twice for recomputation; the embedding lookup is no
  product and is not counted; a tied head is counted once, as the head.

The count comes in two parts: ``linears``, exactly the block matrices the
program sends through ``QuantPolicy.linear`` (the ones a policy may run in
int8), and ``other``: the head, the attention products and any matrix the
policy keeps in full precision, such as a router.

A kernel's operations are counted by ``kernel_ops/<kernel>.py``, found by
the kernel function's name (``registry.kernel_ops``).
"""
from __future__ import annotations

from typing import Dict

from chipbench import registry


def train_ops_per_token(conf: Dict, seq: int) -> Dict[str, float]:
    """Training operations per token of configuration ``conf`` at context
    ``seq``: ``{"linears": ..., "other": ...}`` (module docstring)."""
    return registry.reference(conf).train_ops_per_token(conf, seq)


def train_least_time_per_token(conf: Dict, seq: int, device_kind: str,
                               linears_precision: str) -> float:
    """Least seconds per trained token: the blocks' matrices at the peak of
    ``linears_precision``, everything else at the bfloat16 peak."""
    from chipbench.peaks import peaks
    pk = peaks(device_kind)
    ops = train_ops_per_token(conf, seq)
    rate = {"bf16": pk["bf16_flops"], "int8": pk["int8_ops"]}
    return ops["linears"] / rate[linears_precision] \
        + ops["other"] / pk["bf16_flops"]


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def matmul_ops(call, n_from: int) -> float:
    """2 * M * K * N of a kernel whose first operand is (M, K) or (M, N)
    and whose second holds the remaining size at axis ``n_from``."""
    return 2.0 * _prod(call.operands[0][1]) * call.operands[1][1][n_from]


def kernel_least_time(call, device_kind: str) -> float:
    """Least seconds for one kernel call: its operations at its precision's
    peak, or its operands and results moved once at the HBM peak, whichever
    is larger.  ``call`` is a ``chipbench.trace.Call``."""
    from chipbench.peaks import least_time
    k = registry.kernel_ops(call.kernel)
    return least_time(k.ops(call), call.nbytes, device_kind, k.PRECISION)
