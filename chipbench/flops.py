"""Operations and bytes, computed from shapes.

Model operations per token follow the usual convention: 6 per parameter
of every matrix product (forward, and two in the backward), plus
12 * layers * context * width for the attention scores and their use, with
nothing counted twice for recomputation.  The embedding lookup is no
product and is not counted; a tied head is counted once, as the head.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def dims(cfg) -> Dict[str, int]:
    """Sizes of the program's ``ArchConfig`` that the counts need."""
    return dict(L=cfg.n_layers, d=cfg.d_model, h=cfg.n_heads,
                kv=cfg.n_kv_heads, hd=cfg.head_dim, ff=cfg.d_ff,
                V=cfg.vocab_size, gated=cfg.mlp_kind == "gated")


def block_linears(cfg) -> List[Tuple[str, int, int]]:
    """(name, in, out) of one block's weight matrices."""
    m = dims(cfg)
    qd, kd = m["h"] * m["hd"], m["kv"] * m["hd"]
    out = [("wq", m["d"], qd), ("wk", m["d"], kd), ("wv", m["d"], kd),
           ("wo", qd, m["d"])]
    if m["gated"]:
        out += [("w_gate", m["d"], m["ff"]), ("w_up", m["d"], m["ff"]),
                ("w_down", m["ff"], m["d"])]
    else:
        out += [("w_fc1", m["d"], m["ff"]), ("w_fc2", m["ff"], m["d"])]
    return out


def block_params(cfg) -> int:
    return cfg.n_layers * sum(i * o for _, i, o in block_linears(cfg))


def train_ops_per_token(cfg, seq: int) -> Dict[str, float]:
    """Training operations per token, split by the precision a policy may
    give them: ``linears`` (the blocks' matrices) and ``other`` (the head
    and the attention products)."""
    m = dims(cfg)
    return {"linears": 6.0 * block_params(cfg),
            "other": 6.0 * m["d"] * m["V"]
            + 12.0 * m["L"] * seq * m["h"] * m["hd"]}


def train_least_time_per_token(cfg, seq: int, device_kind: str,
                               linears_precision: str) -> float:
    """Least seconds per trained token: the blocks' matrices at the peak of
    ``linears_precision``, everything else at the bfloat16 peak."""
    from chipbench.peaks import peaks
    pk = peaks(device_kind)
    ops = train_ops_per_token(cfg, seq)
    rate = {"bf16": pk["bf16_flops"], "int8": pk["int8_ops"]}
    return ops["linears"] / rate[linears_precision] \
        + ops["other"] / pk["bf16_flops"]


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _matmul_ops(call, n_from: int) -> float:
    """2 * M * K * N of a kernel whose first operand is (M, K) or (M, N)
    and whose second holds the remaining size at axis ``n_from``."""
    return 2.0 * _prod(call.operands[0][1]) * call.operands[1][1][n_from]


#: operations of one call of each Pallas kernel, from its instruction's
#: shapes: the int8 forward (M, K) @ (K, N), the input gradient
#: (M, N) @ (K, N)^T and the weight gradient (M, K)^T @ (M, N); the fused
#: AdamW's few operations per element are negligible beside its bytes
KERNEL_OPS = {
    "_int8_matmul_kernel": lambda c: _matmul_ops(c, 1),
    "_int8_matmul_nt_kernel": lambda c: _matmul_ops(c, 0),
    "_int8_matmul_tn_kernel": lambda c: _matmul_ops(c, 1),
    "_adamw_kernel": lambda c: 0.0,
}
#: the precision whose peak bounds each kernel's operations
KERNEL_PRECISION = {"_int8_matmul_kernel": "int8",
                    "_int8_matmul_nt_kernel": "int8",
                    "_int8_matmul_tn_kernel": "int8",
                    "_adamw_kernel": "bf16"}


def kernel_least_time(call, device_kind: str) -> float:
    """Least seconds for one kernel call: its operations at its precision's
    peak, or its operands and results moved once at the HBM peak, whichever
    is larger.  ``call`` is a ``chipbench.trace.Call``."""
    from chipbench.peaks import least_time
    return least_time(KERNEL_OPS[call.kernel](call), call.nbytes,
                      device_kind, KERNEL_PRECISION[call.kernel])
