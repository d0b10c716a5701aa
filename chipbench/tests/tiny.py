"""Tiny versions of the benchmark's cells for the CPU tests: the real
configuration and traffic files with their sizes cut, so that a whole run
(set-up, window, reference, comparison) takes seconds."""
from __future__ import annotations

import argparse

from chipbench import registry

def _gpt2(bench, d: int):
    conf = registry.config(bench, "gpt2-small")
    conf.update(n_layer=2, n_embd=d, n_head=2, n_positions=64, n_ctx=64,
                vocab_size=4 * d + 44)
    conf["program"]["fields"].update(
        n_layers=2, d_model=d, n_heads=2, n_kv_heads=2, head_dim=d // 2,
        d_ff=4 * d, vocab_size=4 * d + 44, max_seq=64)
    return conf


def cell(workload: str, d: int = 64):
    """(bench, cell, conf, mix, lim) of the gpt2-small training cell
    ``workload`` at a tiny size: 2 layers of width ``d``, batch 4 (8 at
    width 128 and more) of 32 (64) tokens."""
    bench = registry.benchmark()
    cell = registry.workload(bench, workload)
    conf = _gpt2(bench, d)
    mix = registry.traffic(cell["traffic"])
    mix.update(batch=4, seq=32, pool=4) if d < 128 else \
        mix.update(batch=8, seq=64, pool=4)
    return bench, cell, conf, mix, registry.limits(workload)


def args(workload: str, seed: int = 2 ** 33 + 17, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=trace)
