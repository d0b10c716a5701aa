"""Tiny versions of the benchmark's cells for the CPU tests: the real
configuration and traffic files with their sizes cut, so that a whole run
(set-up, window, reference, comparison) takes seconds."""
from __future__ import annotations

import argparse

from chipbench import registry


def cell(workload: str, d: int = 64):
    """(bench, cell, conf, mix, lim) of the training cell ``workload`` at a
    tiny size: its configuration cut by its reference's ``tiny`` to width
    ``d``, batch 4 (8 at width 128 and more) of 32 (64) tokens."""
    bench = registry.benchmark()
    cell = registry.workload(bench, workload)
    conf = registry.config(bench, cell["config"])
    conf = registry.reference(conf).tiny(conf, d)
    mix = registry.traffic(cell["traffic"])
    mix.update(batch=4, seq=32, pool=4) if d < 128 else \
        mix.update(batch=8, seq=64, pool=4)
    return bench, cell, conf, mix, registry.limits(workload)


def args(workload: str, seed: int = 2 ** 33 + 17, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=trace)
