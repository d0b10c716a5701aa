"""The model a cell runs: the program's configuration built from the
configuration file, and weights made from the seed by the benchmark.

Weights come from the configuration's plain reference (``init``), in one
jitted call on the device, and are handed to the program as they are: the
program and the reference compute from the same numbers, and neither
derives them from the other.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax

from chipbench import registry


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one stream of the run (weights, tokens, ...) from a
    seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise registry.BenchError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def program_config(conf: Dict):
    """The program's ``ArchConfig`` for this configuration: its registered
    architecture with the fields the file states set as stated."""
    from repro.configs import get_config

    prog = conf["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["fields"])
    for k, v in prog["fields"].items():
        if getattr(cfg, k) != v:
            raise registry.BenchError(
                f"program config {prog['arch']}: {k}={getattr(cfg, k)!r}, "
                f"the configuration states {v!r}")
    return cfg


def _shape_tree(tree):
    return jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)


def check_layout(ref, conf: Dict, model) -> None:
    """The reference's parameter layout must be the program's: same keys,
    same shapes.  A program that renames or reshapes a parameter stops here
    rather than running on misplaced weights."""
    ours = jax.eval_shape(lambda k: ref.init(k, conf), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if _shape_tree(ours) != _shape_tree(theirs):
        raise registry.BenchError(
            "the program's parameter layout differs from the reference's:\n"
            f"  program:   {_shape_tree(theirs)}\n"
            f"  reference: {_shape_tree(ours)}")
