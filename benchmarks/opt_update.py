"""Optimizer-update fast paths: one AdamW step over gpt2-small-smoke params
under fp / fake-quant / int8-loop / int8-fused moment storage -- per-step
``opt_ms``, optimizer-state bytes, and an analytic HBM-traffic counter that
prices what each path streams (the fused kernel's whole point is bandwidth:
one read + one write per buffer instead of ~6 round trips over moment-sized
fp32 materializations).

Rows (CSV, matching benchmarks/run.py):

    opt::<path>  us_per_step  opt_ms=..;hbm_bytes=..;opt_bytes=..;path=..

Analytic HBM model (bytes per parameter element, documented not measured --
CPU wall times exercise interpret-mode kernels and only validate dispatch;
TPU is the target):

  * every path pays the global-norm pre-pass read of g (4B);
  * ``fp``    : update reads g/p/m1/m2 and writes p/m1/m2 fp32 (one fused
                elementwise pass): 4+4+4+4 + 4+4+4 = 28B (+4 pre-pass);
  * ``fake``  : fp traffic + one extra fp32 round trip per moment for the
                blockwise qdq (the reshape/pad boundary materializes):
                28 + 2*8 = 44B (+4);
  * ``int8 loop``  : per moment, decode (read int8 1B, write fp32 4B), update
                (read 4B, write 4B), encode (read 4B, write int8 1B) = 18B;
                plus g read 4B and p read+write 8B: 48B (+4) -- the ~6
                moment-sized round trips the motivation names;
  * ``int8 fused`` : one read + one write per buffer: g 4 + p 4+4 + payloads
                1+1 in, 1+1 out = 16B (+4), sidecars 32/block_size.

Usage:
    PYTHONPATH=src python -m benchmarks.opt_update [--steps N] [--json PATH]
        [--smoke]

``--smoke`` asserts the fast-path invariants (fused HBM bytes < 1/2 the loop
path, fused-vs-loop parity, int8 state compression) -- the CI gate.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import qadam
from repro.core.qconfig import parse_recipe
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.optim import (OptConfig, adamw_update, init_adam_state,
                         opt_path_desc)

#: Moment recipe: both codecs blockwise (the fused-kernel contract); m2 is
#: the beyond-paper asymmetric sqrt-domain codec that fixes paper Fig. 12.
M_RECIPE = "m1:8c-b128,m2:8c-asym-b128-sqrt"

#: name -> (recipe string or None, state_storage, REPRO_FUSED_ADAM value)
PATHS = (
    ("fp", None, "fake", "0"),
    ("fake", M_RECIPE, "fake", "0"),
    ("int8_loop", M_RECIPE, "int", "0"),
    ("int8_fused", M_RECIPE, "int", "1"),
)


def _tree_bytes(tree) -> int:
    return sum(qadam.state_nbytes(l) for l in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, qadam.QState)))


def analytic_hbm_bytes(path: str, params, recipe) -> int:
    """Bytes streamed to/from HBM for one optimizer step under the model in
    the module docstring.  Non-quantizable leaves (1-D / tiny) always take
    the fp path."""
    per_elem = {"fp": 32.0, "fake": 48.0, "int8_loop": 52.0,
                "int8_fused": 20.0}
    total = 0.0
    bs = recipe.adam_m1.block_size if recipe and recipe.adam_m1 else 0
    for p in jax.tree_util.tree_leaves(params):
        if path != "fp" and qadam.quantizable(p):
            total += per_elem[path] * p.size
            if path == "int8_fused" and bs:
                total += 32.0 * p.size / bs          # scale/zero sidecars
        else:
            total += per_elem["fp"] * p.size
    return int(total)


def bench_path(name: str, recipe_str, storage: str, fused: str, *,
               steps: int = 3, lr: float = 1e-3) -> dict:
    """Time `steps` jitted AdamW updates over the gpt2-small smoke params."""
    recipe = parse_recipe(recipe_str) if recipe_str else None
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    grads = jax.tree_util.tree_map(
        lambda p: 0.01 * jax.random.normal(
            jax.random.PRNGKey(1), p.shape, jnp.float32), params)
    opt_cfg = OptConfig(lr=lr, total_steps=max(steps, 10),
                        state_storage=storage)
    prev = os.environ.get("REPRO_FUSED_ADAM")
    os.environ["REPRO_FUSED_ADAM"] = fused
    try:
        state = init_adam_state(params, recipe, opt_cfg)
        step = jax.jit(lambda p, g, s: adamw_update(p, g, s, opt_cfg, recipe))
        params2, state, stats = step(params, grads, state)   # compile+warmup
        jax.block_until_ready(stats["update_norm"])
        t0 = time.perf_counter()
        for _ in range(steps):
            params2, state, stats = step(params2, grads, state)
        jax.block_until_ready(stats["update_norm"])
        dt = (time.perf_counter() - t0) / steps
        path_desc = opt_path_desc(recipe, opt_cfg)
    finally:
        if prev is None:
            os.environ.pop("REPRO_FUSED_ADAM", None)
        else:
            os.environ["REPRO_FUSED_ADAM"] = prev
    moments_bytes = _tree_bytes(state.m1) + _tree_bytes(state.m2)
    return {
        "path": name,
        "recipe": recipe_str or "fp",
        "storage": storage,
        "opt_ms": dt * 1e3,
        "us_per_step": dt * 1e6,
        "update_norm": float(stats["update_norm"]),
        "opt_state_bytes": moments_bytes,
        "hbm_bytes_per_step": analytic_hbm_bytes(name, params, recipe),
        "kernel_path": path_desc,
        "final_params": params2,                    # for parity checks
    }


def run(steps: int) -> list:
    rows = [bench_path(name, r, st, f, steps=steps)
            for name, r, st, f in PATHS]
    for r in rows:
        r.pop("final_params")
    return rows


def smoke() -> None:
    """CI gate: fused path halves (at least) the analytic HBM traffic of the
    loop path, tracks it numerically, and int8 states actually compress."""
    rows = {name: bench_path(name, r, st, f, steps=1)
            for name, r, st, f in PATHS}
    loop, fused = rows["int8_loop"], rows["int8_fused"]
    assert fused["hbm_bytes_per_step"] < loop["hbm_bytes_per_step"] / 2, \
        (fused["hbm_bytes_per_step"], loop["hbm_bytes_per_step"])
    assert fused["opt_state_bytes"] == loop["opt_state_bytes"], rows
    assert fused["opt_state_bytes"] < rows["fake"]["opt_state_bytes"] / 3.5, \
        (fused["opt_state_bytes"], rows["fake"]["opt_state_bytes"])
    assert "int8-fused" in fused["kernel_path"], fused["kernel_path"]
    assert "int8-loop" in loop["kernel_path"], loop["kernel_path"]
    for r in rows.values():
        assert np.isfinite(r["update_norm"]) and r["update_norm"] > 0, r
    # fused parity vs the reference loop after 2 steps (<= 1 codec ulp per
    # moment -> param drift bounded well below one lr)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        loop["final_params"], fused["final_params"])
    worst = max(jax.tree_util.tree_leaves(diffs))
    assert worst < 1e-3, worst
    print("opt-update smoke ok:",
          {k: f"{v['hbm_bytes_per_step'] / 1e6:.1f}MB" for k, v in
           rows.items()}, f"parity={worst:.2e}")


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--json", default="",
                    help="also dump the result rows to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="fast-path assertions (CI gate)")
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return

    rows = run(args.steps)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"opt::{r['path']},{r['us_per_step']:.1f},"
              f"opt_ms={r['opt_ms']:.2f};"
              f"hbm_bytes={r['hbm_bytes_per_step']};"
              f"opt_bytes={r['opt_state_bytes']};"
              f"path={r['kernel_path']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
