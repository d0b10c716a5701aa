"""Training throughput across quantization fast paths: step time, tokens/s,
custom-vjp residual bytes and optimizer-state bytes per policy -- fp baseline
vs fake-quant reference vs int8-forward vs the full int8 fwd+bwd path.

Rows (CSV, matching benchmarks/run.py):

    train::<path>  us_per_step  tok_s=..;residual_bytes=..;opt_bytes=..;kernel=..

Residual bytes are measured on one mlp_up-sized linear (2048 x 768 x 3072 by
default) via ``jax.eval_shape`` of the dispatched custom-vjp forward rule --
the activation-side memory the backward holds live per linear.  Step time is
wall clock on this host (CPU timings exercise interpret-mode kernels and only
validate dispatch; TPU is the target).

Usage:
    PYTHONPATH=src python -m benchmarks.train_throughput [--steps N]
        [--batch B] [--seq S] [--json PATH] [--smoke]

``--smoke`` runs a tiny pass over every path and asserts the fast-path
invariants (int8 residual compression, finite losses) -- the CI gate that
surfaces kernel regressions as step-time/memory deltas.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import as_policy
from repro.core.qlinear import _qlinear_fwd, _qlinear_int8_fwd
from repro.data import Loader, SyntheticCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.optim import OptConfig
from repro.train import init_train_state, make_train_step
from repro.train.step import train_path_summary

#: name -> policy string (None = fp baseline).  The G8 spec is what arms the
#: int8 backward; w8c+a8t alone runs int8 forward over the fake-quant vjp.
PATHS = (
    ("fp", None),
    ("fake_quant", "*=w8c+a8t+g8t"),
    ("int8_fwd", "*=w8c+a8t@int8_pallas"),
    ("int8_fwd_bwd", "*=w8c+a8t+g8t@int8_pallas"),
)


def _tree_bytes(tree) -> int:
    return sum(l.size * jnp.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "dtype"))


def residual_bytes(policy, m: int = 2048, k: int = 768, n: int = 3072) -> int:
    """Custom-vjp residual footprint of one (m, k) x (k, n) block linear
    under this policy's effective backend (fp keeps the raw operands; fake
    keeps qdq'd fp copies; int8 keeps payloads + scales)."""
    pol = as_policy(policy)
    backend, _ = pol.effective_backend("mlp_up")
    recipe = pol.resolve("mlp_up").recipe
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32)
    if backend == "fp":
        fwd = lambda xx, ww: (xx @ ww, (xx, ww))
    elif backend == "int8_pallas":
        fwd = lambda xx, ww: _qlinear_int8_fwd(xx, ww, None, recipe)
    else:
        fwd = lambda xx, ww: _qlinear_fwd(xx, ww, None, recipe)
    _, res = jax.eval_shape(fwd, x, w)
    return _tree_bytes(res)


def bench_path(name: str, policy, *, steps: int = 3, batch: int = 8,
               seq: int = 128, lr: float = 1e-3) -> dict:
    """Time `steps` jitted train steps of the gpt2-small smoke config under
    one quantization path; report throughput + memory metrics."""
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
    loader = Loader(corpus, cfg, batch_size=batch, seq_len=seq)
    b = next(loader)
    opt = OptConfig(lr=lr, total_steps=max(steps, 10))
    state = init_train_state(model, jax.random.PRNGKey(0), policy, opt)
    step = jax.jit(make_train_step(model, policy, opt))
    state, m = step(state, b, None)                       # compile + warmup
    jax.block_until_ready(m["ce"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, b, None)
    jax.block_until_ready(m["ce"])
    dt = (time.perf_counter() - t0) / steps
    return {
        "path": name,
        "policy": "fp" if policy is None else policy,
        "us_per_step": dt * 1e6,
        "tokens_per_s": batch * seq / dt,
        "final_ce": float(m["ce"]),
        "residual_bytes_linear": residual_bytes(policy),
        "opt_state_bytes": _tree_bytes(state.opt),
        "kernel_path": train_path_summary(policy),
    }


def run(steps: int, batch: int, seq: int) -> list:
    return [bench_path(name, pol, steps=steps, batch=batch, seq=seq)
            for name, pol in PATHS]


def smoke() -> None:
    """CI gate: every path trains, and the quantized paths actually compress
    (the fake-quant reference stores int8 QState residuals too now -- both
    compare against the fp path's raw fp32 operands)."""
    rows = run(steps=2, batch=2, seq=32)
    by = {r["path"]: r for r in rows}
    for r in rows:
        assert np.isfinite(r["final_ce"]), r
    assert by["int8_fwd_bwd"]["residual_bytes_linear"] < \
        by["fp"]["residual_bytes_linear"] / 3.5, by
    assert by["fake_quant"]["residual_bytes_linear"] < \
        by["fp"]["residual_bytes_linear"] / 3.5, by
    assert by["int8_fwd"]["residual_bytes_linear"] == \
        by["int8_fwd_bwd"]["residual_bytes_linear"], by
    assert "bwd=int8" in by["int8_fwd_bwd"]["kernel_path"], by
    print("train-throughput smoke ok:",
          {k: f"{v['residual_bytes_linear']}B" for k, v in by.items()})


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--json", default="",
                    help="also dump the result rows to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny pass + fast-path assertions (CI gate)")
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return

    rows = run(args.steps, args.batch, args.seq)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"train::{r['path']},{r['us_per_step']:.1f},"
              f"tok_s={r['tokens_per_s']:.1f};"
              f"residual_bytes={r['residual_bytes_linear']};"
              f"opt_bytes={r['opt_state_bytes']};"
              f"kernel={r['kernel_path']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
