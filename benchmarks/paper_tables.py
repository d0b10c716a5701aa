"""Paper-table reproduction at reduced scale (DESIGN.md Section 6/7).

One pre-training run per quantization configuration of the paper's controlled
study (Tables 2-5, Figs 9-13), on the mini GPT-2 + deterministic synthetic
corpus.  Absolute OpenWebText perplexities are not reproducible in a CPU
container; the validation targets are the paper's QUALITATIVE orderings
(which configs track the baseline / degrade / diverge).

  PYTHONPATH=src python -m benchmarks.paper_tables --steps 300
  PYTHONPATH=src python -m benchmarks.paper_tables --check   # assert claims

Results are cached per-config in experiments/paper/<name>.json.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.qconfig import Granularity as G
from repro.core.qconfig import QuantRecipe, QuantSpec
from repro.data import Loader, SyntheticCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.optim import OptConfig
from repro.train import init_train_state, make_eval_step, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "paper")

W = lambda b, g: QuantRecipe(weights=QuantSpec(b, g))
A = lambda b, g, sym=True: QuantRecipe(acts=QuantSpec(b, g, symmetric=sym))
GR = lambda b, g: QuantRecipe(grads=QuantSpec(b, g))
M1 = lambda b, g: QuantRecipe(adam_m1=QuantSpec(b, g))

CONFIGS = {
    # paper Table 2 / Fig 4 -- weights
    "baseline": QuantRecipe(),
    "w4_per_tensor": W(4, G.PER_TENSOR),
    "w4_per_channel": W(4, G.PER_CHANNEL),
    "w8_per_tensor": W(8, G.PER_TENSOR),
    "w8_per_channel": W(8, G.PER_CHANNEL),
    # Table 3 / Figs 6-8 -- activations
    "a4_per_tensor": A(4, G.PER_TENSOR),
    "a4_per_token": A(4, G.PER_TOKEN),
    "a4_per_token_asym": A(4, G.PER_TOKEN, sym=False),
    "a4_per_channel": A(4, G.PER_CHANNEL),
    "a8_per_tensor": A(8, G.PER_TENSOR),
    "a8_per_token": A(8, G.PER_TOKEN),
    # Table 4 / Figs 9-10 -- gradients (dW path only, Fig. 1)
    "g4_per_tensor": GR(4, G.PER_TENSOR),
    "g4_per_token": GR(4, G.PER_TOKEN),
    "g8_per_tensor": GR(8, G.PER_TENSOR),
    "g8_per_token": GR(8, G.PER_TOKEN),
    # Fig 10 (top) -- the input-gradient-path instability ablation
    "gdx8_per_token": QuantRecipe(grads_dx=QuantSpec(8, G.PER_TOKEN)),
    "gdx4_per_token": QuantRecipe(grads_dx=QuantSpec(4, G.PER_TOKEN)),
    # Table 5 / Fig 11 -- Adam m1
    "m1_4_per_tensor": M1(4, G.PER_TENSOR),
    "m1_4_per_channel": M1(4, G.PER_CHANNEL),
    "m1_8_per_tensor": M1(8, G.PER_TENSOR),
    "m1_8_per_channel": M1(8, G.PER_CHANNEL),
    # Fig 12 -- Adam m2: the paper's linear scheme vs the beyond-paper codec
    "m2_8_per_channel": QuantRecipe(adam_m2=QuantSpec(8, G.PER_CHANNEL)),
    "m2_8_blockwise_sqrt": QuantRecipe(adam_m2=QuantSpec(
        8, G.PER_CHANNEL, symmetric=False, block_size=128,
        sqrt_domain=True)),
    # Fig 13 / Section 4.5 -- combined
    "w8a8": QuantRecipe(weights=QuantSpec(8, G.PER_CHANNEL),
                        acts=QuantSpec(8, G.PER_TOKEN)),
    "w8a8g8": QuantRecipe(weights=QuantSpec(8, G.PER_CHANNEL),
                          acts=QuantSpec(8, G.PER_TOKEN),
                          grads=QuantSpec(8, G.PER_TOKEN)),
}


def run_config(name: str, recipe: QuantRecipe, steps: int, batch: int,
               seq: int, lr: float, eval_every: int, out_dir: str,
               force: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            cached = json.load(f)
        if cached.get("steps") >= steps:
            return cached

    cfg = get_smoke_config("gpt2-small")          # the paper's model, reduced
    model = build_model(cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
    opt = OptConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                    total_steps=steps, state_storage="fake")
    state = init_train_state(model, jax.random.PRNGKey(0), recipe, opt)
    step = jax.jit(make_train_step(model, recipe, opt))
    eval_step = jax.jit(make_eval_step(model, recipe))
    loader = Loader(corpus, cfg, batch_size=batch, seq_len=seq)
    valid = Loader(corpus, cfg, batch_size=batch, seq_len=seq, split="valid")

    t0 = time.time()
    train_curve, valid_curve = [], []
    diverged = False
    for i in range(steps):
        state, m = step(state, next(loader), jax.random.fold_in(
            jax.random.PRNGKey(0), i))
        ce = float(m["ce"])
        train_curve.append(ce)
        if not math.isfinite(ce) or ce > 30.0:
            diverged = True
            break
        if (i + 1) % eval_every == 0 or i == 0:
            vl = float(np.mean([
                float(eval_step(state.params, valid.peek(step=j))["ce"])
                for j in range(2)]))
            valid_curve.append({"step": i + 1, "ce": vl})

    final_valid = (valid_curve[-1]["ce"] if valid_curve else float("nan"))
    result = {
        "name": name, "recipe": recipe.describe(), "steps": len(train_curve),
        "diverged": diverged,
        "final_train_ce": train_curve[-1] if train_curve else None,
        "final_valid_ce": final_valid,
        "max_train_ce_after_warmup": (max(train_curve[10:])
                                      if len(train_curve) > 10 else None),
        "train_curve_every10": train_curve[::10],
        "valid_curve": valid_curve,
        "wall_s": round(time.time() - t0, 1),
    }
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def load_all(out_dir: str) -> dict:
    out = {}
    for name in CONFIGS:
        path = os.path.join(out_dir, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                out[name] = json.load(f)
    return out


def check_claims(results: dict) -> list:
    """The paper's qualitative orderings; returns list of (claim, ok, detail)."""
    def ce(name):
        r = results.get(name)
        if r is None:
            return float("nan")
        if r["diverged"]:
            return float("inf")
        return r["final_valid_ce"]

    base = ce("baseline")
    checks = [
        ("W8 per-channel tracks baseline (Fig 4)",
         ce("w8_per_channel") < base + 0.1,
         f"{ce('w8_per_channel'):.3f} vs {base:.3f}"),
        ("W4 per-channel beats W4 per-tensor (Table 2)",
         ce("w4_per_channel") <= ce("w4_per_tensor") + 0.02,
         f"{ce('w4_per_channel'):.3f} vs {ce('w4_per_tensor'):.3f}"),
        ("W4 worse than W8 (Fig 4)",
         ce("w4_per_tensor") > ce("w8_per_tensor") - 0.02,
         f"{ce('w4_per_tensor'):.3f} vs {ce('w8_per_tensor'):.3f}"),
        ("A8 per-token tracks baseline (Fig 7)",
         ce("a8_per_token") < base + 0.1,
         f"{ce('a8_per_token'):.3f} vs {base:.3f}"),
        ("A4 worse than A8 (Table 3)",
         min(ce("a4_per_token"), ce("a4_per_tensor"))
         > ce("a8_per_token") - 0.02,
         f"a4 {ce('a4_per_token'):.3f}/{ce('a4_per_tensor'):.3f} "
         f"vs a8 {ce('a8_per_token'):.3f}"),
        ("A4 asym improves on A4 sym per-token (Fig 7)",
         ce("a4_per_token_asym") <= ce("a4_per_token") + 0.05,
         f"{ce('a4_per_token_asym'):.3f} vs {ce('a4_per_token'):.3f}"),
        ("G8 per-token converges but trails baseline (Fig 9)",
         math.isfinite(ce("g8_per_token"))
         and ce("g8_per_token") > base - 0.05,
         f"{ce('g8_per_token'):.3f} vs {base:.3f}"),
        ("G4 per-tensor much worse / diverges (Table 4)",
         ce("g4_per_tensor") > base + 0.2 or results.get(
             "g4_per_tensor", {}).get("diverged", False),
         f"{ce('g4_per_tensor'):.3f}"),
        ("Quantizing the dx path is the most unstable (Fig 10)",
         ce("gdx4_per_token") >= ce("g4_per_token") - 0.05,
         f"gdx4 {ce('gdx4_per_token'):.3f} vs g4 {ce('g4_per_token'):.3f}"),
        ("M1 8-bit per-channel tracks baseline (Fig 11)",
         ce("m1_8_per_channel") < base + 0.1,
         f"{ce('m1_8_per_channel'):.3f} vs {base:.3f}"),
        ("M1 4-bit per-channel feasible; per-tensor worst (Table 5)",
         ce("m1_4_per_channel") <= ce("m1_4_per_tensor") + 0.02,
         f"{ce('m1_4_per_channel'):.3f} vs {ce('m1_4_per_tensor'):.3f}"),
        ("M2 linear 8-bit hurts/diverges (Fig 12)",
         ce("m2_8_per_channel") > base + 0.15 or results.get(
             "m2_8_per_channel", {}).get("diverged", False),
         f"{ce('m2_8_per_channel'):.3f} vs {base:.3f}"),
        ("Beyond-paper m2 codec fixes it (Section 2 item 1)",
         ce("m2_8_blockwise_sqrt") < base + 0.1,
         f"{ce('m2_8_blockwise_sqrt'):.3f} vs {base:.3f}"),
        ("W8A8 recipe tracks baseline (Fig 13)",
         ce("w8a8") < base + 0.1, f"{ce('w8a8'):.3f} vs {base:.3f}"),
        ("Adding G8 degrades the combined recipe (Fig 13)",
         ce("w8a8g8") > ce("w8a8") - 0.05,
         f"{ce('w8a8g8'):.3f} vs {ce('w8a8'):.3f}"),
    ]
    return checks


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--configs", default=None,
                    help="comma-separated subset")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.check:
        results = load_all(args.out)
        ok_all = True
        for claim, ok, detail in check_claims(results):
            print(f"{'PASS' if ok else 'FAIL'}  {claim}  [{detail}]")
            ok_all &= ok
        raise SystemExit(0 if ok_all else 1)

    names = (args.configs.split(",") if args.configs else list(CONFIGS))
    for name in names:
        r = run_config(name, CONFIGS[name], args.steps, args.batch, args.seq,
                       args.lr, args.eval_every, args.out, force=args.force)
        print(f"{name:22s} steps={r['steps']:4d} "
              f"final_valid={r['final_valid_ce']:.4f} "
              f"diverged={r['diverged']} ({r['wall_s']}s)", flush=True)


if __name__ == "__main__":
    main()
