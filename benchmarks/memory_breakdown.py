"""Paper Fig. 2 analog: peak-memory breakdown across GPT-2 sizes x batch.

PyTorch Memory Profiler -> ``compiled.memory_analysis()`` on the train step
(DESIGN.md Section 3).  Run on the host device (1 CPU): absolute bytes are
exact for the program; the paper's observation to verify is that ACTIVATIONS
(temp) dominate as batch grows, so quantizing gradients saves no peak memory
while quantizing weights/activations does.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from repro.configs.base import ArchConfig
from repro.core import paper_recipe
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.models.model_api import train_batch_specs
from repro.configs.base import ShapeConfig
from repro.optim import OptConfig
from repro.train.step import init_train_state, make_train_step

# reduced GPT-2 family (small/medium/large ratios preserved; CPU-compilable)
GPT2_SIZES = {
    "gpt2-small-r": dict(n_layers=4, d_model=256, n_heads=4, d_ff=1024),
    "gpt2-medium-r": dict(n_layers=6, d_model=384, n_heads=6, d_ff=1536),
    "gpt2-large-r": dict(n_layers=9, d_model=512, n_heads=8, d_ff=2048),
}


def _cfg(name: str, sizes: dict) -> ArchConfig:
    return ArchConfig(
        name=name, family="dense", n_kv_heads=sizes["n_heads"],
        vocab_size=50304, act="gelu", mlp_kind="classic", norm="layernorm",
        pos="learned", use_bias=True, tie_embeddings=True, max_seq=1024,
        **sizes)


def measure(cfg: ArchConfig, batch: int, seq: int = 1024) -> dict:
    model = build_model(cfg)
    recipe = paper_recipe()
    opt = OptConfig()
    shape = ShapeConfig("probe", "train", seq, batch)
    state_shapes = jax.eval_shape(
        lambda k: init_train_state(model, k, recipe, opt),
        jax.random.PRNGKey(0))
    specs = train_batch_specs(cfg, shape)
    step = make_train_step(model, recipe, opt)
    lowered = jax.jit(lambda s, b: step(s, b, None)).lower(state_shapes, specs)
    ma = lowered.compile().memory_analysis()
    params = sum(x.size * 4 for x in
                 jax.tree_util.tree_leaves(state_shapes.params))
    return {
        "batch": batch,
        "params_plus_opt_bytes": int(ma.argument_size_in_bytes),
        "activations_and_workspace_bytes": int(ma.temp_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "raw_param_bytes_fp32": int(params),
    }


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="2,4,8")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "experiments", "memory_breakdown.json"))
    args = ap.parse_args()
    out = {}
    for name, sizes in GPT2_SIZES.items():
        cfg = _cfg(name, sizes)
        rows = []
        for b in [int(x) for x in args.batches.split(",")]:
            r = measure(cfg, b, args.seq)
            rows.append(r)
            act_frac = r["activations_and_workspace_bytes"] / (
                r["activations_and_workspace_bytes"]
                + r["params_plus_opt_bytes"])
            print(f"{name:16s} batch={b:3d} act+ws="
                  f"{r['activations_and_workspace_bytes']/1e9:6.2f}GB "
                  f"state={r['params_plus_opt_bytes']/1e9:6.2f}GB "
                  f"act_frac={act_frac:.2f}", flush=True)
        out[name] = rows
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
