"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * op micro-benchmarks (fake-quant granularities, quantized linear fwd/bwd,
    train steps) -- us_per_call on this host;
  * paper-table derived metrics (final valid CE delta vs baseline per
    quantization config) -- from experiments/paper/*.json if present, else
    quick 60-step runs are executed on the spot;
  * Fig 2/3 analogs (activation-memory fraction, linear-layer FLOP share);
  * roofline MFUs per dry-run cell (experiments/dryrun/*.json when present).

Full-fidelity runs:  python -m benchmarks.paper_tables --steps 300
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import LinearCtx, QuantPolicy, paper_recipe, parse_policy
from repro.core.qconfig import Granularity, QuantRecipe, QuantSpec
from repro.core.quantizer import fake_quant_nograd
from repro.core.qlinear import quantized_linear
from repro.data import Loader, SyntheticCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.optim import OptConfig
from repro.train import init_train_state, make_train_step

EXP = os.path.join(os.path.dirname(__file__), "..", "experiments")


def _time(fn, *args, warmup=2, iters=10) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def row(name: str, us: float, derived="") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------

def bench_quantizer_ops() -> None:
    """Section 3.1 op costs."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 1024), jnp.float32)
    for gran in Granularity:
        spec = QuantSpec(8, gran)
        f = jax.jit(lambda v, s=spec: fake_quant_nograd(v, s))
        row(f"qdq_8bit_{gran.value}", _time(f, x), "fake-quant 4M elems")
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024))
    r = paper_recipe()
    fwd = jax.jit(lambda a, b: quantized_linear(a, b, r))
    row("qlinear_fwd_w8a8", _time(fwd, x, w), "4096x1024x1024")
    bwd = jax.jit(jax.grad(lambda a, b: jnp.sum(
        quantized_linear(a, b, QuantRecipe(
            weights=QuantSpec(8, Granularity.PER_CHANNEL),
            acts=QuantSpec(8, Granularity.PER_TOKEN),
            grads=QuantSpec(8, Granularity.PER_TOKEN))) ** 2), argnums=1))
    row("qlinear_bwd_w8a8g8", _time(bwd, x, w), "dW path quantized")
    plain = jax.jit(lambda a, b: a @ b)
    row("linear_fp_baseline", _time(plain, x, w), "matmul only")


def bench_kernels() -> None:
    """Pallas kernels (interpret mode on CPU -- TPU is the target; timings
    here validate dispatch overhead only)."""
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 512))
    spec = QuantSpec(8, Granularity.PER_TOKEN)
    f = jax.jit(lambda v: ops.fused_fake_quant(v, spec))
    row("pallas_qdq_row_interp", _time(f, x, iters=3),
        "interpret-mode; TPU target")
    g = jax.jit(lambda a, b: ops.int8_quantized_matmul(a, b))
    row("pallas_int8_matmul_interp", _time(g, x, w, iters=3),
        "interpret-mode; TPU target")


def bench_policy_backends() -> None:
    """QuantPolicy dispatch: fake-quant reference vs real-int8 Pallas on one
    W8A8 linear (interpret mode on CPU -- TPU is the target), plus the
    depth-switch overhead of a layer-banded policy."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 1024))
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024))
    for name, pol in [
            ("policy_fake_quant", QuantPolicy(default=paper_recipe())),
            ("policy_int8_pallas", QuantPolicy(default=paper_recipe(),
                                               backend="int8_pallas"))]:
        f = jax.jit(lambda a, b, p=pol: p.linear(LinearCtx("mlp_up"), a, b))
        row(name, _time(f, x, w, iters=3), "2048x1024x1024 W8A8")
    banded = parse_policy("block[0:2].*=fp,*=w8c+a8t")
    f = jax.jit(lambda a, b, li: banded.linear(
        LinearCtx("mlp_up", layer=li, n_layers=12), a, b))
    row("policy_depth_switch", _time(f, x, w, jnp.int32(6)),
        "lax.switch over 2 depth classes")


def bench_train_steps() -> None:
    """Train-step wall time: fp baseline, global paper recipe, and a
    per-layer policy with fp end-blocks (mini GPT-2)."""
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
    loader = Loader(corpus, cfg, batch_size=8, seq_len=128)
    batch = next(loader)
    for name, recipe in [
            ("fp", None), ("paper_w8a8", paper_recipe()),
            ("policy_banded", parse_policy(
                "block[0:1].*=fp,block[-1:].*=fp,*=w8c+a8t"))]:
        opt = OptConfig(lr=1e-3, total_steps=100)
        state = init_train_state(model, jax.random.PRNGKey(0), recipe, opt)
        step = jax.jit(make_train_step(model, recipe, opt))
        f = lambda s, b: step(s, b, None)[0].opt.step
        row(f"train_step_{name}", _time(f, state, batch, warmup=1, iters=3),
            "mini gpt2 b8 s128")


def bench_train_throughput() -> None:
    """Training fast paths: fp vs fake-quant vs int8-fwd vs int8-fwd+bwd
    (step time, tokens/s, residual + optimizer-state bytes)."""
    from benchmarks.train_throughput import PATHS, bench_path
    for name, pol in PATHS:
        r = bench_path(name, pol, steps=2, batch=4, seq=64)
        row(f"train::{name}", r["us_per_step"],
            f"tok_s={r['tokens_per_s']:.1f};"
            f"residual_bytes={r['residual_bytes_linear']};"
            f"opt_bytes={r['opt_state_bytes']};"
            f"kernel={r['kernel_path']}")


def table_paper_results() -> None:
    """Tables 2-5 / Figs 9-13 derived metrics (valid-CE delta vs baseline)."""
    from benchmarks.paper_tables import CONFIGS, load_all, run_config
    out_dir = os.path.join(EXP, "paper")
    results = load_all(out_dir)
    need = [n for n in CONFIGS if n not in results]
    quick = [n for n in need if n in (
        "baseline", "w8_per_channel", "w4_per_tensor", "a8_per_token",
        "g8_per_token", "m2_8_per_channel", "w8a8")]
    for n in quick:
        results[n] = run_config(n, CONFIGS[n], steps=60, batch=8, seq=128,
                                lr=3e-3, eval_every=30, out_dir=out_dir)
    base = results.get("baseline", {}).get("final_valid_ce", float("nan"))
    for name, r in sorted(results.items()):
        ce = float("inf") if r["diverged"] else r["final_valid_ce"]
        delta = ce - base if math.isfinite(ce) else float("inf")
        row(f"paper::{name}", float(r.get("wall_s", 0)) * 1e6 /
            max(r.get("steps", 1), 1),
            f"valid_ce={ce:.4f};delta_vs_baseline={delta:+.4f};"
            f"diverged={r['diverged']}")


def table_memory_and_linear_share() -> None:
    """Fig 2 / Fig 3 analogs."""
    from benchmarks.linear_share import flops_split
    from repro.configs import get_config
    for arch in ("gpt2-small", "llama3-8b"):
        cfg = get_config(arch)
        for seq in (256, 1024, 4096, 32768):
            r = flops_split(cfg, seq)
            row(f"linear_share::{arch}::s{seq}", 0.0,
                f"linear_share={r['linear_share']:.3f}")
    path = os.path.join(EXP, "memory_breakdown.json")
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
        for name, rows_ in data.items():
            for r in rows_:
                act = r["activations_and_workspace_bytes"]
                st = r["params_plus_opt_bytes"]
                row(f"memfig::{name}::b{r['batch']}", 0.0,
                    f"activation_fraction={act / (act + st):.3f}")


def bench_opt_update() -> None:
    """Optimizer fast paths: fp vs fake vs int8-loop vs int8-fused AdamW
    (opt_ms, analytic HBM bytes, optimizer-state bytes)."""
    from benchmarks.opt_update import PATHS, bench_path
    for name, recipe_str, storage, fused in PATHS:
        r = bench_path(name, recipe_str, storage, fused, steps=1)
        row(f"opt::{name}", r["us_per_step"],
            f"opt_ms={r['opt_ms']:.2f};"
            f"hbm_bytes={r['hbm_bytes_per_step']};"
            f"opt_bytes={r['opt_state_bytes']};"
            f"path={r['kernel_path']}")


def bench_serve() -> None:
    """Engine serving throughput + KV residency, fp vs int8 policies."""
    from benchmarks.serve_throughput import POLICIES, bench_engine
    for pol in POLICIES:
        r = bench_engine(pol, slots=4, prompt_len=32, new_tokens=16)
        row(f"serve::{pol}", 0.0,
            f"prefill_tok_s={r['prefill_tok_s']:.1f};"
            f"decode_tok_s={r['decode_tok_s']:.1f};"
            f"kv_bytes={r['kv_bytes']};params_bytes={r['params_bytes']};"
            f"kv_read_bytes={r['kv_read_bytes']};path={r['path']}")


def bench_serve_trace() -> None:
    """Poisson-arrival trace through the paged engine's async scheduler:
    end-to-end latency percentiles, tokens/s, and peak live-KV bytes vs the
    dense engine's always-resident cache (emits BENCH_serve_trace.json)."""
    from benchmarks.serve_throughput import bench_serve_trace as trace
    r = trace(smoke=False)
    row("serve_trace::paged", 0.0,
        f"tok_s={r['tokens_per_s']:.1f};"
        f"p50_ms={r['latency_p50_s'] * 1e3:.2f};"
        f"p99_ms={r['latency_p99_s'] * 1e3:.2f};"
        f"live_kv_bytes={r['peak_live_kv_bytes']};"
        f"dense_kv_bytes={r['dense_resident_kv_bytes']};"
        f"parity={r['token_parity_vs_dense']};path={r['path']}")


def bench_serve_aot() -> None:
    """AOT-compiled (and, with multiple devices, mesh-sharded) serving:
    warmup compile cost + trace-free serving throughput (emits
    BENCH_serve.json)."""
    from benchmarks.serve_throughput import bench_aot_smoke
    r = bench_aot_smoke()
    row("serve_aot::kv_cache=a8t,*=w8c", 0.0,
        f"mesh={r['mesh']};n_exec={r['n_executables']};"
        f"compile_s={r['total_compile_s']:.2f};"
        f"decode_tok_s={r['decode_tok_s']:.1f};path={r['path']}")


def bench_resilience() -> None:
    """Fault-injection gates: sentinel skip/rollback/fallback ladder,
    checkpoint rotation fallback + atomic saves, scheduler watchdog and
    request deadlines (emits rows; the CI gate is --smoke)."""
    from benchmarks.resilience import run_all
    run_all(smoke=False)        # prints matching CSV rows itself


def bench_decode_attention() -> None:
    """Decode-attention hot path: fp cache vs int8 dequant-on-read vs the
    fused int8-KV kernel (per-step ms + analytic KV-bytes-read counter;
    interpret mode off-TPU -- dispatch validation, not kernel-speed truth)."""
    from benchmarks.serve_throughput import bench_decode_attn
    for mode in ("fp", "dequant", "fused"):
        r = bench_decode_attn(mode, slots=2, max_seq=64, kv_heads=2,
                              groups=2, head_dim=32, iters=2)
        row(f"decode_attn::{mode}", r["us_per_step"],
            f"decode_attn_ms={r['decode_attn_ms']:.3f};"
            f"kv_read_bytes={r['kv_read_bytes']}")


def table_roofline() -> None:
    """Dry-run roofline MFUs (train cells, single pod)."""
    from benchmarks.roofline import load
    rows_ = load(os.path.join(EXP, "dryrun"))
    for d in rows_:
        if d["status"] != "ok":
            row(f"roofline::{d['arch']}::{d['shape']}", 0.0, d["status"])
            continue
        r = d["roofline"]
        row(f"roofline::{d['arch']}::{d['shape']}", r["step_time_s"] * 1e6,
            f"dominant={r['dominant']};mfu={r.get('roofline_mfu', 0):.4f};"
            f"useful={r.get('useful_flops_ratio', 0):.3f}")


def main() -> None:
    setup_compile_cache()
    print("name,us_per_call,derived")
    bench_quantizer_ops()
    bench_kernels()
    bench_policy_backends()
    bench_train_steps()
    bench_train_throughput()
    bench_opt_update()
    bench_serve()
    bench_serve_trace()
    bench_serve_aot()
    bench_decode_attention()
    bench_resilience()
    table_paper_results()
    table_memory_and_linear_share()
    table_roofline()


if __name__ == "__main__":
    main()
