"""Serving throughput: engine prefill / decode tokens-per-second, KV-cache
residency, and the decode-attention hot path -- fp vs prepared-int8 weights
vs int8 KV, dequant-on-read vs fused kernel (gpt2-small smoke config).

Rows (CSV, matching benchmarks/run.py):

    serve::<policy>::prefill_tok_s   -- prompt tokens/s through admission
    serve::<policy>::decode_tok_s    -- batched decode steps x slots / s
    serve::<policy>::kv_bytes        -- resident decode-state bytes
    serve::<policy>::params_bytes    -- resident (prepared) parameter bytes
    decode_attn::<mode>              -- per-step decode-attention ms + the
                                        analytic KV-bytes-read counter
                                        (fp | dequant | fused)

Usage:
    PYTHONPATH=src python -m benchmarks.serve_throughput [--smoke]
        [--decode-smoke] [--aot-smoke] [--trace] [--trace-smoke] [--json]
        [--sweep]

``--smoke`` runs one tiny engine pass and asserts sane output (the CI
serve-smoke gate).  ``--decode-smoke`` is the decode-attention CI gate: it
pins the fused kernel on (interpret mode), asserts fused-vs-dequant logit
parity and that the fused path's analytic KV read is < 1/3 of the
dequant-on-read bytes.  ``--trace`` replays a Poisson-arrival request trace
through the paged engine's async scheduler and reports p50/p99 end-to-end
latency, tokens/s, and peak live-KV bytes vs the dense engine's resident
cache.  ``--trace-smoke`` is its CI gate: same trace, asserting per-request
token parity with a dense engine, finite p99, and peak paged live-token
bytes under half the dense resident bytes; writes ``BENCH_serve_trace.json``.
``--aot-smoke`` is the AOT/sharded serving gate: construct an
ahead-of-time-compiled engine (on a dp x tp2 mesh when the host exposes
multiple devices), then assert zero traces or compiles happen while
serving; writes ``BENCH_serve.json``.  ``--trace-overload-smoke`` is the
overload gate: an open-loop burst submits far past capacity into a
bounded-queue engine and asserts every overflow request is shed (finish
reason ``"shed"`` + retry-after hint, zero ``CapacityError`` escaping the
loop) while the admitted requests keep a finite p99 and positive goodput;
writes ``BENCH_serve_overload.json``.  ``--sweep`` times the fused kernel
across kv tile lengths (the ``REPRO_DECODE_BLOCK`` autotune hook, passed
explicitly so each size retraces).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.infer import Engine, Request, params_nbytes
from repro.launch.compile_cache import setup_compile_cache

POLICIES = ("*=fp", "*=w8c", "*=w8c+a8t", "kv_cache=a8t,*=w8c")

SWEEP_BLOCKS = (64, 128, 256, 512)


def build(policy: str, slots: int = 8, max_seq: int = 160):
    from repro.models import build_model
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return Engine(model, params, policy, max_slots=slots, max_seq=max_seq)


def bench_engine(policy: str, *, slots: int = 8, prompt_len: int = 64,
                 new_tokens: int = 32, vocab: int = 256) -> dict:
    eng = build(policy, slots=slots, max_seq=prompt_len + new_tokens + 1)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, vocab, (slots, prompt_len))

    # warmup: compile prefill (full-batch bucket) + decode
    eng.generate(prompts[:slots], 2)

    t0 = time.perf_counter()
    eng.generate(prompts, new_tokens)
    dt = time.perf_counter() - t0
    total_prefill = slots * prompt_len
    total_decode = slots * new_tokens
    # one timed run covers both phases; attribute by a second prefill-only run
    t1 = time.perf_counter()
    eng.generate(prompts, 1)
    dt_prefill = time.perf_counter() - t1
    dt_decode = max(dt - dt_prefill, 1e-9)
    return {
        "prefill_tok_s": total_prefill / max(dt_prefill, 1e-9),
        "decode_tok_s": total_decode / dt_decode,
        "kv_bytes": eng.kv_cache_nbytes(),
        "params_bytes": params_nbytes(eng.params),
        "kv_read_bytes": eng.kv_decode_read_bytes(),
        "path": eng.path_summary(),
    }


# ---------------------------------------------------------------------------
# Decode-attention micro-benchmark: one layer's attention read, three paths
# ---------------------------------------------------------------------------

def _decode_attn_inputs(slots: int, max_seq: int, kv_heads: int, groups: int,
                        head_dim: int, seed: int = 0):
    """Random ragged int8 cache + fp mirror + the step's fresh q/k/v rows
    (the shared fixture from kernels/ref.py, lengths spread over the slots)."""
    from repro.kernels.ref import decode_attn_inputs
    lengths = [(i * 7 + 3) % (max_seq - 1) for i in range(slots)]
    return decode_attn_inputs(slots, max_seq, kv_heads, groups, head_dim,
                              lengths, seed)


def _fp_attend(q, kf, vf, pos):
    s_ = jnp.einsum("bkgh,btkh->bkgt", q, kf,
                    preferred_element_type=jnp.float32)
    s_ = s_ / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    t = jnp.arange(kf.shape[1])
    s_ = jnp.where((t[None, :] <= pos[:, None])[:, None, None, :], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bkgt,btkh->bkgh", p, vf)


def bench_decode_attn(mode: str, *, slots: int = 8, max_seq: int = 512,
                      kv_heads: int = 4, groups: int = 4, head_dim: int = 64,
                      iters: int = 10, block_k=None) -> dict:
    """Per-step decode-attention time + the analytic KV-bytes-read counter
    for one layer.  ``fp`` attends on an fp cache, ``dequant`` dequantizes
    the whole int8 buffer (the reference), ``fused`` runs the Pallas kernel
    (interpret mode off-TPU: dispatch validation, not kernel-speed truth)."""
    from repro.kernels.decode_attn import decode_attention, decode_kv_read_bytes
    from repro.kernels.ref import decode_attn_ref
    q, kq, ks, vq, vs, kf, vf, nk, nv, pos = _decode_attn_inputs(
        slots, max_seq, kv_heads, groups, head_dim)

    if mode == "fp":
        rows = jnp.arange(slots)
        fn = jax.jit(lambda: _fp_attend(q, kf.at[rows, pos].set(nk),
                                        vf.at[rows, pos].set(nv), pos))
    elif mode == "dequant":
        fn = jax.jit(lambda: decode_attn_ref(q, kq, ks, vq, vs,
                                             nk, nv, pos)[0])
    elif mode == "fused":
        fn = jax.jit(lambda: decode_attention(q, kq, ks, vq, vs, nk, nv, pos,
                                              block_k=block_k)[0])
    else:
        raise ValueError(mode)

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    return {
        "decode_attn_ms": ms,
        "us_per_step": ms * 1e3,
        "kv_read_bytes": decode_kv_read_bytes(
            mode, slots, max_seq, kv_heads, head_dim, fp_bytes=4),
    }


def decode_smoke() -> None:
    """CI gate: fused kernel parity vs the dequant oracle (interpret mode)
    plus the memory-roofline claim on the analytic byte counters."""
    from repro.kernels.decode_attn import decode_attention, decode_kv_read_bytes
    from repro.kernels.ref import decode_attn_ref
    q, kq, ks, vq, vs, _, _, nk, nv, pos = _decode_attn_inputs(
        4, 32, 2, 3, 16)
    ref, (rkq, rks, rvq, rvs) = decode_attn_ref(q, kq, ks, vq, vs,
                                                nk, nv, pos)
    out, fkq, fks, fvq, fvs = decode_attention(q, kq, ks, vq, vs, nk, nv,
                                               pos, block_k=8,
                                               interpret=True)
    diff = float(jnp.max(jnp.abs(out - ref)))
    assert diff < 1e-4, f"fused vs dequant logits diverge: {diff}"
    assert jnp.array_equal(fkq, rkq) and jnp.array_equal(fvq, rvq), \
        "fused scatter payload != reference"
    fused = decode_kv_read_bytes("fused", 8, 2048, 8, 128, fp_bytes=2)
    deq = decode_kv_read_bytes("dequant", 8, 2048, 8, 128, fp_bytes=2)
    fp = decode_kv_read_bytes("fp", 8, 2048, 8, 128, fp_bytes=2)
    assert fused * 3 < deq, (fused, deq)
    assert fused < fp, (fused, fp)
    # the engine reports the fused path when it is enabled
    eng = build("kv_cache=a8t,*=w8c", slots=2, max_seq=24)
    assert "int8-fused" in eng.path_summary(), eng.path_summary()
    assert eng.kv_decode_read_bytes() < build("*=fp", slots=2, max_seq=24
                                              ).kv_decode_read_bytes()
    eng.submit(Request(tokens=[1, 2, 3, 4], max_new_tokens=4))
    eng.submit(Request(tokens=[5, 6], max_new_tokens=3))
    out_ = eng.run()
    assert [len(r.tokens) for r in out_] == [4, 3], out_
    print(f"decode-attn smoke ok: max|fused-dequant|={diff:.2e}, "
          f"kv_read fused={fused} dequant={deq} fp={fp}, "
          f"engine path [{eng.path_summary()}]")


def bench_serve_trace(*, n_requests: int = 12, mean_gap_s: float = 0.02,
                      slots: int = 4, max_seq: int = 64, page_size: int = 8,
                      max_new: int = 6, seed: int = 0,
                      policy: str = "kv_cache=a8t,*=w8c",
                      smoke: bool = False,
                      out_path: str = "BENCH_serve_trace.json") -> dict:
    """Poisson-arrival trace through the paged engine's async scheduler.

    ``n_requests`` random prompts arrive with exponential inter-arrival gaps
    (mean ``mean_gap_s``) while the scheduler loop runs in its background
    thread -- admission, chunked prefill, decode, preemption-free page churn
    all overlap with the arrival process.  The same requests run through a
    dense engine synchronously as the memory baseline and the token oracle
    (greedy decode is batch-invariant, so arrival pattern must not change
    one token).

    Reports p50/p99 end-to-end latency, wall-clock generated tokens/s, and
    the peak live-KV bytes the trace ever held vs the dense engine's
    always-resident ``slots x max_seq`` cache.  ``smoke`` asserts the gate
    (parity, finite p99, live < dense/2) and writes ``out_path``."""
    from repro.models import build_model
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    dense = Engine(model, params, policy, max_slots=slots, max_seq=max_seq,
                   seed=0)
    paged = Engine(model, params, policy, max_slots=slots, max_seq=max_seq,
                   seed=0, paged=True, page_size=page_size)

    rng = np.random.RandomState(seed)
    # prompt + max_new stays well under half of max_seq: the trace's mean
    # live occupancy sits near 50% of one slot strip, which is exactly the
    # regime where paging should (must) beat the dense resident cache
    lens = rng.randint(4, 13, n_requests)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]
    gaps = rng.exponential(mean_gap_s, n_requests)

    dense_ids = [dense.submit(Request(tokens=p, max_new_tokens=max_new))
                 for p in prompts]
    oracle = {i: r.tokens for i, r in
              zip(dense_ids, sorted(dense.run(),
                                    key=lambda r: r.request_id))}

    sched = paged.scheduler
    sched.start()
    t0 = time.monotonic()
    ids = []
    try:
        for p, g in zip(prompts, gaps):
            time.sleep(float(g))
            ids.append(paged.submit(Request(tokens=p,
                                            max_new_tokens=max_new)))
        sched.wait(ids, timeout=600)
    finally:
        sched.stop()
    wall_s = time.monotonic() - t0
    responses = {rid: sched.result(rid) for rid in ids}

    stats = sched.latency_stats()
    gen_tokens = sum(len(r.tokens) for r in responses.values())
    parity = all(responses[rid].tokens == oracle[did]
                 for rid, did in zip(ids, dense_ids))
    result = {
        "n_requests": n_requests,
        "mean_gap_s": mean_gap_s,
        "generated_tokens": gen_tokens,
        "wall_s": wall_s,
        "tokens_per_s": gen_tokens / max(wall_s, 1e-9),
        "latency_p50_s": stats["p50_s"],
        "latency_p99_s": stats["p99_s"],
        "latency_mean_s": stats["mean_s"],
        "peak_live_kv_bytes": sched.peak_live_bytes,
        "dense_resident_kv_bytes": dense.kv_cache_nbytes(),
        "live_over_dense": (sched.peak_live_bytes
                            / max(dense.kv_cache_nbytes(), 1)),
        "token_parity_vs_dense": parity,
        "scheduler_steps": sched.steps,
        "path": paged.path_summary(),
    }
    if smoke:
        assert parity, "paged trace tokens diverge from the dense engine"
        assert np.isfinite(stats["p99_s"]), stats
        assert result["peak_live_kv_bytes"] * 2 \
            < result["dense_resident_kv_bytes"], (
            "paged live-KV bytes not under half the dense resident cache: "
            f"{result['peak_live_kv_bytes']} vs "
            f"{result['dense_resident_kv_bytes']}")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"serve trace smoke ok: {gen_tokens} tokens, "
              f"p50={stats['p50_s'] * 1e3:.1f}ms "
              f"p99={stats['p99_s'] * 1e3:.1f}ms, "
              f"live/dense={result['live_over_dense']:.3f}, "
              f"path [{result['path']}] -> {out_path}")
    return result


def bench_overload_trace(*, n_requests: int = 24, slots: int = 2,
                         max_queue: int = 6, max_seq: int = 64,
                         page_size: int = 8, max_new: int = 8,
                         timeout_s: float = 60.0, seed: int = 0,
                         policy: str = "kv_cache=a8t,*=w8c",
                         smoke: bool = False,
                         out_path: str = "BENCH_serve_overload.json") -> dict:
    """Open-loop overload: submit ``n_requests`` back-to-back (no pacing,
    no client backpressure) into a ``slots``-slot paged engine whose
    scheduler caps the submit queue at ``max_queue``.

    Past capacity the bounded queue sheds at submit time (finish reason
    ``"shed"`` with a retry-after hint) and the deadline sweep sheds queued
    requests that can no longer make their deadline -- so the admitted
    work keeps flowing: the gate asserts every request got exactly one
    outcome (completed / shed / timeout -- no ``CapacityError`` ever
    escapes the loop), that overload actually occurred (shed > 0) while
    goodput stayed positive, and that the completed requests' p99 stayed
    finite and bounded.  ``smoke`` asserts and writes ``out_path``."""
    from repro.models import build_model
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = Engine(model, params, policy, max_slots=slots, max_seq=max_seq,
                 seed=0, paged=True, page_size=page_size,
                 max_queue=max_queue)

    rng = np.random.RandomState(seed)
    lens = rng.randint(4, 13, n_requests)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]

    # compile outside the timed burst (prefill buckets + decode); these two
    # warmup requests show up in the scheduler's outcome counters too
    n_warm = 2
    eng.generate(np.asarray([prompts[0][:4], prompts[1][:4]]), 2)

    sched = eng.scheduler
    sched.start()
    t0 = time.monotonic()
    ids = []
    try:
        for p in prompts:
            ids.append(eng.submit(Request(tokens=p, max_new_tokens=max_new,
                                          timeout_s=timeout_s)))
        sched.wait(ids, timeout=600)
    finally:
        sched.stop()                     # raises if the loop thread died
    wall_s = time.monotonic() - t0
    responses = {rid: sched.result(rid) for rid in ids}

    stats = sched.latency_stats()
    shed = [r for r in responses.values() if r.finish_reason == "shed"]
    done = [r for r in responses.values()
            if r.finish_reason in ("eos", "length")]
    result = {
        "n_requests": n_requests,
        "max_queue": max_queue,
        "slots": slots,
        "wall_s": wall_s,
        "completed": stats["completed"],
        "shed": stats["shed"],
        "timeout": stats["timeout"],
        "peak_queue_depth": stats["peak_queue_depth"],
        "goodput_tok_s": stats["goodput_tok_s"],
        "latency_p50_s": stats["p50_s"],
        "latency_p99_s": stats["p99_s"],
        "retry_after_s": [r.retry_after_s for r in shed[:3]],
        "path": eng.path_summary(),
    }
    if smoke:
        outcomes = stats["completed"] + stats["shed"] + stats["timeout"]
        assert outcomes == n_requests + n_warm, (stats, n_requests, n_warm)
        assert len(shed) > 0, "burst never overloaded the bounded queue"
        assert len(done) >= 1, stats
        assert all(r.retry_after_s is not None and r.retry_after_s > 0
                   for r in shed), "shed response missing retry-after hint"
        assert np.isfinite(stats["p99_s"]) and stats["p99_s"] < 120, stats
        assert stats["goodput_tok_s"] > 0, stats
        assert sched._loop_error is None, sched._loop_error
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"serve overload smoke ok: {result['completed']} completed / "
              f"{result['shed']} shed / {result['timeout']} timeout of "
              f"{n_requests + n_warm}, p99={stats['p99_s'] * 1e3:.1f}ms, "
              f"goodput={result['goodput_tok_s']:.1f} tok/s, "
              f"peak_depth={result['peak_queue_depth']} -> {out_path}")
    return result


def bench_aot_smoke(*, slots: int = 4, max_seq: int = 64,
                    prompt_len: int = 12, new_tokens: int = 8,
                    policy: str = "kv_cache=a8t,*=w8c",
                    out_path: str = "BENCH_serve.json") -> dict:
    """AOT serving gate: construct the engine ahead-of-time compiled (on a
    dp x tp2 mesh when the host exposes >= 2 devices, else single-device),
    assert the warmup report accounts for every executable, serve a batch,
    and assert *nothing* compiled or retraced during serving -- then write
    ``out_path`` with the compile/report/throughput numbers.

    CI runs this under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    so the mesh branch is the one the gate actually exercises."""
    from repro.models import build_model
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    mesh = None
    n_dev = jax.device_count()
    if n_dev >= 2:
        from jax.sharding import Mesh
        tp = 2
        dp = n_dev // tp
        mesh = Mesh(np.asarray(jax.devices()[:dp * tp]).reshape(dp, tp),
                    ("data", "model"))
    t0 = time.perf_counter()
    eng = Engine(model, params, policy, max_slots=slots, max_seq=max_seq,
                 prefill_bucket=16, mesh=mesh, aot=True)
    construct_s = time.perf_counter() - t0

    rep = eng.warmup_report()
    names = [e["name"] for e in rep["executables"]]
    assert "decode" in names and rep["n_executables"] >= 2, rep
    traces = dict(eng._trace_counts)
    n_exec = rep["n_executables"]

    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size, (slots, prompt_len))
    t1 = time.perf_counter()
    out = eng.generate(prompts, new_tokens)
    serve_s = time.perf_counter() - t1
    assert out.shape == (slots, new_tokens), out.shape

    # the gate: serving an AOT engine never traces or compiles
    assert eng._trace_counts == traces, (traces, eng._trace_counts)
    assert eng.warmup_report()["n_executables"] == n_exec, \
        "serving compiled a new executable past warmup"

    # the CPU backend's compiled executables expose no generated-code size
    # (memory_analysis reports 0) -- report n/a rather than a misleading 0;
    # on a real TPU this is the per-core program size and should be nonzero
    code_bytes = int(rep["total_code_bytes"])
    result = {
        "devices": n_dev,
        "mesh": (f"dp{mesh.devices.shape[0]}xtp{mesh.devices.shape[1]}"
                 if mesh is not None else None),
        "policy": policy,
        "n_executables": rep["n_executables"],
        "executables": names,
        "total_compile_s": rep["total_compile_s"],
        "total_code_bytes": code_bytes if code_bytes else "n/a",
        "code_bytes_note": (None if code_bytes else
                            "backend reports no generated-code size "
                            "(expected on CPU; nonzero on real TPU)"),
        "construct_s": construct_s,
        "serve_s": serve_s,
        "decode_tok_s": slots * new_tokens / max(serve_s, 1e-9),
        "path": eng.path_summary(),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"serve aot smoke ok: {result['n_executables']} executables "
          f"compiled in {result['total_compile_s']:.2f}s "
          f"(mesh={result['mesh']}), zero traces/compiles while serving, "
          f"path [{result['path']}] -> {out_path}")
    return result


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny engine pass + sanity assertions (CI gate)")
    ap.add_argument("--decode-smoke", action="store_true",
                    help="fused decode-attention parity + KV-bytes gate (CI)")
    ap.add_argument("--trace", action="store_true",
                    help="Poisson-arrival trace through the paged async "
                         "scheduler: latency percentiles + live-KV memory")
    ap.add_argument("--trace-smoke", action="store_true",
                    help="trace gate (CI): token parity vs dense, finite "
                         "p99, live bytes < dense/2; writes "
                         "BENCH_serve_trace.json")
    ap.add_argument("--aot-smoke", action="store_true",
                    help="AOT/sharded serving gate (CI): warmup report "
                         "complete, zero traces or compiles while serving; "
                         "writes BENCH_serve.json")
    ap.add_argument("--trace-overload", action="store_true",
                    help="open-loop burst past capacity: shed/goodput/"
                         "latency report for a bounded-queue engine")
    ap.add_argument("--trace-overload-smoke", action="store_true",
                    help="overload gate (CI): every request completed or "
                         "shed (zero CapacityError), finite p99, positive "
                         "goodput; writes BENCH_serve_overload.json")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object instead of CSV rows")
    ap.add_argument("--sweep", action="store_true",
                    help="fused-kernel timing across kv tile lengths "
                         "(REPRO_DECODE_BLOCK values)")
    args = ap.parse_args()

    if args.decode_smoke:
        import os
        os.environ.setdefault("REPRO_FUSED_DECODE", "1")
        decode_smoke()
        return

    if args.aot_smoke:
        import os
        os.environ.setdefault("REPRO_FUSED_DECODE", "1")
        r = bench_aot_smoke()
        if args.json:
            print(json.dumps(r, indent=2))
        return

    if args.smoke:
        eng = build("kv_cache=a8t,*=w8c", slots=2, max_seq=32)
        eng.submit(Request(tokens=[1, 2, 3, 4], max_new_tokens=6))
        eng.submit(Request(tokens=[5, 6], max_new_tokens=4))
        out = eng.run()
        assert len(out) == 2 and [len(r.tokens) for r in out] == [6, 4], out
        fp = build("*=fp", slots=2, max_seq=32)
        assert eng.kv_cache_nbytes() < fp.kv_cache_nbytes(), "int8 KV not smaller"
        assert params_nbytes(eng.params) < params_nbytes(fp.params), \
            "prepared weights not smaller"
        print("serve smoke ok:", [(r.request_id, r.finish_reason) for r in out],
              f"kv {eng.kv_cache_nbytes()}B vs fp {fp.kv_cache_nbytes()}B,",
              f"path [{eng.path_summary()}]")
        return

    if args.trace_overload or args.trace_overload_smoke:
        r = bench_overload_trace(smoke=args.trace_overload_smoke)
        if args.json:
            print(json.dumps(r, indent=2))
        elif not args.trace_overload_smoke:
            print("name,us_per_call,derived")
            print(f"serve_overload::completed,0.0,{r['completed']}")
            print(f"serve_overload::shed,0.0,{r['shed']}")
            print(f"serve_overload::goodput_tok_s,0.0,"
                  f"{r['goodput_tok_s']:.1f}")
            print(f"serve_overload::p99_ms,0.0,{r['latency_p99_s'] * 1e3:.2f}")
            print(f"serve_overload::peak_depth,0.0,{r['peak_queue_depth']}")
        return

    if args.trace or args.trace_smoke:
        r = bench_serve_trace(smoke=args.trace_smoke)
        if args.json:
            print(json.dumps(r, indent=2))
        elif not args.trace_smoke:
            print("name,us_per_call,derived")
            print(f"serve_trace::tok_s,0.0,{r['tokens_per_s']:.1f}")
            print(f"serve_trace::p50_ms,0.0,{r['latency_p50_s'] * 1e3:.2f}")
            print(f"serve_trace::p99_ms,0.0,{r['latency_p99_s'] * 1e3:.2f}")
            print(f"serve_trace::live_kv_bytes,0.0,{r['peak_live_kv_bytes']}")
            print("serve_trace::dense_kv_bytes,0.0,"
                  f"{r['dense_resident_kv_bytes']}")
        return

    if args.sweep:
        rows = []
        for blk in SWEEP_BLOCKS:
            r = bench_decode_attn("fused", block_k=blk, iters=3)
            rows.append({"block_k": blk, **r})
        if args.json:
            print(json.dumps(rows, indent=2))
        else:
            print("name,us_per_call,derived")
            for r in rows:
                print(f"decode_attn::fused::b{r['block_k']},"
                      f"{r['us_per_step']:.1f},"
                      f"kv_read_bytes={r['kv_read_bytes']}")
        return

    results = {}
    for pol in POLICIES:
        results[pol] = bench_engine(pol)
    attn = {mode: bench_decode_attn(mode, iters=3)
            for mode in ("fp", "dequant", "fused")}
    if args.json:
        print(json.dumps({"engine": results, "decode_attn": attn}, indent=2))
        return
    print("name,us_per_call,derived")
    for pol, r in results.items():
        print(f"serve::{pol}::prefill_tok_s,0.0,{r['prefill_tok_s']:.1f}")
        print(f"serve::{pol}::decode_tok_s,0.0,{r['decode_tok_s']:.1f}")
        print(f"serve::{pol}::kv_bytes,0.0,{r['kv_bytes']}")
        print(f"serve::{pol}::params_bytes,0.0,{r['params_bytes']}")
        print(f"serve::{pol}::kv_read_bytes,0.0,{r['kv_read_bytes']}")
    for mode, r in attn.items():
        print(f"decode_attn::{mode},{r['us_per_step']:.1f},"
              f"decode_attn_ms={r['decode_attn_ms']:.3f};"
              f"kv_read_bytes={r['kv_read_bytes']}")


if __name__ == "__main__":
    main()
