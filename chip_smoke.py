"""Prove that the paper's main path runs on a TPU, at full gpt2-small width.

    python chip_smoke.py              # one chip: int8 train step + serving
    python chip_smoke.py --chips 4    # the sharded train step on a 2x2 mesh

One process, no fallback: the script exits non-zero when JAX finds no TPU,
when the repository is not beside it, or when any phase fails a check.  Its
last line of standard output is the contract line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (one chip):

* **train** -- the launcher's own ``repro.launch.train.build`` on the real
  gpt2-small config (12L, d=768, vocab 50257) with the paper's int8 policy
  on the ``int8_pallas`` backend and int8 optimizer states: checks the
  reported kernel path, that the compiled step holds Pallas kernels, that
  the first step agrees with the same step on the ``fake_quant`` backend,
  and that ten steps give finite, falling cross-entropy.
* **serve** -- the fused int8-KV decode and q8 prefill kernels against the
  ``kernels/ref.py`` oracles at gpt2-small head widths, then ``Engine`` with
  prepared int8 weights and an int8 KV cache (AOT) answering four requests
  on the fused path at ladder rung 0.

``--chips 4`` runs only the sharded train step (FSDP x TP over a 2x2
``data x model`` mesh) and compares it with the one-device step on the same
state and batch.  Compile seconds and step wall times are printed for
information; they are host-clock times, not device metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the paper's int8 recipe end to end: W8 per-channel, A8 per-token, G8
#: per-token, both Adam moments int8 blockwise (b128; m2 asymmetric in the
#: sqrt domain) -- forward and backward on the Pallas int8 kernels
TRAIN_POLICY = "*=w8c+a8t+g8t+m1:8c-b128+m2:8c-asym-b128-sqrt@int8_pallas"
#: lr 3e-3 (5x the launcher default): ten steps from a random init must
#: move the loss well past the batch-to-batch spread
TRAIN_ARGV = ["--arch", "gpt2-small", "--policy", TRAIN_POLICY,
              "--state-storage", "int", "--seq", "1024", "--batch", "8",
              "--steps", "10", "--lr", "3e-3"]
SERVE_POLICY = "kv_cache=a8t,*=w8c"

# int8 MXU kernels vs fake quantization of the same codecs: the operands
# are identical, only the accumulation differs (exact int32 vs fp of the
# dequantized bf16 values).  Bounds on the first step's loss (absolute),
# gradient and update norms (relative).
CE_TOL = 2e-2
NORM_RTOL = 1e-1
# the sharded step is the same math partitioned over four chips; in the
# bf16 model only the order and precision of partial sums change.  The
# loss at the shared starting state is a forward quantity and agrees
# closely; the gradient norm is dominated by the embedding gradients, whose
# bf16 scatter-adds cancel heavily (float32 agrees to 1e-4, bf16 differs by
# up to 14% at the CPU smoke width).  A sharding fault -- a missing dp
# mean, a wrong shard order -- moves it by far more.
SHARD_CE_TOL = 5e-3
SHARD_NORM_RTOL = 0.25
#: Mosaic kernels cannot be partitioned by the SPMD compiler ("Mosaic
#: kernels cannot be automatically partitioned"), so under a mesh the
#: step runs the fake-quant backend with the fused AdamW and fused qdq
#: kernels switched off, on both sides of the comparison
SHARD_POLICY = TRAIN_POLICY.replace("@int8_pallas", "@fake_quant")
SHARD_ENV = {"REPRO_FUSED_ADAM": "0", "REPRO_FUSED_FQ": "0"}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def first_step_metrics(step, state, batch, key):
    import jax
    _, m = step(state, batch, key)
    return {k: float(v) for k, v in jax.device_get(m).items()}


def compile_step(step, state, batch, key, name: str):
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, key).compile()
    log(f"  {name}: compiled in {time.perf_counter() - t0:.1f}s")
    mem = compiled.memory_analysis()
    if mem is not None:
        log(f"  {name}: memory_analysis args="
            f"{mem.argument_size_in_bytes / 2**30:.2f}GiB "
            f"out={mem.output_size_in_bytes / 2**30:.2f}GiB "
            f"temp={mem.temp_size_in_bytes / 2**30:.2f}GiB")
    return compiled


def train_phase(argv=TRAIN_ARGV):
    """The int8 pre-training step through the launcher.  Returns the
    trained run (its model and params feed the serve phase)."""
    import dataclasses

    import jax

    from repro.core import parse_policy
    from repro.launch.train import build, parse_args
    from repro.train import make_train_step

    log("== train: int8_pallas fwd/bwd + fused int8 AdamW")
    run = build(parse_args(argv))
    log(f"  arch={run.cfg.name} layers={run.cfg.n_layers} "
        f"d_model={run.cfg.d_model} vocab={run.cfg.vocab_size} "
        f"batch={run.batch} seq={run.seq}")
    log(f"  train-path: {run.summary}")
    check("int8_pallas(fwd=int8,bwd=int8,res=int8)" in run.summary,
          "block linears run the int8 Pallas kernels forward and backward")
    check("opt=int8-fused(b128)" in run.summary,
          "optimizer runs the fused 8-bit AdamW kernel")

    trainer = run.trainer
    state0 = trainer.state
    batch0 = trainer.loader.peek(0)
    rng = jax.random.PRNGKey(0)
    key0 = jax.random.fold_in(rng, 0)
    compiled = compile_step(run.step, state0, batch0, key0, "int8 step")
    check("tpu_custom_call" in compiled.as_text(),
          "compiled train step holds Pallas TPU kernels")

    fake_policy = parse_policy(TRAIN_POLICY.replace("@int8_pallas",
                                                    "@fake_quant"))
    fake_step = jax.jit(make_train_step(run.model, fake_policy, run.opt,
                                        rules=run.rules))
    m_int = first_step_metrics(compiled, state0, batch0, key0)
    m_fake = first_step_metrics(fake_step, state0, batch0, key0)
    log(f"  first batch: int8 ce={m_int['ce']!r} grad_norm="
        f"{m_int['grad_norm']!r} update_norm={m_int['update_norm']!r}")
    log(f"  first batch: fake ce={m_fake['ce']!r} grad_norm="
        f"{m_fake['grad_norm']!r} update_norm={m_fake['update_norm']!r}")
    check(abs(m_int["ce"] - m_fake["ce"]) <= CE_TOL,
          f"int8 ce within {CE_TOL} of fake_quant")
    check(rel(m_int["grad_norm"], m_fake["grad_norm"]) <= NORM_RTOL,
          f"int8 grad_norm within {NORM_RTOL:.0%} of fake_quant")
    check(rel(m_int["update_norm"], m_fake["update_norm"]) <= NORM_RTOL,
          f"int8 update_norm within {NORM_RTOL:.0%} of fake_quant")

    # the trainer drives the executable compiled above, one synced log row
    # per step
    trainer.train_step = compiled
    trainer.cfg = dataclasses.replace(trainer.cfg, log_every=1)
    rows = trainer.run(rng=rng)
    ces = [r["ce"] for r in rows]
    prev = 0.0
    for r in rows:
        total = r["sec_per_step"] * r["step"]
        log(f"  step {r['step']:3d} ce={r['ce']:.4f} "
            f"wall={1e3 * (total - prev):.1f}ms")
        prev = total
    check(len(ces) == run.opt.total_steps, f"{len(ces)} steps ran")
    check(all(np.isfinite(ces)), "every step's ce is finite")
    check(ces[-1] < ces[0], f"ce fell: {ces[0]:.4f} -> {ces[-1]:.4f}")
    return run


def kernel_parity_phase():
    """The fused int8-KV kernels against the ``kernels/ref.py`` oracles at
    gpt2-small head widths (12 kv heads x 64)."""
    import jax
    import jax.numpy as jnp

    from repro.core.qconfig import Granularity, QuantSpec
    from repro.core.quantizer import quantize_int
    from repro.kernels.decode_attn import decode_attention
    from repro.kernels.flash_attn import flash_attention_fwd_q8
    from repro.kernels.ref import decode_attn_inputs, decode_attn_ref

    log("== serve kernels vs oracles")
    b, s, kh, hd = 4, 256, 12, 64
    q, kq, ks, vq, vs, _, _, nk, nv, pos = decode_attn_inputs(
        b, s, kh, 1, hd, lengths=[1, 100, 200, 255], seed=0)
    ref, (rkq, rks, rvq, rvs) = decode_attn_ref(q, kq, ks, vq, vs, nk, nv,
                                                pos)
    out, fkq, fks, fvq, fvs = jax.jit(decode_attention)(
        q, kq, ks, vq, vs, nk, nv, pos)
    err = float(jnp.max(jnp.abs(out - ref)))
    log(f"  decode: max |ctx - ref| = {err!r}")
    check(err < 1e-2, "fused decode context matches the oracle")
    check(bool(jnp.array_equal(fkq, rkq) and jnp.array_equal(fvq, rvq)),
          "fused decode writes the oracle's int8 K/V payloads bit for bit")
    check(bool(jnp.allclose(fks, rks, rtol=1e-6)
               and jnp.allclose(fvs, rvs, rtol=1e-6)),
          "fused decode writes the oracle's scales")

    spec = QuantSpec(8, Granularity.PER_TOKEN)
    sq, smax, h = 128, 256, 12
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    kf = jax.random.normal(keys[0], (2, smax, kh, hd), jnp.float32)
    vf = jax.random.normal(keys[1], (2, smax, kh, hd), jnp.float32)
    written = (jnp.arange(smax) < sq)[None, :, None, None]
    kq, ks, _ = quantize_int(kf, spec)
    vq, vs, _ = quantize_int(vf, spec)
    kq, vq = jnp.where(written, kq, 0), jnp.where(written, vq, 0)
    ks, vs = jnp.where(written, ks, 0.0), jnp.where(written, vs, 0.0)
    qp = jax.random.normal(keys[2], (2, sq, h, hd), jnp.float32)
    out = jax.jit(flash_attention_fwd_q8)(qp, kq, ks, vq, vs)
    kd = kq.astype(jnp.float32) * jnp.where(ks == 0, 1.0, ks)
    vd = vq.astype(jnp.float32) * jnp.where(vs == 0, 1.0, vs)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qp, kd,
                    precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(smax)[None, :] <= jnp.arange(sq)[:, None]
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, vd,
                      precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(out - want)))
    log(f"  q8 prefill: max |out - ref| = {err!r}")
    check(err < 1e-2, "q8 prefill flash matches the dequantized reference")


def serve_phase(model, params, max_seq: int = 256, prompt_len: int = 128,
                new_tokens: int = 32):
    from repro.infer import Engine, Request

    log("== serve: Engine, prepared int8 weights + int8 KV, AOT")
    t0 = time.perf_counter()
    eng = Engine(model, params, SERVE_POLICY, max_slots=4, max_seq=max_seq,
                 prefill_bucket=prompt_len, aot=True)
    log(f"  engine built + AOT-compiled in {time.perf_counter() - t0:.1f}s")
    ps = eng.path_summary()
    log(f"  path: {ps}")
    check("weights=prepared-int8" in ps, "weights are prepared int8")
    check(" kv=int8-fused(" in ps, "decode runs the fused int8-KV kernel")
    check("tpu_custom_call" in eng.lowered_decode_hlo(),
          "compiled decode step holds Pallas TPU kernels")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, model.cfg.vocab_size, prompt_len).tolist()
               for _ in range(4)]
    ids = [eng.submit(Request(tokens=p, max_new_tokens=new_tokens))
           for p in prompts]
    t0 = time.perf_counter()
    resps = {r.request_id: r for r in eng.run()}
    log(f"  {len(resps)} requests served in "
        f"{time.perf_counter() - t0:.2f}s")
    check(sorted(resps) == sorted(ids), "every request answered")
    for i in ids:
        r = resps[i]
        log(f"  request {i}: {r.finish_reason} {len(r.tokens)} tokens "
            f"{r.tokens[:8]}...")
        check(r.finish_reason == "length" and len(r.tokens) == new_tokens,
              f"request {i} finished with {new_tokens} tokens")
        check(all(0 <= t < model.cfg.vocab_size for t in r.tokens),
              f"request {i} tokens are in the vocabulary")
    rs = eng.resilience_summary()
    log(f"  resilience: rung={rs['rung']} demotions={rs['demotions']} "
        f"kernel_errors={rs['kernel_errors']} "
        f"quarantined={rs['quarantined']} step_ms={rs['step_ms']}")
    check(rs["rung_index"] == 0 and not rs["demotions"]
          and rs["kernel_errors"] == 0 and rs["quarantined"] == 0,
          "serving stayed on rung 0 with no demotion")


def sharded_phase(argv=TRAIN_ARGV, model_parallel: int = 2):
    """The launcher's sharded train step (FSDP over ``data``, TP over
    ``model``) against the one-device step on the same state and batch."""
    import jax

    from repro.launch.train import build, parse_args
    from repro.train import make_train_step

    log("== sharded train step vs one device")
    log(f"  Pallas kernels cannot be partitioned under a mesh: both steps "
        f"run {SHARD_POLICY.rsplit('@', 1)[1]} with {SHARD_ENV}")
    os.environ.update(SHARD_ENV)
    argv = [SHARD_POLICY if a == TRAIN_POLICY else a for a in argv]
    run = build(parse_args(argv + ["--model-parallel", str(model_parallel)]))
    mesh = dict(zip(run.mesh.axis_names, run.mesh.devices.shape))
    log(f"  mesh={mesh} train-path: {run.summary}")
    check(run.rules is not None and mesh["model"] == model_parallel,
          f"launcher built a {mesh} mesh with sharding rules")
    state0 = run.trainer.state
    batch0 = run.trainer.loader.peek(0)
    key0 = jax.random.fold_in(jax.random.PRNGKey(0), 0)
    sharded = compile_step(run.step, state0, batch0, key0, "sharded step")
    hlo = sharded.as_text()
    counts = {op: hlo.count(op) for op in
              ("tpu_custom_call", "all-gather", "all-reduce",
               "reduce-scatter")}
    log(f"  sharded step HLO op mentions: {counts}")
    one = jax.jit(make_train_step(run.model, run.recipe, run.opt))
    one = compile_step(one, state0, batch0, key0, "one-device step")
    m_sh = first_step_metrics(sharded, state0, batch0, key0)
    m_one = first_step_metrics(one, state0, batch0, key0)
    for name, m in (("sharded", m_sh), ("one-device", m_one)):
        log(f"  {name}: ce={m['ce']!r} grad_norm={m['grad_norm']!r} "
            f"update_norm={m['update_norm']!r}")
    check(abs(m_sh["ce"] - m_one["ce"]) <= SHARD_CE_TOL,
          f"sharded ce within {SHARD_CE_TOL} of one device")
    for k in ("grad_norm", "update_norm"):
        check(rel(m_sh[k], m_one[k]) <= SHARD_NORM_RTOL,
              f"sharded {k} within {SHARD_NORM_RTOL:.0%} of one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import setup_compile_cache
    log(f"device: {devs[0].device_kind} x{len(devs)}  jax {jax.__version__}"
        f"  compile cache: {setup_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        run = train_phase()
        kernel_parity_phase()
        serve_phase(run.model, run.trainer.state.params)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
