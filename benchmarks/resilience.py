"""Resilience gate: drive the fault-injection harness through every guard.

Each scenario plants a deterministic fault (``train.faults.FaultPlan``) and
asserts the matching defense absorbed it:

    detect_and_skip    nan_grad mid-run, no checkpoint: the sentinel skips
                       the poisoned updates; params stay finite.
    recovery_ladder    nan_grad with checkpointing + a compiled fallback
                       step: skip -> rollback to the newest checkpoint ->
                       fallback window past the fault -> re-engage; the run
                       finishes with a full complement of applied updates.
    rotation_fallback  the newest checkpoint is corrupted on disk before
                       the rollback needs it: ``restore_latest`` walks the
                       rotation to the previous intact one.
    atomic_save        SIGTERM lands in the payload/commit window of a
                       save: nothing half-written is ever restorable.
    sched_watchdog     the serving scheduler's background thread dies:
                       blocked ``wait()`` callers are woken and re-raise
                       instead of hanging.
    request_timeout    a request past its deadline is cancelled (finish
                       reason ``"timeout"``), its slot freed, the engine
                       immediately reusable.
    overload_shed      six submissions against a one-slot engine with a
                       bounded submit queue: the overflow is rejected with
                       finish reason ``"shed"`` + a retry-after hint, the
                       admitted requests complete, no exception escapes.
    nan_quarantine     a non-finite logits row mid-decode evicts only that
                       request (finish reason ``"numerics"``); its batchmate
                       and the engine are unharmed.
    ladder_walk        a fused-kernel failure then repeated numeric faults
                       walk the engine down its degradation ladder (paged
                       fused -> dequant-on-read -> fp reference) exactly as
                       scripted, then healthy steps re-engage rung by rung
                       back to fused.
    oom_preempt        an injected page-pool drain mid-decode forces
                       preemption instead of CapacityError; every request
                       still completes and the pages come back.

``--smoke`` runs all scenarios, asserts every gate AND that every planned
fault actually fired, then writes ``BENCH_resilience.json`` (the CI
artifact).  Default (no flag) prints the same CSV rows as benchmarks.run.

Usage:
    PYTHONPATH=src python -m benchmarks.resilience [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import json
import math
import signal
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import setup_compile_cache

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Shared smoke-trainer scaffolding
# ---------------------------------------------------------------------------

def _trainer_parts():
    from repro.configs import get_smoke_config
    from repro.core import beyond_paper_recipe
    from repro.data import Loader, SyntheticCorpus
    from repro.models import build_model
    from repro.optim import OptConfig
    from repro.train import init_train_state

    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
    recipe = beyond_paper_recipe()
    opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=100,
                    state_storage="int")
    loader = Loader(corpus, cfg, batch_size=4, seq_len=32)
    state = init_train_state(model, KEY, recipe, opt)
    return cfg, model, recipe, opt, loader, state


def _guarded_run(fault_spec: str, tmp_dir, *, total_steps: int = 12,
                 ckpt_every: int = 10 ** 9, fallback: bool = False,
                 sentinel_kw=None):
    from repro.checkpoint import CheckpointManager
    from repro.core import fallback_policy
    from repro.train import (FaultPlan, LoopConfig, SentinelConfig,
                             StabilitySentinel, Trainer, make_train_step)

    _, model, recipe, opt, loader, state = _trainer_parts()
    faults = FaultPlan.parse(fault_spec)
    step = jax.jit(make_train_step(model, recipe, opt, faults=faults,
                                   health=True))
    fb = (jax.jit(make_train_step(model, fallback_policy(recipe), opt,
                                  health=True))
          if fallback else None)
    cfg_kw = dict(window=8, min_history=2, skip_limit=1, fallback_steps=4,
                  max_rollbacks=3)
    cfg_kw.update(sentinel_kw or {})
    sentinel = StabilitySentinel(SentinelConfig(**cfg_kw))
    mgr = CheckpointManager(str(tmp_dir)) if ckpt_every < 10 ** 9 else None
    t = Trainer(step, None, state, loader, ckpt=mgr,
                loop_cfg=LoopConfig(total_steps=total_steps,
                                    ckpt_every=ckpt_every, log_every=1),
                sentinel=sentinel, fallback_step=fb, faults=faults)
    hist = t.run(rng=KEY)
    summary = t.resilience_summary()
    summary["final_ce"] = float(hist[-1]["ce"]) if hist else float("nan")
    summary["params_finite"] = all(
        bool(jnp.all(jnp.isfinite(p))) for p in
        jax.tree_util.tree_leaves(t.state.params))
    summary["opt_step"] = int(t.state.opt.step)
    return summary


# ---------------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------------

def scenario_detect_and_skip(tmp_dir) -> dict:
    s = _guarded_run("nan_grad@3", tmp_dir, sentinel_kw={"skip_limit": 99})
    ok = (s["skipped_batches"] >= 1 and s["restores"] == 0
          and s["params_finite"] and math.isfinite(s["final_ce"])
          and "nan_grad@3" in s["faults_fired"])
    return {"ok": ok, "skipped": s["skipped_batches"],
            "final_ce": s["final_ce"]}


def scenario_recovery_ladder(tmp_dir) -> dict:
    s = _guarded_run("nan_grad@5", tmp_dir, ckpt_every=3, fallback=True)
    sent = s["sentinel"]
    ok = (sent["rollbacks"] >= 1 and s["restores"] >= 1
          and sent["fallback_steps_run"] >= 1 and s["params_finite"]
          and s["opt_step"] == 12 and math.isfinite(s["final_ce"])
          and "nan_grad@5" in s["faults_fired"])
    return {"ok": ok, "rollbacks": sent["rollbacks"],
            "restores": s["restores"], "skipped": s["skipped_batches"],
            "fallback_steps": sent["fallback_steps_run"],
            "opt_step": s["opt_step"], "final_ce": s["final_ce"]}


def scenario_rotation_fallback(tmp_dir) -> dict:
    # the 2nd completed save (the newest at rollback time) is corrupted on
    # disk; restore_latest must fall back to the older intact checkpoint
    s = _guarded_run("nan_grad@5;corrupt_ckpt@2:mode=flip", tmp_dir,
                     ckpt_every=2, fallback=True)
    sent = s["sentinel"]
    ok = (sent["rollbacks"] >= 1 and s["restores"] >= 1
          and s["params_finite"] and math.isfinite(s["final_ce"])
          and set(s["faults_fired"]) >= {"nan_grad@5",
                                         "corrupt_ckpt@2:mode=flip"})
    return {"ok": ok, "rollbacks": sent["rollbacks"],
            "restores": s["restores"], "final_ce": s["final_ce"]}


def scenario_atomic_save(tmp_dir) -> dict:
    from repro.checkpoint import CheckpointManager
    from repro.train import FaultPlan

    plan = FaultPlan.parse("sigterm_save@1")
    mgr = CheckpointManager(str(tmp_dir))
    plan.install(mgr)
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    aborted = False

    def raise_term(signum, frame):
        raise RuntimeError("SIGTERM")

    old = signal.signal(signal.SIGTERM, raise_term)
    try:
        try:
            mgr.save(1, tree)
        except RuntimeError:
            aborted = True
    finally:
        signal.signal(signal.SIGTERM, old)
    none_after_abort = mgr.all_steps() == []
    mgr.save(2, tree)                            # the fault is one-shot
    mgr.restore(2, jax.tree_util.tree_map(jnp.zeros_like, tree))
    ok = (aborted and none_after_abort and mgr.all_steps() == [2]
          and plan.fired == ["sigterm_save@1"])
    return {"ok": ok, "aborted": aborted,
            "none_after_abort": none_after_abort}


def _engine(max_slots=1, max_seq=256):
    from repro.configs import get_smoke_config
    from repro.infer import Engine
    from repro.models import build_model

    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(KEY)
    return Engine(model, params, max_slots=max_slots, max_seq=max_seq)


def scenario_sched_watchdog() -> dict:
    from repro.infer import Request
    from repro.train import FaultInjected, FaultPlan

    eng = _engine(max_slots=2, max_seq=64)
    sched = eng.scheduler
    plan = FaultPlan.parse("dead_sched@2")
    sched.fault_hook = plan.scheduler_hook()
    sched.start()
    rid = eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=50))
    t0 = time.monotonic()
    wait_raised = stop_raised = False
    try:
        sched.wait([rid], timeout=60)
    except FaultInjected:
        wait_raised = True
    try:
        sched.stop()
    except FaultInjected:
        stop_raised = True
    woke_s = time.monotonic() - t0
    ok = (wait_raised and stop_raised and woke_s < 60
          and plan.fired == ["dead_sched@2"])
    return {"ok": ok, "wait_raised": wait_raised, "stop_raised": stop_raised,
            "woke_s": woke_s}


def scenario_request_timeout() -> dict:
    from repro.infer import Request

    eng = _engine(max_slots=1, max_seq=256)
    eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=200,
                       timeout_s=0.01))
    [r] = eng.run()
    timed_out = r.finish_reason == "timeout" and len(r.tokens) < 200
    slot_freed = not eng._running and len(eng._free) == 1
    # the engine stays serviceable after the cancel
    eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=3))
    [r2] = eng.run()
    reusable = r2.finish_reason == "length" and len(r2.tokens) == 3
    ok = timed_out and slot_freed and reusable
    return {"ok": ok, "finish_reason": r.finish_reason,
            "partial_tokens": len(r.tokens), "slot_freed": slot_freed,
            "reusable": reusable}


def scenario_overload_shed() -> dict:
    from repro.configs import get_smoke_config
    from repro.infer import Engine, Request
    from repro.models import build_model

    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(KEY)
    eng = Engine(model, params, max_slots=1, max_seq=64, max_queue=2)
    for _ in range(6):
        eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=4))
    rs = eng.run()                       # no CapacityError may escape
    shed = [r for r in rs if r.finish_reason == "shed"]
    done = [r for r in rs if r.finish_reason == "length"]
    stats = eng.scheduler.latency_stats()
    ok = (len(rs) == 6 and len(shed) == 4 and len(done) == 2
          and all(r.retry_after_s is not None and r.retry_after_s > 0
                  for r in shed)
          and all(not r.tokens for r in shed)
          and all(len(r.tokens) == 4 for r in done)
          and stats["shed"] == 4 and stats["completed"] == 2
          and stats["n"] == 2)           # shed excluded from latency pctls
    return {"ok": ok, "shed": len(shed), "completed": len(done),
            "retry_after_s": (shed[0].retry_after_s if shed else None),
            "goodput_tok_s": round(stats["goodput_tok_s"], 1)}


def scenario_nan_quarantine() -> dict:
    from repro.infer import Request
    from repro.train import FaultPlan

    eng = _engine(max_slots=2, max_seq=64)
    plan = FaultPlan.parse("nan_logit@2:slot=0")
    eng.fault_hooks = plan.engine_hooks()
    rid_victim = eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=8))
    rid_other = eng.submit(Request(tokens=[4, 5, 6], max_new_tokens=8))
    rs = {r.request_id: r for r in eng.run()}
    victim, other = rs[rid_victim], rs[rid_other]
    quarantined = (victim.finish_reason == "numerics"
                   and 0 < len(victim.tokens) < 8)
    survivor_ok = (other.finish_reason == "length"
                   and len(other.tokens) == 8)
    # the engine keeps serving after the quarantine
    eng.submit(Request(tokens=[1, 2, 3], max_new_tokens=3))
    [r2] = eng.run()
    reusable = r2.finish_reason == "length" and len(r2.tokens) == 3
    s = eng.resilience_summary()
    ok = (quarantined and survivor_ok and reusable
          and s["quarantined"] == 1 and s["rung_index"] == 0
          and plan.fired == ["nan_logit@2:slot=0"])
    return {"ok": ok, "victim_reason": victim.finish_reason,
            "victim_tokens": len(victim.tokens),
            "survivor_tokens": len(other.tokens), "reusable": reusable}


def scenario_ladder_walk() -> dict:
    import os

    from repro.configs import get_smoke_config
    from repro.infer import Engine, MonitorConfig, Request
    from repro.models import build_model
    from repro.train import FaultPlan

    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(KEY)
    old = os.environ.get("REPRO_FUSED_DECODE")
    os.environ["REPRO_FUSED_DECODE"] = "1"
    try:
        eng = Engine(model, params, "kv_cache=a8t,*=w8c", max_slots=2,
                     max_seq=64, paged=True, page_size=8, n_pages=16,
                     monitor=MonitorConfig(reprobe_after=4, numeric_limit=2,
                                           numeric_window=8))
    finally:
        if old is None:
            os.environ.pop("REPRO_FUSED_DECODE", None)
        else:
            os.environ["REPRO_FUSED_DECODE"] = old
    plan = FaultPlan.parse(
        "kernel_error@1;nan_logit@3:slot=1;nan_logit@5:slot=1")
    eng.fault_hooks = plan.engine_hooks()
    rid_a = eng.submit(Request(tokens=[1, 2, 3, 4], max_new_tokens=24))
    eng.submit(Request(tokens=[5, 6], max_new_tokens=16))
    eng.submit(Request(tokens=[7, 8], max_new_tokens=16))
    rs = {r.request_id: r for r in eng.run()}
    s = eng.resilience_summary()
    walk_down = [(d["step"], d["from"], d["to"]) for d in s["demotions"]]
    walk_up = [(p["step"], p["from"], p["to"]) for p in s["promotions"]]
    # the scripted walk, exactly: kernel fault at 1 demotes fused->dequant;
    # two quarantines inside the window demote dequant->fp at 5; 4-step
    # healthy streaks re-engage fp->dequant at 9 and dequant->fused at 13
    ok = (walk_down == [(1, "fused", "dequant"), (5, "dequant", "fp")]
          and walk_up == [(9, "fp", "dequant"), (13, "dequant", "fused")]
          and s["rung"] == "fused" and s["rung_index"] == 0
          and s["kernel_errors"] == 1 and s["quarantined"] == 2
          and rs[rid_a].finish_reason == "length"
          and len(rs[rid_a].tokens) == 24
          and len(plan.fired) == 3)
    return {"ok": ok, "demotions": walk_down, "promotions": walk_up,
            "final_rung": s["rung"], "survivor_tokens": len(rs[rid_a].tokens)}


def scenario_oom_preempt() -> dict:
    from repro.configs import get_smoke_config
    from repro.infer import Engine, Request
    from repro.models import build_model
    from repro.train import FaultPlan

    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    params = model.init_params(KEY)
    eng = Engine(model, params, max_slots=2, max_seq=64, paged=True,
                 page_size=4, n_pages=6)            # 5 allocatable pages
    plan = FaultPlan.parse("oom_pages@1:hold=2")
    eng.fault_hooks = plan.engine_hooks()
    free0 = eng.pool.free_pages
    rids = [eng.submit(Request(tokens=[1, 2, 3, 4], max_new_tokens=12)),
            eng.submit(Request(tokens=[5, 6, 7, 8], max_new_tokens=12))]
    rs = {r.request_id: r for r in eng.run()}       # no CapacityError
    all_done = all(rs[rid].finish_reason == "length"
                   and len(rs[rid].tokens) == 12 for rid in rids)
    pages_back = eng.pool.free_pages == free0
    ok = (all_done and eng.preemptions >= 1 and pages_back
          and plan.fired == ["oom_pages@1:hold=2"])
    return {"ok": ok, "preemptions": eng.preemptions,
            "pages_back": pages_back,
            "tokens": [len(rs[rid].tokens) for rid in rids]}


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

def run_all(out_path: str = "BENCH_resilience.json", smoke: bool = False,
            emit_json: bool = False) -> dict:
    import tempfile

    results = {}
    t_all = time.monotonic()
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2, \
            tempfile.TemporaryDirectory() as d3, \
            tempfile.TemporaryDirectory() as d4:
        for name, fn in (
                ("detect_and_skip", lambda: scenario_detect_and_skip(d1)),
                ("recovery_ladder", lambda: scenario_recovery_ladder(d2)),
                ("rotation_fallback", lambda: scenario_rotation_fallback(d3)),
                ("atomic_save", lambda: scenario_atomic_save(d4)),
                ("sched_watchdog", scenario_sched_watchdog),
                ("request_timeout", scenario_request_timeout),
                ("overload_shed", scenario_overload_shed),
                ("nan_quarantine", scenario_nan_quarantine),
                ("ladder_walk", scenario_ladder_walk),
                ("oom_preempt", scenario_oom_preempt)):
            t0 = time.monotonic()
            r = fn()
            r["wall_s"] = round(time.monotonic() - t0, 2)
            results[name] = r
            if not emit_json:
                print(f"resilience::{name},0.0,"
                      + ";".join(f"{k}={v}" for k, v in r.items()),
                      flush=True)
    results["total_wall_s"] = round(time.monotonic() - t_all, 2)
    if smoke:
        failed = [n for n, r in results.items()
                  if isinstance(r, dict) and not r.get("ok")]
        assert not failed, f"resilience scenarios failed: {failed}"
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"resilience smoke ok: 10 scenarios in "
              f"{results['total_wall_s']:.1f}s -> {out_path}")
    if emit_json:
        print(json.dumps(results, indent=2))
    return results


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="assert every gate; write BENCH_resilience.json")
    ap.add_argument("--json", action="store_true",
                    help="print results as JSON instead of CSV rows")
    args = ap.parse_args()
    run_all(smoke=args.smoke, emit_json=args.json)


if __name__ == "__main__":
    main()
