"""Production launcher: --arch <id> --shape <shape> on whatever mesh the
host provides (falls back to single device for local runs; on a real TPU
slice the same entry point builds the full mesh and sharded train step).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --smoke --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --shape train_4k --model-parallel 4       # on hardware
    PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --smoke \
        --policy 'block[0:2].*=fp,*=w8c+a8t@int8_pallas'   # per-layer policy
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_shape, get_smoke_config
from repro.core import fallback_policy, get_recipe, parse_policy
from repro.data import Loader, SyntheticCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import OptConfig
from repro.parallel.sharding import make_rules
from repro.train import (FaultPlan, LoopConfig, SentinelConfig,
                         StabilitySentinel, Trainer, init_train_state,
                         make_eval_step, make_train_step)
from repro.train.step import state_shardings, train_path_summary


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small batch (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--recipe", default="paper",
                    help="preset name or compact spec ('w8c,a8t,m1:4c')")
    ap.add_argument("--policy", default="",
                    help="per-layer-role policy rules, e.g. "
                         "'embed=fp,block[0:2].*=fp,*=w8c+a8t@int8_pallas' "
                         "(overrides --recipe)")
    ap.add_argument("--state-storage", default="fake")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--sentinel", action="store_true",
                    help="guard every step with the stability sentinel "
                         "(skip-batch / rollback / fallback-window ladder)")
    ap.add_argument("--sentinel-window", type=int, default=32)
    ap.add_argument("--sentinel-sigma", type=float, default=6.0)
    ap.add_argument("--fallback-steps", type=int, default=16,
                    help="length of the fp/fake-quant window after a rollback")
    ap.add_argument("--fallback-mode", choices=("fake", "fp"), default="fake",
                    help="degraded policy during the fallback window: "
                         "'fake' keeps fake-quant (continual-QAT posture), "
                         "'fp' drops quantization entirely")
    ap.add_argument("--fault", default="",
                    help="deterministic fault-injection spec (overrides the "
                         "REPRO_FAULT env var), e.g. 'nan_grad@50'")
    return ap.parse_args(argv)


@dataclasses.dataclass
class TrainRun:
    """Everything :func:`build` wires together: the config, the compiled-
    to-be step (sharded under a multi-device mesh) and the trainer that
    drives it.  ``summary`` is the ``train-path:`` line."""
    cfg: object
    model: object
    recipe: object
    opt: OptConfig
    mesh: object
    rules: object
    step: object
    trainer: Trainer
    batch: int
    seq: int
    summary: str
    faults: Optional[FaultPlan]


def build(args: argparse.Namespace) -> TrainRun:
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        batch = args.batch or 8
        seq = args.seq or 64
    else:
        cfg = get_config(args.arch)
        shape = get_shape(args.shape)
        batch = args.batch or shape.global_batch
        seq = args.seq or shape.seq_len

    model = build_model(cfg)
    recipe = (parse_policy(args.policy) if args.policy
              else get_recipe(args.recipe))
    mesh = make_host_mesh(args.model_parallel)
    multi = mesh.devices.size > 1
    rules = make_rules(mesh, "train", cfg=cfg) if multi else None
    opt = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                    total_steps=args.steps, state_storage=args.state_storage)
    summary = train_path_summary(recipe, getattr(cfg, "n_layers", 0),
                                 opt_cfg=opt)
    faults = FaultPlan.from_env(args.fault or None)
    sentinel = fallback_step = None
    if args.sentinel:
        sentinel = StabilitySentinel(SentinelConfig(
            window=args.sentinel_window, spike_sigma=args.sentinel_sigma,
            fallback_steps=args.fallback_steps))
    state = init_train_state(model, jax.random.PRNGKey(0), recipe, opt)
    step_fn = make_train_step(model, recipe, opt, rules=rules,
                              accum_steps=args.accum,
                              faults=faults if faults else None,
                              health=args.sentinel)
    if multi:
        st_sh = state_shardings(rules, model, jax.eval_shape(lambda: state))
        step = jax.jit(step_fn, in_shardings=(st_sh, None, None),
                       out_shardings=(st_sh, None))
    else:
        step = jax.jit(step_fn)
    if args.sentinel:
        # the degraded policy keeps the AdamState structure (m1/m2 specs are
        # preserved) so the two compiled steps hand the state back and forth
        fb_policy = fallback_policy(
            recipe, mode="fake_quant" if args.fallback_mode == "fake"
            else "fp")
        fb_fn = make_train_step(model, fb_policy, opt, rules=rules,
                                accum_steps=args.accum, health=True)
        fallback_step = (jax.jit(fb_fn, in_shardings=(st_sh, None, None),
                                 out_shardings=(st_sh, None))
                         if multi else jax.jit(fb_fn))
    eval_step = jax.jit(make_eval_step(model, recipe, rules=rules))

    corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
    loader = Loader(corpus, cfg, batch_size=batch, seq_len=seq)
    valid = Loader(corpus, cfg, batch_size=batch, seq_len=seq, split="valid")
    mgr = CheckpointManager(args.ckpt, async_write=True) if args.ckpt else None
    trainer = Trainer(step, eval_step, state, loader, ckpt=mgr,
                      valid_loader=valid,
                      loop_cfg=LoopConfig(
                          total_steps=args.steps,
                          ckpt_every=max(args.steps // 3, 50),
                          eval_every=max(args.steps // 5, 20),
                          log_every=10),
                      sentinel=sentinel, fallback_step=fallback_step,
                      faults=faults if faults else None)
    return TrainRun(cfg=cfg, model=model, recipe=recipe, opt=opt, mesh=mesh,
                    rules=rules, step=step, trainer=trainer, batch=batch,
                    seq=seq, summary=summary,
                    faults=faults if faults else None)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    setup_compile_cache()
    run = build(args)
    mesh = run.mesh
    print(f"arch={run.cfg.name} devices={mesh.devices.size} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"policy=[{run.recipe.describe()}] batch={run.batch} seq={run.seq}")
    print(f"train-path: {run.summary}")
    if run.faults:
        print(f"fault-plan: {run.faults.describe()}")
    trainer = run.trainer
    trainer.install_preemption_handler()
    trainer.maybe_resume()
    for rowd in trainer.run(rng=jax.random.PRNGKey(0)):
        extra = f"  valid={rowd['valid_ce']:.4f}" if "valid_ce" in rowd else ""
        if rowd.get("fallback"):
            extra += "  [fallback]"
        print(f"step {rowd['step']:5d}  ce={rowd['ce']:.4f}"
              f"  {rowd['sec_per_step']*1e3:.0f}ms/step{extra}", flush=True)
    if args.sentinel or run.faults:
        print(f"resilience: {trainer.resilience_summary()}")


if __name__ == "__main__":
    main()
