"""Training cells: the program's compiled train step, driven from the seed.

Set-up builds one object, the compiled step with its state, and drives it
through its first steps with the window's own call and feed: those steps
give the readings that the plain reference is compared with once the
window has closed.  The same state then runs on through the window.

The numbers compared (each against its limit in ``limits/<cell>.json``):

* ``loss_gap``: the largest gap, over the compared steps, between the loss
  the program's step reports and the reference's loss on the same batch;
* ``grad_gap``: by the worst leaf, the gap between the norm of the first
  gradient as the program's optimizer got it (its first moment after one
  step, over 1 - beta1) and the reference's clipped gradient, relative to
  the larger of that leaf's reference norm and the median leaf's;
* ``change_gap``: the same measure for the parameters' change over the
  compared steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import statistics
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model as cbmodel
from chipbench import registry
from chipbench.reference import adamw as ref_adamw
from chipbench.reference.common import batch_loss

#: streams of the seed
_WEIGHTS, _TOKENS, _STEP_KEY = 1, 2, 3
#: leaves whose reference gradient norm is below this share of the median
#: leaf's are left out of ``change_gap``
NEGLIGIBLE_GRAD = 1e-3
#: steps the window keeps queued beyond the newest one known finished
AHEAD = 2


def _say(what: str, t0: float) -> float:
    """Print how long a part of the run took (host clock) and return now."""
    now = time.perf_counter()
    print(f"time {what} {now - t0:.3f} s", flush=True)
    return now


def _name(path) -> str:
    """``blocks/attn/wq`` of a pytree key path."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_name(path) for path, _ in flat]


def make_tokens(key, mix: Dict, vocab: int, n_batches: int):
    """(n_batches, batch, seq + 1) token ids, log-uniform over the
    vocabulary (a Zipf-like law: id r drawn with weight ~ 1/(r + 1))."""
    shape = (n_batches, mix["batch"], mix["seq"] + 1)
    u = jax.random.uniform(key, shape, jnp.float32)
    ids = jnp.floor(jnp.exp(u * jnp.log(vocab + 1.0))).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, vocab - 1)


@dataclasses.dataclass
class Readings:
    """What the program and the reference are compared on."""
    losses: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]


@jax.jit
def _change_norms(a, b):
    """Per-leaf norms of a - b."""
    return [jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]


def _decoded_norm(m1, size: int, b1: float):
    """Norm of the gradient an optimizer state's first moment holds after
    one step: a float array, or an int payload with scale and zero point in
    (blocks, block_size) rows, decoded as scale * (q + zero)."""
    if hasattr(m1, "q") and hasattr(m1, "scale"):
        x = m1.scale * (m1.q.astype(jnp.float32) + m1.zero)
        x = x.reshape(-1)[:size]
    else:
        x = m1.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(x))) / (1.0 - b1)


@contextlib.contextmanager
def program_env(mix: Dict):
    """The program's switches of ``mix["program_env"]``, set while the
    program is traced; the environment is restored as it was after."""
    saved = dict(os.environ)
    os.environ.update(mix.get("program_env", {}))
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


def program(conf: Dict, mix: Dict):
    """The program's side of a cell: its config, model, train step (the
    launcher's ``make_train_step`` for one device) and a function that
    makes the whole train state from a key, weights from the reference's
    initialisation and the optimizer state from the program's own.  Trace
    them inside :func:`program_env`."""
    from repro.core import parse_policy
    from repro.models import build_model
    from repro.optim import OptConfig
    from repro.optim.adamw import init_adam_state
    from repro.train import TrainState, make_train_step

    cfg = cbmodel.program_config(conf)
    model = build_model(cfg)
    policy = parse_policy(mix["policy"])
    o = mix["optimizer"]
    opt = OptConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
                    warmup_steps=o["warmup_steps"],
                    total_steps=o["total_steps"],
                    state_storage=mix["state_storage"])
    ref = registry.reference(conf)

    def make_state(k):
        params = ref.init(k, conf)
        return TrainState(params, init_adam_state(params, policy, opt))

    return cfg, model, make_train_step(model, policy, opt), make_state


class TrainCell:
    """One training cell: :meth:`setup` compiles the program's step once,
    :meth:`load` makes a seed's state and batches for it.  ``fault`` plants
    a fault in the timed path for the benchmark's own tests:
    ``"unchanged"`` (the step returns its state as it got it) or
    ``"half_batch"`` (the step sees the first half of the batch only)."""

    def __init__(self, conf: Dict, mix: Dict, lim: Dict,
                 fault: Optional[str] = None):
        self.conf, self.mix, self.lim = conf, mix, lim
        self.fault = fault
        self.ref = registry.reference(conf)
        self.ref_steps = int(lim["ref_steps"])
        self.opt = dict(mix["optimizer"])
        self._ref_fns = None

    # -- program side --------------------------------------------------------

    def setup(self) -> None:
        with program_env(self.mix):
            self._setup()

    def _setup(self) -> None:
        self.cfg, model, step_fn, make_state = program(self.conf, self.mix)
        cbmodel.check_layout(self.ref, self.conf, model)
        ref, conf, mix = self.ref, self.conf, self.mix
        self.init_params = jax.jit(lambda k: ref.init(k, conf))
        self.make_state = jax.jit(make_state)
        self.make_pool = jax.jit(lambda k: make_tokens(
            k, mix, self.cfg.vocab_size, mix["pool"]))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (mix["batch"], mix["seq"] + 1), jnp.int32)}
        with jax.profiler.TraceAnnotation("chipbench.compile"):
            self.step = jax.jit(self._plant(step_fn),
                                donate_argnums=(0,)).lower(
                jax.eval_shape(make_state, key), batch, key).compile()

    def load(self, seed: int) -> None:
        """The state and the pool of batches of ``seed``."""
        self.seed = seed
        self.wkey = cbmodel.seed_key(seed, _WEIGHTS)
        with program_env(self.mix), \
                jax.profiler.TraceAnnotation("chipbench.load"):
            self.state = self.make_state(self.wkey)
            pool = self.make_pool(cbmodel.seed_key(seed, _TOKENS))
            self.batches = [{"tokens": pool[i]}
                            for i in range(self.mix["pool"])]
            self.rng = cbmodel.seed_key(seed, _STEP_KEY)
        self.fed = 0

    def _plant(self, step_fn: Callable) -> Callable:
        if self.fault is None:
            return step_fn
        if self.fault == "unchanged":
            return lambda s, b, r: (s, step_fn(s, b, r)[1])
        if self.fault == "half_batch":
            half = self.mix["batch"] // 2
            return lambda s, b, r: step_fn(
                s, {"tokens": b["tokens"][:half]}, r)
        raise ValueError(f"unknown fault {self.fault!r}")

    def _call(self):
        """The window's own call and feed: the next batch of the pool."""
        batch = self.batches[self.fed % len(self.batches)]
        self.fed += 1
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            self.state, met = self.step(self.state, batch, self.rng)
        return met

    def first_steps(self) -> Readings:
        """Run the compared steps through the window's call; read the loss
        of each, the first gradient from the optimizer state after step one,
        and the parameters' change after the last."""
        if not hasattr(self, "_m1_norms"):
            sizes = [int(np.prod(p.shape))
                     for p in jax.tree_util.tree_leaves(self.state.params)]
            b1 = self.opt["b1"]
            self._m1_norms = jax.jit(lambda m1: [
                _decoded_norm(m, n, b1) for m, n in zip(
                    jax.tree_util.tree_leaves(
                        m1, is_leaf=lambda x: hasattr(x, "q")), sizes)])
        losses, g = [], None
        for k in range(self.ref_steps):
            met = self._call()
            losses.append(met["loss"])
            if k == 0:
                g = self._m1_norms(self.state.opt.m1)
        p0 = self.init_params(self.wkey)
        change = _change_norms(self.state.params, p0)
        names = _paths(p0)
        del p0
        return Readings([float(x) for x in losses],
                        dict(zip(names, map(float, g))),
                        dict(zip(names, map(float, change))))

    def window(self, seconds: float) -> Dict:
        """Drive the step for ``seconds`` of host time.  The host keeps
        ``AHEAD`` steps queued beyond the newest one it knows finished and
        reads nothing back until the window has closed, when the last step
        issued has finished; then the losses are checked."""
        losses = []
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                losses.append(self._call()["loss"])
                if len(losses) > AHEAD:
                    with jax.profiler.TraceAnnotation("chipbench.wait"):
                        losses[-1 - AHEAD].block_until_ready()
                if time.perf_counter() >= deadline:
                    break
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready((self.state, losses))
            t1 = time.perf_counter()
        failed = sum(not math.isfinite(float(x))
                     for x in jax.device_get(losses))
        steps = len(losses)
        tokens = steps * self.mix["batch"] * self.mix["seq"]
        return {"steps": steps, "failed": failed, "seconds": t1 - t0,
                "tokens": tokens}

    def free(self) -> None:
        """Drop the seed's state and batches (the compiled step stays)."""
        del self.state, self.batches

    # -- the plain reference -------------------------------------------------

    def _reference_fns(self):
        """The reference's jitted gradient (on the chip), clip and update
        (on the host's CPU), built once per cell."""
        if self._ref_fns is not None:
            return self._ref_fns
        ref, conf, opt = self.ref, self.conf, self.opt
        grad = jax.jit(jax.value_and_grad(lambda p, t: batch_loss(
            lambda pp, row: ref.seq_loss(pp, row, conf), p, t)))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def clip(g):
            cs = ref_adamw.clip_scale(g, opt["grad_clip"])
            g = jax.tree_util.tree_map(lambda x: x * cs, g)
            return g, [jnp.sqrt(jnp.sum(jnp.square(x)))
                       for x in jax.tree_util.tree_leaves(g)]

        @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
        def update(p, g, m, v, lr, c1, c2):
            out = jax.tree_util.tree_map_with_path(
                lambda path, a, b, c, d: ref_adamw.update(
                    a, b, c, d, lr, c1, c2, opt, ref_adamw.decays(
                        _name(path), a.shape, opt["decay"])), p, g, m, v)
            pick = lambda i: jax.tree_util.tree_map(
                lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
            return pick(0), pick(1), pick(2)

        self._ref_fns = (grad, clip, update)
        return self._ref_fns

    def reference(self) -> Readings:
        """The plain float32 reference over the same weights and batches.
        The gradient is taken on the chip, one sequence at a time; the
        optimizer's state lives on the host's CPU, where the update runs,
        so that only the weights and one gradient share the chip."""
        opt = self.opt
        cpu, dev = jax.devices("cpu")[0], jax.devices()[0]
        # every input committed to its device, so that each jitted
        # function compiles once for all steps
        pool = jax.device_put(self.make_pool(
            cbmodel.seed_key(self.seed, _TOKENS))[:self.ref_steps], dev)
        with jax.default_matmul_precision("highest"):
            grad, clip, update = self._reference_fns()
            p = jax.device_put(self.init_params(self.wkey), dev)
            names = _paths(p)
            p_host = jax.device_put(p, cpu)
            m = jax.tree_util.tree_map(jnp.zeros_like, p_host)
            v = jax.tree_util.tree_map(jnp.zeros_like, p_host)
            losses, g1 = [], None
            for k in range(1, self.ref_steps + 1):
                t = time.perf_counter()
                loss, g = grad(p, pool[k - 1])
                losses.append(float(loss))
                t = _say(f"reference gradient {k}", t)
                g = jax.device_put(g, cpu)
                del p
                g, gn = clip(g)
                if k == 1:
                    g1 = [float(x) for x in gn]
                lr, c1, c2 = ref_adamw.schedule(k, opt)
                p_host, m, v = update(p_host, g, m, v, lr, c1, c2)
                del g
                p = jax.device_put(p_host, dev)
                jax.block_until_ready(p)
                _say(f"reference update {k} (host)", t)
            del p_host, m, v
            p0 = jax.device_put(self.init_params(self.wkey), dev)
            change = [float(x) for x in _change_norms(p, p0)]
            del p, p0
        return Readings(losses, dict(zip(names, g1)),
                        dict(zip(names, change)))


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> Dict[str, float]:
    med = statistics.median(ref[k] for k in keep)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in keep}


def worst_leaves(prog: Readings, ref: Readings) -> Dict[str, str]:
    """The leaf that sets ``grad_gap`` and the one that sets
    ``change_gap`` (for the calibration's record)."""
    g = _leaf_gaps(prog.grad_norms, ref.grad_norms, sorted(ref.grad_norms))
    c = _leaf_gaps(prog.change_norms, ref.change_norms, _moving(ref))
    return {"grad_gap_leaf": max(g, key=g.get),
            "change_gap_leaf": max(c, key=c.get)}


def _moving(ref: Readings) -> List[str]:
    """Leaves whose reference gradient is not negligible."""
    leaves = sorted(ref.grad_norms)
    med = statistics.median(ref.grad_norms[k] for k in leaves)
    return [k for k in leaves
            if ref.grad_norms[k] >= NEGLIGIBLE_GRAD * med]


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers compared, from the two sets of readings."""
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf
                   for a, b in zip(prog.losses, ref.losses))
    grad = _leaf_gaps(prog.grad_norms, ref.grad_norms, sorted(ref.grad_norms))
    change = _leaf_gaps(prog.change_norms, ref.change_norms, _moving(ref))
    return {"loss_gap": loss_gap, "grad_gap": max(grad.values()),
            "change_gap": max(change.values())}


def verdict(numbers: Dict[str, float], lim: Dict, nonfinite: int = 0):
    """(correct, checks): each number compared beside its limit, with the
    count of non-finite losses in the window held to 0."""
    checks = {k: {"value": v, "limit": lim[k]} for k, v in numbers.items()}
    checks["window_nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_cell(conf: Dict, mix: Dict, lim: Dict, seed: int, seconds: float,
             watch, fault: Optional[str] = None) -> Dict:
    """Set up, read the first steps, measure the window inside ``watch``
    (a ``chipbench.trace.Window``), free the program, run the reference,
    compare.  Returns the pieces of the result line."""
    cell = TrainCell(conf, mix, lim, fault=fault)
    t = time.perf_counter()
    cell.setup()
    t = _say("setup (compile)", t)
    cell.load(seed)
    t = _say("setup (state, batches)", t)
    prog = cell.first_steps()
    setup_end = _say("first steps", t)
    watch.start()
    win = cell.window(seconds)
    watch.stop()
    if watch.trace:
        watch.hlo_text = cell.step.as_text()
    peak = jax.devices()[0].memory_stats() or {}
    cell.free()
    del cell.step
    t = time.perf_counter()
    numbers = compare(prog, cell.reference())
    _say("reference", t)
    correct, checks = verdict(numbers, lim, win["failed"])
    return {"correct": correct, "attempted": win["steps"],
            "failed": win["failed"], "window": win, "setup_end": setup_end,
            "memory_peak_bytes": int(peak.get("peak_bytes_in_use", 0)),
            "checks": checks,
            "e2e": {"train_tokens_per_s": win["tokens"] / win["seconds"]},
            "cell": cell}
