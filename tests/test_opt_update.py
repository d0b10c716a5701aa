"""Fused 8-bit AdamW kernel vs the reference decode->update->encode loop
(kernels/opt_update.py + the bucketed path in optim/adamw.py)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qadam
from repro.core.qconfig import (Granularity, QuantRecipe, QuantSpec,
                                parse_recipe)
from repro.optim import (OptConfig, adamw_update, fused_adam_enabled,
                         init_adam_state, opt_path_desc)

KEY = jax.random.PRNGKey(3)
#: Both moments blockwise (the fused contract); m2 is the beyond-paper
#: asymmetric sqrt-domain codec, so the kernel's asym + sqrt branches run.
RECIPE = parse_recipe("m1:8c-b128,m2:8c-asym-b128-sqrt")


def _params():
    return {
        "w_ragged": jax.random.normal(KEY, (130, 70)),       # 9100 % 128 != 0
        "w_aligned": jax.random.normal(jax.random.fold_in(KEY, 1), (64, 128)),
        "bias": jax.random.normal(jax.random.fold_in(KEY, 2), (128,)),
        "tiny": jax.random.normal(jax.random.fold_in(KEY, 3), (8, 8)),
    }


def _grads(params, i):
    return jax.tree_util.tree_map(
        lambda p: 0.1 * jax.random.normal(jax.random.fold_in(KEY, 100 + i),
                                          p.shape), params)


def _run(monkeypatch, fused: bool, storage: str = "int", steps: int = 3,
         recipe=RECIPE, tile: str = "8"):
    monkeypatch.setenv("REPRO_FUSED_ADAM", "1" if fused else "0")
    monkeypatch.setenv("REPRO_OPT_BLOCK", tile)
    cfg = OptConfig(lr=1e-2, warmup_steps=0, total_steps=10**6,
                    weight_decay=0.1, grad_clip=1.0, state_storage=storage)
    p = _params()
    st = init_adam_state(p, recipe, cfg)
    stats = {}
    for i in range(steps):
        p, st, stats = adamw_update(p, _grads(p, i), st, cfg, recipe)
    return p, st, stats


def test_fused_matches_loop_int_storage(monkeypatch):
    """Parity contract: payloads within one codec bin (fp fusion/FMA ulps can
    flip a round at a bin boundary), scales/zeros to float rounding, params
    well inside one lr of the reference trajectory."""
    p_l, st_l, stats_l = _run(monkeypatch, fused=False)
    p_f, st_f, stats_f = _run(monkeypatch, fused=True)
    for name in p_l:
        dp = float(jnp.max(jnp.abs(p_l[name] - p_f[name])))
        assert dp < 1e-3, (name, dp)                    # lr=1e-2 >> drift
    for tree_l, tree_f in ((st_l.m1, st_f.m1), (st_l.m2, st_f.m2)):
        for name in ("w_ragged", "w_aligned"):
            ml, mf = tree_l[name], tree_f[name]
            assert isinstance(ml, qadam.QState) and isinstance(mf, qadam.QState)
            dq = int(jnp.max(jnp.abs(ml.q.astype(jnp.int32)
                                     - mf.q.astype(jnp.int32))))
            assert dq <= 1, (name, dq)
            np.testing.assert_allclose(np.asarray(ml.scale),
                                       np.asarray(mf.scale), rtol=1e-5)
            assert int(jnp.max(jnp.abs(ml.zero - mf.zero))) <= 1, name
        # non-quantizable leaves take the loop on both sides: bit-identical
        np.testing.assert_array_equal(np.asarray(tree_l["bias"]),
                                      np.asarray(tree_f["bias"]))
        np.testing.assert_array_equal(np.asarray(tree_l["tiny"]),
                                      np.asarray(tree_f["tiny"]))
    np.testing.assert_allclose(float(stats_l["update_norm"]),
                               float(stats_f["update_norm"]), rtol=1e-3)


def test_moment_bytes_and_layout_unchanged(monkeypatch):
    """The fused path must not change what is stored: same QState shapes
    (the blockwise codec layout) and the same byte count as the loop."""
    _, st_l, _ = _run(monkeypatch, fused=False, steps=1)
    _, st_f, _ = _run(monkeypatch, fused=True, steps=1)

    def total(tree):
        return sum(qadam.state_nbytes(l) for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, qadam.QState)))

    assert total(st_f.m1) == total(st_l.m1)
    assert total(st_f.m2) == total(st_l.m2)
    for name in ("w_ragged", "w_aligned"):
        q_shape, s_shape = qadam.blockwise_state_shapes(
            _params()[name].shape, RECIPE.adam_m1)
        assert st_f.m1[name].q.shape == q_shape
        assert st_f.m1[name].scale.shape == s_shape
        assert st_f.m1[name].q.dtype == jnp.int8


@pytest.mark.parametrize("storage", ["fake", "fp"])
def test_auto_fallback_for_non_int_storage(monkeypatch, storage):
    """REPRO_FUSED_ADAM=1 with fp/fake storage must fall back to the loop
    bit-for-bit (there are no int payloads to stream)."""
    recipe = None if storage == "fp" else RECIPE
    st_storage = "fake"
    p_l, st_l, _ = _run(monkeypatch, fused=False, storage=st_storage,
                        recipe=recipe, steps=2)
    p_f, st_f, _ = _run(monkeypatch, fused=True, storage=st_storage,
                        recipe=recipe, steps=2)
    for name in p_l:
        np.testing.assert_array_equal(np.asarray(p_l[name]),
                                      np.asarray(p_f[name]))


def test_ragged_bucket_padding_is_safe(monkeypatch):
    """Bucket rows are padded to the kernel tile with 0 payloads and 0
    scales; the encode guard (maximum(.., 1e-12)) must keep every output
    finite -- no 0/0 from padding lanes -- and tail-padded leaves must
    round-trip exactly like the loop."""
    monkeypatch.setenv("REPRO_FUSED_ADAM", "1")
    # tile of 16 rows over 72+64=136 blocks -> 8 fully-padded bucket rows
    monkeypatch.setenv("REPRO_OPT_BLOCK", "16")
    cfg = OptConfig(lr=1e-2, warmup_steps=0, total_steps=10**6,
                    state_storage="int")
    p = {"w": jax.random.normal(KEY, (130, 70)),
         "w2": jax.random.normal(jax.random.fold_in(KEY, 7), (64, 128))}
    st = init_adam_state(p, RECIPE, cfg)
    p2, st2, stats = adamw_update(p, _grads(p, 0), st, cfg, RECIPE)
    for leaf in jax.tree_util.tree_leaves((p2, st2)):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    assert np.isfinite(float(stats["update_norm"]))
    # fresh scales stay nonzero (guarded), decodable without NaN
    assert float(jnp.min(st2.m1["w"].scale)) > 0.0
    m1 = qadam.decode(st2.m1["w"], RECIPE.adam_m1, p["w"].shape)
    assert np.isfinite(np.asarray(m1)).all()


def test_tile_size_does_not_change_results(monkeypatch):
    """REPRO_OPT_BLOCK only partitions rows across grid steps; every scale
    reduction is per-row, so results are invariant to the tile choice."""
    _, st_a, _ = _run(monkeypatch, fused=True, steps=2, tile="8")
    _, st_b, _ = _run(monkeypatch, fused=True, steps=2, tile="32")
    np.testing.assert_array_equal(np.asarray(st_a.m1["w_ragged"].q),
                                  np.asarray(st_b.m1["w_ragged"].q))
    np.testing.assert_array_equal(np.asarray(st_a.m2["w_aligned"].q),
                                  np.asarray(st_b.m2["w_aligned"].q))


def test_update_norm_is_real(monkeypatch):
    """The update_norm stat (hardcoded 0 before this PR) equals the l2 norm
    of the applied parameter deltas on both paths."""
    for fused in (False, True):
        monkeypatch.setenv("REPRO_FUSED_ADAM", "1" if fused else "0")
        monkeypatch.setenv("REPRO_OPT_BLOCK", "8")
        cfg = OptConfig(lr=1e-2, warmup_steps=0, total_steps=10**6,
                        weight_decay=0.0, grad_clip=1e9, state_storage="int")
        p = _params()
        st = init_adam_state(p, RECIPE, cfg)
        p2, _, stats = adamw_update(p, _grads(p, 0), st, cfg, RECIPE)
        want = jnp.sqrt(sum(
            jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(jax.tree_util.tree_leaves(p2),
                            jax.tree_util.tree_leaves(p))))
        np.testing.assert_allclose(float(stats["update_norm"]), float(want),
                                   rtol=1e-4)
        assert float(stats["update_norm"]) > 0.0


def test_eligibility_and_path_desc():
    blk = QuantSpec(8, Granularity.PER_CHANNEL, block_size=128)
    assert qadam.fused_spec_eligible(blk)
    assert not qadam.fused_spec_eligible(None)
    assert not qadam.fused_spec_eligible(
        QuantSpec(8, Granularity.PER_CHANNEL))               # no blocking
    assert not qadam.fused_spec_eligible(
        QuantSpec(16, Granularity.PER_CHANNEL, block_size=128))  # int16
    from repro.core.qconfig import RoundMode
    assert not qadam.fused_spec_eligible(
        QuantSpec(8, Granularity.PER_CHANNEL, block_size=128,
                  round_mode=RoundMode.STOCHASTIC))
    assert qadam.fused_pair_eligible(RECIPE.adam_m1, RECIPE.adam_m2)
    assert not qadam.fused_pair_eligible(
        blk, QuantSpec(8, Granularity.PER_CHANNEL, block_size=64))  # mixed bs

    cfg_int = OptConfig(state_storage="int")
    cfg_fake = OptConfig(state_storage="fake")
    os.environ["REPRO_FUSED_ADAM"] = "1"
    try:
        assert opt_path_desc(RECIPE, cfg_int) == "int8-fused(b128)"
        assert opt_path_desc(RECIPE, cfg_fake) == "fake-loop"
        assert opt_path_desc(None, cfg_int) == "fp-loop"
        assert opt_path_desc(
            QuantRecipe(adam_m1=QuantSpec(8, Granularity.PER_CHANNEL)),
            cfg_int) == "int8-loop"
        assert fused_adam_enabled()
        os.environ["REPRO_FUSED_ADAM"] = "0"
        assert opt_path_desc(RECIPE, cfg_int) == "int8-loop"
    finally:
        os.environ.pop("REPRO_FUSED_ADAM", None)


def test_train_path_summary_opt_segment():
    from repro.train.step import train_path_summary
    cfg = OptConfig(state_storage="int")
    os.environ["REPRO_FUSED_ADAM"] = "1"
    try:
        s = train_path_summary("*=w8c+a8t,m1:8c-b128,m2:8c-asym-b128-sqrt"
                               .replace(",", "+"), opt_cfg=cfg)
    finally:
        os.environ.pop("REPRO_FUSED_ADAM", None)
    assert "opt=int8-fused(b128)" in s
    assert "opt=" not in train_path_summary(None)


def test_state_shardings_bucketed_layout(monkeypatch):
    """QState moments get leading-block-dim shardings (payload AND sidecars)
    instead of blanket replication."""
    from jax.sharding import NamedSharding
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.parallel.sharding import make_rules
    from repro.train.step import init_train_state, state_shardings
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, "train")
    cfg = get_smoke_config("gpt2-small")
    model = build_model(cfg)
    opt = OptConfig(state_storage="int")
    state = jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0),
                                 "*=w8c+a8t+m1:8c-b128+m2:8c-b128", opt))
    sh = state_shardings(rules, model, state)
    m1_leaves = [l for l in jax.tree_util.tree_leaves(
        sh.opt.m1, is_leaf=lambda x: isinstance(x, qadam.QState))
        if isinstance(l, qadam.QState)]
    assert m1_leaves, "expected QState moments under the int recipe"
    for qs in m1_leaves:
        assert isinstance(qs.q, NamedSharding)
        assert isinstance(qs.scale, NamedSharding)


def test_loss_curve_smoke_fused_vs_loop(monkeypatch):
    """20 training steps of the gpt2-small smoke config with int8-stored
    moments: the fused kernel tracks the reference loop's loss curve."""
    from repro.configs import get_smoke_config
    from repro.data import Loader, SyntheticCorpus
    from repro.models import build_model
    from repro.train import init_train_state, make_train_step

    def train(fused):
        monkeypatch.setenv("REPRO_FUSED_ADAM", "1" if fused else "0")
        monkeypatch.setenv("REPRO_OPT_BLOCK", "64")
        cfg = get_smoke_config("gpt2-small")
        model = build_model(cfg)
        corpus = SyntheticCorpus(cfg.vocab_size, seed=7)
        loader = Loader(corpus, cfg, batch_size=2, seq_len=32)
        policy = "*=w8c+a8t+m1:8c-b128+m2:8c-asym-b128-sqrt"
        opt = OptConfig(lr=3e-3, warmup_steps=2, total_steps=20,
                        state_storage="int")
        state = init_train_state(model, jax.random.PRNGKey(0), policy, opt)
        step = jax.jit(make_train_step(model, policy, opt))
        ces = []
        for i, batch in zip(range(20), loader):
            state, m = step(state, batch, None)
            ces.append(float(m["ce"]))
        return ces

    ce_loop = train(False)
    ce_fused = train(True)
    assert all(np.isfinite(ce_fused)), ce_fused
    assert ce_fused[-1] < ce_fused[0], ce_fused        # it actually learns
    # same trajectory up to codec-ulp drift
    assert abs(ce_fused[-1] - ce_loop[-1]) < 0.05 * abs(ce_loop[-1]), \
        (ce_loop[-1], ce_fused[-1])
