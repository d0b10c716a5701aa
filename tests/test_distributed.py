"""Distributed-path tests: run in subprocesses with 8 forced host devices
(the main test process must keep the single real CPU device); the runner is
the shared ``forced8_run`` conftest fixture."""


def test_mini_dryrun_train_compiles_on_mesh(forced8_run):
    """Smoke configs lower+compile+run on a (2,4) data x model mesh; the
    sharded loss equals the single-device loss."""
    print(forced8_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.core import paper_recipe
        from repro.optim import OptConfig
        from repro.parallel.sharding import make_rules
        from repro.train.step import (init_train_state, make_train_step,
                                      state_shardings, batch_shardings)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ("llama3-8b", "mamba2-130m"):
            cfg = get_smoke_config(arch)
            model = build_model(cfg)
            rules = make_rules(mesh, "train", cfg=cfg)
            recipe = paper_recipe()
            opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
            state = init_train_state(model, jax.random.PRNGKey(0), recipe, opt)
            st_sh = state_shardings(rules, model, jax.eval_shape(lambda: state))
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)}
            b_sh = batch_shardings(rules, jax.eval_shape(lambda: batch))
            step_sh = jax.jit(make_train_step(model, recipe, opt, rules=rules),
                              in_shardings=(st_sh, b_sh, None),
                              out_shardings=(st_sh, None))
            with mesh:
                new_state, metrics = step_sh(state, batch, None)
            step_1d = jax.jit(make_train_step(model, recipe, opt))
            _, metrics_1d = step_1d(state, batch, None)
            d = abs(float(metrics["ce"]) - float(metrics_1d["ce"]))
            print(arch, float(metrics["ce"]), d)
            assert d < 2e-2, (arch, d)
        print("MESH-TRAIN-OK")
    """))


def test_moe_shard_map_modes_match_local(forced8_run):
    """a2a EP / masked EP / ff-sharded outputs == single-device dispatch."""
    print(forced8_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.moe import moe_apply, moe_spec
        from repro.models.common import init_from_spec
        from repro.parallel.sharding import make_rules
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for arch, s in (("phi3.5-moe-42b-a6.6b", 8),    # E=4 % tp=4 -> a2a
                        ("phi3.5-moe-42b-a6.6b", 3),    # s%tp!=0 -> masked
                        ("granite-moe-3b-a800m", 8)):   # E=8, ff d32%4 -> a2a
            cfg = get_smoke_config(arch)
            params = init_from_spec(jax.random.PRNGKey(0), moe_spec(cfg))
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (4, s, cfg.d_model)) * 0.5
            rules = make_rules(mesh, "train", cfg=cfg)
            with mesh:
                y_sh, aux_sh, z_sh = jax.jit(
                    lambda p, xx: moe_apply(p, xx, cfg, policy=None,
                                            rules=rules))(params, x)
            y_loc, aux_loc, z_loc = moe_apply(params, x, cfg, policy=None,
                                              rules=None)
            err = float(jnp.max(jnp.abs(y_sh - y_loc)))
            rel = err / (float(jnp.max(jnp.abs(y_loc))) + 1e-9)
            print(arch, s, "rel", rel)
            assert rel < 0.05, (arch, s, rel)
        print("MOE-MODES-OK")
    """))


def test_compressed_allreduce_close_to_exact(forced8_run):
    print(forced8_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compress import int8_psum_flat
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        v = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))

        def body(vb):
            # each rank contributes its own row; compressed psum of the sum
            mine = vb[0]
            return int8_psum_flat(mine, "d")[None, :]

        with mesh:
            got = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("d", None),
                out_specs=P("d", None), check_vma=False))(v)
        # every rank's compressed sum approximates the true sum of all rows
        want = jnp.sum(v, axis=0)
        got0 = got[0]
        rel = float(jnp.linalg.norm(got0 - want) / jnp.linalg.norm(want))
        print("rel", rel)
        assert rel < 0.02, rel
        print("COMPRESS-OK")
    """))


def test_compressed_allreduce_tree_matches_fp_psum(forced8_run):
    """compressed_allreduce over a gradient pytree vs the exact fp psum on a
    1-D mesh: same tree structure, <2% relative error per leaf, and the
    ragged leaf exercises the wire padding."""
    print(forced8_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compress import compressed_allreduce
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        k = jax.random.PRNGKey(0)
        tree = {"w": jax.random.normal(k, (64, 96)),
                "b": jax.random.normal(jax.random.fold_in(k, 1), (131,))}

        got = compressed_allreduce(tree, mesh, "d")

        def fp_body(t):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, "d"), t)

        with mesh:
            want = jax.jit(jax.shard_map(fp_body, mesh=mesh, in_specs=P(),
                                         out_specs=P(), check_vma=False))(tree)
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(tree)
        for name in tree:
            g, wnt = got[name], want[name]
            assert g.shape == tree[name].shape, (name, g.shape)
            rel = float(jnp.linalg.norm(g - wnt) / jnp.linalg.norm(wnt))
            print(name, "rel", rel)
            assert rel < 0.02, (name, rel)
        print("COMPRESS-TREE-OK")
    """))


def test_serve_prefill_decode_sharded(forced8_run):
    print(forced8_run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.parallel.sharding import make_rules
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("llama3-8b")
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rules = make_rules(mesh, "serve", cfg=cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                  cfg.vocab_size)
        with mesh:
            logits, st = jax.jit(lambda p, b: model.prefill(
                p, b, rules=rules, max_seq=20))(params, {"tokens": toks[:, :16]})
            step_logits, _ = jax.jit(lambda p, s, t, pos: model.decode(
                p, s, t, pos, rules=rules))(params, st, toks[:, 16:17],
                                            jnp.int32(16))
        full_logits, _ = model.prefill(params, {"tokens": toks}, max_seq=20)
        err = float(jnp.max(jnp.abs(step_logits - full_logits)))
        print("err", err)
        assert err < 0.2, err
        print("SERVE-SHARDED-OK")
    """))
