"""The plain references: each configuration's module brings what the
harness asks of it, its tiny cut keeps the program's layout, and the
optimizer follows the weight decay rule that the traffic file names, leaf
by leaf."""
import sys

import jax
import pytest

from chipbench import registry
from chipbench.reference import adamw, gpt2

CONFIGS = [c["name"] for c in registry.benchmark()["configs"]]

#: (leaf, decays under the program's rule, decays under nanoGPT's rule)
LEAVES = [("embed", True, True), ("pos_embed", True, True),
          ("blocks/attn/wq", True, True), ("blocks/mlp/b_fc1", True, False),
          ("blocks/ln1/scale", True, False), ("final_norm/scale", False,
                                                False)]


@pytest.mark.parametrize("rule,col", [("every stored leaf of rank >= 2", 1),
                                      ("matrices only", 2)])
def test_decay_rule_by_leaf(rule, col):
    conf = registry.config(registry.benchmark(), "gpt2-small")
    tree = jax.eval_shape(lambda k: gpt2.init(k, conf), jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    shapes = {"/".join(str(getattr(k, "key", k)) for k in path): x.shape
              for path, x in flat}
    for row in LEAVES:
        assert adamw.decays(row[0], shapes[row[0]], rule) is row[col], row


def test_every_traffic_file_names_a_known_rule():
    for path in (registry.HERE / "traffic").glob("*.json"):
        mix = registry.traffic(path.stem)
        assert mix["optimizer"]["decay"] in adamw.DECAY_RULES
    with pytest.raises(ValueError):
        adamw.decays("embed", (4, 4), "no such rule")


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_brings_what_the_harness_asks(name):
    conf = registry.config(registry.benchmark(), name)
    ref = registry.reference(conf)
    for fn in ("init", "seq_loss", "train_ops_per_token", "tiny"):
        assert callable(getattr(ref, fn, None)), fn
    params = jax.eval_shape(lambda k: ref.init(k, conf),
                            jax.random.PRNGKey(0))
    block_weights = sum(x.size for x in
                        jax.tree_util.tree_leaves(params["blocks"]))
    ops = ref.train_ops_per_token(conf, 1024)
    assert set(ops) == {"linears", "other"}
    assert 0 < ops["linears"] <= 6 * block_weights
    assert ops["other"] > 0


@pytest.mark.parametrize("name", CONFIGS)
def test_tiny_cut_keeps_the_program_layout(name):
    sys.path.insert(0, str(registry.ROOT / "src"))
    from repro.models import build_model
    from chipbench import model as cbmodel
    conf = registry.config(registry.benchmark(), name)
    small = registry.reference(conf).tiny(conf, 64)
    cfg = cbmodel.program_config(small)
    assert cfg.d_model == 64 and cfg.n_layers == 2
    cbmodel.check_layout(registry.reference(small), small,
                         build_model(cfg))
