"""The plain reference's optimizer follows the weight decay rule that the
traffic file names, leaf by leaf."""
import jax
import pytest

from chipbench import registry
from chipbench.reference import adamw, gpt2

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
