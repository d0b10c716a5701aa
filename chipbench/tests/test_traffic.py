"""Traffic is a pure function of the seed, inside its bounds, and every
cell of BENCHMARK.json resolves by name to its files."""
import sys

import numpy as np
import pytest

from chipbench import registry

BENCH = registry.benchmark()
TRAIN_MIXES = sorted({w["traffic"] for w in BENCH["workloads"]
                      if registry.traffic(w["traffic"])["kind"] == "train"})


@pytest.fixture(scope="module", autouse=True)
def program_on_path():
    sys.path.insert(0, str(registry.ROOT / "src"))


@pytest.mark.parametrize("mix_name", TRAIN_MIXES)
def test_train_tokens_deterministic_and_bounded(mix_name):
    from chipbench.model import seed_key
    from chipbench.train import make_tokens
    mix = dict(registry.traffic(mix_name), batch=3, seq=64)
    big = 2 ** 32 + 12345                    # more than 32 bits
    a = np.asarray(make_tokens(seed_key(big, 2), mix, 1000, 2))
    b = np.asarray(make_tokens(seed_key(big, 2), mix, 1000, 2))
    c = np.asarray(make_tokens(seed_key(big + 1, 2), mix, 1000, 2))
    assert a.shape == (2, 3, 65) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 0 and a.max() < 1000
    rows = a.reshape(-1, 65)
    assert len({r.tobytes() for r in rows}) == len(rows)   # rows differ


def test_seed_out_of_range_is_refused():
    from chipbench.model import seed_key
    with pytest.raises(registry.BenchError):
        seed_key(-1, 0)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_by_name(w):
    conf = registry.config(BENCH, w["config"])
    mix = registry.traffic(w["traffic"])
    lim = registry.limits(w["name"])
    registry.reference(conf)
    assert mix["kind"] == "train"
    assert (registry.HERE / f"{mix['kind']}.py").is_file()
    for kind in ("end_to_end", "per_layer"):
        for m in registry.metrics_for(BENCH, w["name"], kind):
            if kind == "per_layer":
                assert callable(registry.metric_reader(m["name"]).read)
    names = {m["name"] for m in registry.metrics_for(BENCH, w["name"],
                                                     "end_to_end")}
    assert "setup_s" in names and len(names) >= 2
    for m in registry.metrics_for(BENCH, w["name"], "per_layer"):
        assert m["moves"] in names
    assert all(k in lim for k in ("ref_steps", "loss_gap", "grad_gap",
                                  "change_gap", "control_policy"))

