"""A configuration unlike the benchmark's dense ones is admitted by new
files and entries alone.

In a copy of the benchmark, the test adds the files of
``data/tiny-moe.json`` (sparse experts with shared experts; query/key
heads wider than value heads; its own scope names), its reference
``data/tiny_moe.py`` and the operation count of a new kernel,
``data/_grouped_int8_matmul_kernel.py``, with one entry in
``BENCHMARK.json``; no module that is there changes.  The copy then
counts the configuration's work, classifies its scopes and counts the
kernel, in a process of its own.
"""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import registry

DATA = registry.HERE / "tests" / "data"

PATHS = {
    "jit(train_step)/jvp(layers)/while/body/moe/router/dot_general":
        ["router", "forward"],
    "jit(train_step)/transpose(jvp(layers))/while/body/dispatch/gather":
        ["dispatch", "backward"],
    "jit(train_step)/jvp(layers)/while/body/experts/linear/qdq/round":
        ["qdq", "forward"],
    "jit(train_step)/jvp(layers)/while/body/experts/silu":
        ["experts", "forward"],
}

SCRIPT = """
import json, sys
import jax
from chipbench import flops, registry, scopes
from chipbench.trace import Call

bench = registry.benchmark()
conf = registry.config(bench, "tiny-moe")
ref = registry.reference(conf)
call = Call("_grouped_int8_matmul_kernel", (("bf16", (256, 32)),),
            (("s8", (256, 64)), ("s8", (8, 64, 32)), ("s32", (8,))))
small = ref.tiny(conf, 32)
params = ref.init(jax.random.PRNGKey(0), small)
row = jax.random.randint(jax.random.PRNGKey(1), (17,), 0,
                         small["vocab_size"])
print(json.dumps({
    "reference": ref.__file__,
    "ops": flops.train_ops_per_token(conf, 128),
    "block_weights": sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: ref.init(k, conf),
                       jax.random.PRNGKey(0))["blocks"])),
    "classes": {p: scopes.classify(p, conf["scopes"])
                for p in json.loads(sys.argv[1])},
    "kernel_ops": registry.kernel_ops(call.kernel).ops(call),
    "kernel_least_s": flops.kernel_least_time(call, "TPU v5 lite"),
    "tiny_loss": float(ref.seq_loss(params, row, small)),
}))
"""


@pytest.fixture(scope="module")
def admitted(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(registry.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = registry.benchmark()
    bench["configs"].append({
        "name": "tiny-moe", "source": "chipbench/tests/data/tiny-moe.json",
        "file": "chipbench/configs/tiny-moe.json", "reduced": [],
        "why": "sparse and shared experts, unequal q.k and v heads"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for name, into in [("tiny-moe.json", "configs"),
                       ("tiny_moe.py", "reference"),
                       ("_grouped_int8_matmul_kernel.py", "kernel_ops")]:
        shutil.copy(DATA / name, root / "chipbench" / into / name)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT, json.dumps(
        list(PATHS))], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return root, json.loads(out.stdout.strip().splitlines()[-1])


def test_reference_comes_from_the_new_file(admitted):
    root, got = admitted
    assert got["reference"] == str(root / "chipbench" / "reference" /
                                   "tiny_moe.py")
    assert math.isfinite(got["tiny_loss"]) and got["tiny_loss"] > 0


def test_count_equals_the_hand_figure(admitted):
    # a layer: wq, wk 64 x 96; wv, wo 64 x 64; 2 of 8 routed experts and
    # the shared pair, each 3 x 64 x 32 and 3 x 64 x 64:
    # 2 x 6,144 + 2 x 4,096 + 12,288 + 12,288 = 45,056; 2 layers, 6 each
    # other: head 6 x 64 x 300 = 115,200; router 6 x 2 x 64 x 8 = 6,144;
    # attention 6 x 2 x 128 x 4 x (24 + 16) = 245,760
    _, got = admitted
    assert got["ops"] == {"linears": 6 * 2 * 45_056,
                          "other": 115_200 + 6_144 + 245_760}
    assert got["ops"]["linears"] <= 6 * got["block_weights"]


def test_its_scope_names_classify(admitted):
    _, got = admitted
    assert got["classes"] == PATHS


def test_its_kernel_is_counted(admitted):
    _, got = admitted
    ops = 2 * 256 * 64 * 32
    nbytes = 256 * 64 + 8 * 64 * 32 + 4 * 8 + 2 * 256 * 32
    assert got["kernel_ops"] == ops
    assert got["kernel_least_s"] == max(ops / 393e12, nbytes / 819e9)
