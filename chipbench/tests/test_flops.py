"""Operation counts against hand figures, and the peak table's rules."""
import json

import pytest

from chipbench import flops, peaks, registry
from chipbench.trace import Call


def _conf(name):
    return registry.config(registry.benchmark(), name)


def test_gpt2_small_ops_per_token():
    # 6 x 84,934,656 block weights; 6 x 768 x 50257 (tied head)
    # + 12 x 12 x 1024 x 12 x 64 (attention)
    ops = flops.train_ops_per_token(_conf("gpt2-small"), 1024)
    assert ops == {"linears": 509_607_936, "other": 344_830_464}


def test_yi_cut4_ops_per_token():
    # 6 x 4 x 173,015,040 block weights; 6 x 4096 x 16000 (untied head)
    # + 12 x 4 x 4096 x 32 x 128 (attention)
    ops = flops.train_ops_per_token(_conf("yi-6b-cut4"), 4096)
    assert ops == {"linears": 4_152_360_960, "other": 1_198_522_368}


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.least_time(1e12, 1e9, "cpu", "bf16")


def test_v5e_peaks_are_the_published_ones():
    pk = peaks.peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes"]) == (
        197e12, 393e12, 819e9)


@pytest.mark.parametrize("kernel,ops0,ops1", [
    ("_int8_matmul_kernel", ("s8", (8192, 768)), ("s8", (768, 3072))),
    ("_int8_matmul_nt_kernel", ("bf16", (8192, 3072)), ("s8", (768, 3072))),
    ("_int8_matmul_tn_kernel", ("s8", (8192, 768)), ("bf16", (8192, 3072))),
    ("_adamw_kernel", ("f32", (8,)), ("f32", (1024, 128))),
])
def test_share_is_100_at_the_bound(kernel, ops0, ops1):
    call = Call(kernel, (("bf16", (8192, 3072)),), (ops0, ops1))
    least = flops.kernel_least_time(call, "TPU v5 lite")
    assert least > 0
    assert peaks.share(least, least) == pytest.approx(100.0)
    assert peaks.share(least, 2 * least) == pytest.approx(50.0)


def test_matmul_ops_from_shapes():
    fwd = Call("_int8_matmul_kernel", (("bf16", (256, 384)),),
               (("s8", (256, 128)), ("s8", (128, 384))))
    nt = Call("_int8_matmul_nt_kernel", (("bf16", (256, 128)),),
              (("bf16", (256, 384)), ("s8", (128, 384))))
    tn = Call("_int8_matmul_tn_kernel", (("f32", (128, 384)),),
              (("s8", (256, 128)), ("bf16", (256, 384))))
    for c in (fwd, nt, tn):
        assert registry.kernel_ops(c.kernel).ops(c) == 2 * 256 * 128 * 384
    assert fwd.nbytes == 256 * 128 + 128 * 384 + 2 * 256 * 384


def test_unknown_kernel_names_its_missing_file():
    call = Call("_no_such_kernel", (("bf16", (8, 128)),),
                (("s8", (8, 128)), ("s8", (128, 128))))
    with pytest.raises(registry.BenchError,
                       match="kernel_ops/_no_such_kernel.py"):
        flops.kernel_least_time(call, "TPU v5 lite")


def test_configs_are_json_with_their_source():
    bench = registry.benchmark()
    for c in bench["configs"]:
        with open(registry.ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
