"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from chipbench import registry

ROOT = registry.ROOT


def _run(cwd, workload="gpt2s-pretrain-int8"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_a_clear_message():
    out = _run(ROOT)
    assert out.returncode == 2
    assert "no TPU" in out.stderr and "nothing was run" in out.stderr
    assert out.stdout.strip() == ""


def test_unknown_workload_exits_nonzero():
    out = _run(ROOT, workload="no-such-cell")
    assert out.returncode == 2 and "no workload" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
