"""Launcher plumbing: the argv-driven main, Auto-axis meshes, and where the
persistent compilation cache goes."""
import pathlib

import jax
import pytest
from jax.sharding import AxisType

import repro.launch.train as launch_train
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process-wide cache directory after a test changes it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == jax.config.jax_compilation_cache_dir
    assert pathlib.Path(path) == REPO / ".jax_cache"
    assert compile_cache.setup_compile_cache() == path       # stable
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_meshes_have_auto_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert make_host_mesh().axis_types == (AxisType.Auto, AxisType.Auto)


def test_main_takes_argv(monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(launch_train, "setup_compile_cache",
                        lambda: calls.append(1))
    launch_train.main(["--arch", "gpt2-small", "--smoke", "--steps", "2",
                       "--batch", "2", "--seq", "16"])
    out = capsys.readouterr().out
    assert calls == [1]
    assert "train-path: " in out and "opt=fp-loop" in out
    assert "step     1  ce=" in out
