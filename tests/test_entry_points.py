"""Entry-point plumbing: where the persistent compilation cache goes, and
the scaling benchmark's refusal to start children from a process that
holds an accelerator."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_cache_goes_to_checkout_when_unset(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_cache_env_var_decides(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # untouched


def test_cache_dir_is_gitignored():
    lines = (ROOT / ".gitignore").read_text().split()
    assert "/.jax_cache/" in lines


def test_bench_scaling_refuses_off_cpu(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    from benchmarks import bench_scaling
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="refuses to run"):
        bench_scaling.run(device_counts=(1,))
