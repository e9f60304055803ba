"""Process set-up helpers (`repro.launch.runtime`) and the benchmarks'
use of them: one compile-cache rule, CPU-only flags only on the CPU."""
import os
import pathlib
import re

import jax
import pytest

from repro.launch import runtime

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_env_dir_and_sets_no_other(
        monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("platforms,applied", [
    ("cpu", True), ("cpu,tpu", True), ("tpu", False), ("", False)])
def test_cpu_xla_flags_only_on_the_cpu(monkeypatch, platforms, applied):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=true")
    got = runtime.add_cpu_xla_flags(
        "--xla_force_host_platform_device_count=4",
        runtime.SINGLE_THREAD_EIGEN)
    assert got is applied
    flags = os.environ["XLA_FLAGS"]
    assert ("--xla_force_host_platform_device_count=4" in flags) is applied
    # the caller's own choice of a flag is never overridden
    assert "--xla_cpu_multi_thread_eigen=false" not in flags


def test_benchmarks_pin_no_platform_and_set_no_raw_xla_flags():
    """Children inherit the platform, and host-only XLA flags go through
    `add_cpu_xla_flags`, so no cell silently times the CPU on a chip."""
    for path in sorted((REPO / "benchmarks").glob("*.py")):
        src = path.read_text()
        assert not re.search(r"""["']JAX_PLATFORMS["']\s*[],:)]""",
                             src), path.name
        assert not re.search(r"""environ\[["']XLA_FLAGS["']\]\s*=""",
                             src), path.name
