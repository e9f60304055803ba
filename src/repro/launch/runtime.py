"""Process set-up shared by every entry point: compile cache, CPU-only flags.

`enable_compile_cache` is the first call of `chip_smoke.py`, of every
script under `examples/` and of every benchmark's `main`; nothing else in
the repo chooses a cache directory. `add_cpu_xla_flags` is the one place a
host-only XLA flag (virtual CPU devices, Eigen threading) is set, and it
sets them only when `JAX_PLATFORMS` selects the CPU, so a run on the chip
never inherits a CPU rehearsal's configuration.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache: a fixed path, so every process of every run on one
# checkout finds the programs the previous ones compiled (.gitignore lists it)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
# Eigen's intra-op thread pool thrashing a small host's cores is the main
# run-to-run jitter of the CPU serving benchmarks
SINGLE_THREAD_EIGEN = "--xla_cpu_multi_thread_eigen=false"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is used as is (JAX reads it
    itself) and no other directory is set. Otherwise the cache lives at
    the checkout's `.jax_cache`.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def add_cpu_xla_flags(*flags: str) -> bool:
    """Append host-only `XLA_FLAGS` before JAX creates its backends.

    Applied only when `JAX_PLATFORMS` makes the host CPU JAX's first
    platform; a flag whose name the caller already put in `XLA_FLAGS` is
    left as the caller set it. Returns whether the flags were applied.
    """
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if first.strip().lower() != "cpu":
        return False
    current = os.environ.get("XLA_FLAGS", "")
    for flag in flags:
        if flag.split("=")[0] not in current:
            current = f"{current} {flag}"
    os.environ["XLA_FLAGS"] = current.strip()
    return True
