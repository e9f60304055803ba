"""Mesh construction: the repo's one mesh constructor and its presets.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
pure data parallelism (+ the cross-pod level of every hierarchical
reduction — the RSC-bus level of the iMARS hierarchy).

Defined as FUNCTIONS so importing this module never touches jax device
state (required: the dry-run sets XLA_FLAGS before any jax init; tests and
benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """A device mesh whose every axis is `AxisType.Auto`.

    `jax.make_mesh` defaults to `Explicit` axes, under which a
    `shard_map` output's type keeps its mesh axis and the next plain
    gather outside the map fails to resolve it. Every mesh in this repo —
    engine, benchmarks, tests — comes from here. `devices` defaults to
    `jax.devices()`.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
