"""Small shared utilities: pytree dataclasses, shape helpers, rng streams."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator, Mapping

import jax
import jax.numpy as jnp


class FrozenMapping(Mapping):
    """Immutable, hashable mapping — a dict that can live in a static
    (metadata) field of a jit-traced pytree."""

    __slots__ = ("_d",)

    def __init__(self, d: Mapping):
        object.__setattr__(self, "_d", dict(d))

    def __getitem__(self, k):
        return self._d[k]

    def __iter__(self) -> Iterator:
        return iter(self._d)

    def __len__(self) -> int:
        return len(self._d)

    def __hash__(self) -> int:
        return hash(tuple(sorted(self._d.items())))

    def __eq__(self, other) -> bool:
        if isinstance(other, (FrozenMapping, Mapping)):
            return dict(self._d) == dict(other)
        return NotImplemented

    def __repr__(self) -> str:
        return f"FrozenMapping({self._d!r})"


def pytree_dataclass(cls=None, *, meta_fields: tuple = ()):
    """Register a dataclass as a JAX pytree; `meta_fields` stay static."""

    def wrap(c):
        c = dataclasses.dataclass(c)
        data_fields = tuple(
            f.name for f in dataclasses.fields(c) if f.name not in meta_fields
        )
        jax.tree_util.register_dataclass(c, data_fields, tuple(meta_fields))
        return c

    return wrap(cls) if cls is not None else wrap


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_flops(n: float) -> str:
    for unit in ("", "K", "M", "G", "T", "P", "E"):
        if abs(n) < 1000.0:
            return f"{n:.2f} {unit}FLOP"
        n /= 1000.0
    return f"{n:.2f} ZFLOP"


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (ShapeDtypeStruct or ndarray)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(math.prod(l.shape) for l in leaves if hasattr(l, "shape"))


def fold_key(key: jax.Array, *names: str) -> jax.Array:
    """Deterministically derive a subkey from string path components."""
    for name in names:
        data = sum(ord(c) * (i + 1) for i, c in enumerate(name)) % (2**31 - 1)
        key = jax.random.fold_in(key, data)
    return key
