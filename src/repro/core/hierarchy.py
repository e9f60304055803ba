"""Two-level hierarchical reduction — the iMARS adder trees on a TPU mesh.

Paper (Sec. III-A1): partial sums are accumulated inside each CMA (in-memory
adder), then across the C CMAs of a mat (intra-mat adder tree), then across
mats through a fan-in-4 intra-bank adder tree over the serialized IBC, and
finally blocks communicate over the RSC bus.

TPU image (Sec. 3 of DESIGN.md): VMEM-resident accumulation inside the fused
kernel (CMA level) -> deterministic fan-in tree reduce within a device
(intra-mat) -> psum/reduce-scatter over the `model` axis (intra-bank, the ICI
ring is the serialized adder bus) -> psum over the `pod` axis (RSC). The
row-sharded pooled lookup below is the complete ET dataflow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantization import QuantizedTensor
from repro.kernels import ops


def tree_sum(parts: jax.Array, fan_in: int = 4) -> jax.Array:
    """Deterministic fan-in-k tree sum over axis 0 (adder-tree semantics).

    Matches the paper's fixed accumulation order (counters, no routers), so
    results are bit-identical across runs regardless of parts count.
    """
    x = parts
    while x.shape[0] > 1:
        n = x.shape[0]
        pad = (-n) % fan_in
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        x = x.reshape((x.shape[0] // fan_in, fan_in) + x.shape[1:]).sum(axis=1)
    return x[0]


def hierarchical_psum(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Level-by-level psum (call inside shard_map): model -> data -> pod.

    Mirrors intra-bank (fast, local ring) before RSC (slow, cross-pod): each
    level completes before the next starts, exactly like the paper's
    serialized adder hierarchy.
    """
    for axis in axes:
        x = jax.lax.psum(x, axis)
    return x


def sharded_embedding_bag(
    mesh: jax.sharding.Mesh,
    axis: str,
    table: QuantizedTensor,  # rows sharded over `axis`
    ids: jax.Array,  # (B, L) global ids, replicated, -1 padded
    weights: jax.Array | None = None,
    extra_axes: tuple[str, ...] = (),
) -> jax.Array:
    """Row-sharded pooled lookup with two-level reduction -> (B, d) replicated.

    Each shard pools the subset of ids that live in its row range (intra-mat:
    VMEM accumulation in the fused kernel), then the partial bags are summed
    over `axis` (+ optional `extra_axes` for the pod level) with psum — the
    intra-bank adder tree / RSC bus.
    """
    n = table.values.shape[0]
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, (n, n_shards)
    per_shard = n // n_shards

    def local(table_vals, table_scales, ids_g, w):
        shard = jax.lax.axis_index(axis)
        lo = shard * per_shard
        local_ids = ids_g - lo
        in_range = jnp.logical_and(local_ids >= 0, local_ids < per_shard)
        in_range = jnp.logical_and(in_range, ids_g >= 0)
        local_ids = jnp.where(in_range, local_ids, -1)
        partial = ops.embedding_pool(table_vals, table_scales, local_ids, w)
        return hierarchical_psum(partial, (axis,) + extra_axes)

    w_spec = P() if weights is not None else None
    in_specs = (P(axis, None), P(axis, None), P(), w_spec)
    if weights is None:
        in_specs = in_specs[:3]

        def fn(tv, ts, ig):
            return local(tv, ts, ig, None)

        mapped = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
        )
        return mapped(table.values, table.scales, ids)

    mapped = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return mapped(table.values, table.scales, ids, weights)
