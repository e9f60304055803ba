"""Pallas TPU kernel: fused int8 dequant-gather-pool (embedding bag).

This is the TPU-native image of the iMARS CMA RAM-mode lookup + in-memory
adder + intra-mat adder tree (Sec. III-A1): each grid step DMAs the int8
table tile that holds one bag slot's row from HBM into VMEM, chosen by a
scalar-prefetched index, dequantizes that row, and accumulates it into an
output tile that stays resident in VMEM across the bag's slots — partial
sums never round-trip to HBM, which is the in-memory-computing property
the paper is after.

**Chip layout.** The kernel reads the table word-major, as (d, n): XLA
keeps a (n, d) int8 table in exactly that physical order on TPU, so the
transpose is free there. A row is then one lane of a lane-aligned
(block_d, 128) tile (the whole table when n <= 128) — a block shape the
v5e compiler accepts, where a (1, d) row block is refused. The row is
picked out of its tile with a one-hot lane mask and summed across lanes
(exact: one non-zero term). Output is likewise (d, B), bag b in lane
b % 128 of a (block_d, 128) tile. Ids, per-slot scales and weights ride
in SMEM as scalar-prefetch operands.

Grid: (bag_tiles, d_blocks, bags_per_tile, slots) with slots innermost so
each bag's contributions accumulate in slot order into the resident
output tile. Padding slots carry id 0 / weight 0.

The table stays int8 in HBM: bytes touched per slot = one 128-row tile
of int8 — 4x less than the same tile of an f32 table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import round_up

# bag slots per pallas_call: ids, scales and weights sit in SMEM (1 MiB on
# v5e), 12 bytes a slot; larger batches run as several calls
_MAX_SLOTS_PER_CALL = 1 << 14


def _pool_kernel(ids_ref, scale_ref, w_ref, table_ref, out_ref, *,
                 n_slots, bags, lanes):
    i, b, l = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    s = (i * bags + b) * n_slots + l

    @pl.when(jnp.logical_and(b == 0, l == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = table_ref[...].astype(jnp.float32)  # (block_d, lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    row = jnp.sum(jnp.where(lane == ids_ref[s] % lanes, tile, 0.0), axis=1,
                  keepdims=True)  # (block_d, 1)
    contrib = (row * scale_ref[s]) * w_ref[s]
    bag = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(bag == b, contrib, 0.0)


def _pool_call(table_t, ids, scales, w, *, block_d, interpret):
    """One pallas_call over (B, L) slots -> (d, B) f32."""
    d, n = table_t.shape
    B, L = ids.shape
    lanes = min(n, 128)
    bags = min(B, 128)
    bp = round_up(B, bags)
    if bp > B:  # pad bags: id 0, weight 0
        pad = ((0, bp - B), (0, 0))
        ids, scales, w = (jnp.pad(x, pad) for x in (ids, scales, w))
    kernel = functools.partial(_pool_kernel, n_slots=L, bags=bags,
                               lanes=lanes)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bp // bags, d // block_d, bags, L),
            in_specs=[
                # the lane tile holding this slot's row
                pl.BlockSpec(
                    (block_d, lanes),
                    lambda i, k, b, l, ids, sc, w: (
                        k, ids[(i * bags + b) * L + l] // lanes)),
            ],
            out_specs=pl.BlockSpec(
                (block_d, bags), lambda i, k, b, l, ids, sc, w: (k, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((d, bp), jnp.float32),
        interpret=interpret,
        name="embedding_pool",
    )(ids.reshape(-1), scales.reshape(-1), w.reshape(-1), table_t)
    return out[:, :B]


@functools.partial(
    jax.jit, static_argnames=("block_d", "interpret")
)
def embedding_pool_pallas(
    table_values: jax.Array,  # (n, d) int8
    table_scales: jax.Array,  # (n, 1) f32
    ids: jax.Array,  # (B, L) int32, -1 padding
    weights: jax.Array | None = None,  # (B, L) f32
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    n, d = table_values.shape
    B, L = ids.shape
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)

    valid = (ids >= 0).astype(jnp.float32)
    w = valid if weights is None else weights.astype(jnp.float32) * valid
    safe_ids = jnp.maximum(ids, 0).astype(jnp.int32)
    scales = table_scales[safe_ids, 0].astype(jnp.float32)  # (B, L)
    table_t = table_values.T
    chunk = max(1, _MAX_SLOTS_PER_CALL // L)
    outs = [_pool_call(table_t, safe_ids[lo:lo + chunk],
                       scales[lo:lo + chunk], w[lo:lo + chunk],
                       block_d=block_d, interpret=interpret)
            for lo in range(0, B, chunk)]
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.T
