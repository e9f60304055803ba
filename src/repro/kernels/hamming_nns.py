"""Pallas TPU kernel: Hamming distance sweep over packed LSH signatures.

TPU adaptation of the iMARS TCAM threshold search (Sec. III-A/B): the analog
O(1) matchline compare becomes a VPU-rate XOR + popcount sweep over uint32
lanes. Signatures are packed 256 bits -> 8 x uint32, so one (8, block_n)
VMEM tile covers block_n items; the kernel emits raw distances and the
threshold (fixed-radius) selection stays in plain XLA (it is a trivial
compare + top-k over the int32 distance matrix).

Block geometry: the DB is read word-major, as (words, n) — the order XLA
already keeps a (n, words) signature array in on TPU, so the transpose is
free there — in lane-dense (words, block_n) tiles; the query tile is
(block_q, words) and the output tile (block_q, block_n) int32. With
block_n = 1024 the working set is a few tens of KiB per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.streaming_nns import block_distances
from repro.utils import cdiv


def _hamming_kernel(q_ref, db_ref, out_ref):
    out_ref[...] = block_distances(q_ref[...], db_ref[...])


@functools.partial(jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def hamming_distances_pallas(
    queries: jax.Array,  # (q, words) uint32
    db: jax.Array,  # (n, words) uint32
    *,
    block_q: int = 8,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """(q, n) int32 Hamming distances between packed signatures."""
    q, words = queries.shape
    n, words2 = db.shape
    assert words == words2, (words, words2)

    # pad to block multiples; padded db rows produce garbage distances that
    # the wrapper slices away.
    qp = cdiv(q, block_q) * block_q
    np_ = cdiv(n, block_n) * block_n
    queries_p = jnp.pad(queries, ((0, qp - q), (0, 0)))
    db_p = jnp.pad(db, ((0, np_ - n), (0, 0)))

    grid = (qp // block_q, np_ // block_n)
    out = pl.pallas_call(
        _hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, words), lambda i, j: (i, 0)),
            pl.BlockSpec((words, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.int32),
        interpret=interpret,
    )(queries_p, db_p.T)
    return out[:q, :n]
