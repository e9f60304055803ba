"""Pallas TPU kernel: streaming fused Hamming fixed-radius NNS.

The dense filtering path (`ops.hamming_distances` -> threshold -> top-k)
materializes the whole (q, n) int32 distance matrix, which is the capacity
wall of the pipeline at million-item catalogs. This kernel is the streaming
image of the iMARS TCAM search + priority encoder (Sec. III-A/B): one blocked
scan over the signature DB that fuses

  (1) XOR-popcount distance over packed uint32 signature lanes,
  (2) the fixed-radius threshold compare (matchline),
  (3) bounded candidate selection (priority encode) into a running
      per-query buffer of the `max_candidates` best matches,

so peak memory is O(q * max_candidates) regardless of DB size.

Candidate bookkeeping packs (distance, db_row) into one int32 sort key,
``key = dist << shift | row`` with ``shift = 31 - bitlen(32 * words + 1)``
(256-bit signatures -> 9 distance bits, 22 row bits). Ascending key order is
exactly the dense path's (distance, index) order — `jax.lax.top_k` breaks
ties by lower index — so the streaming result is bit-identical to the dense
`fixed_radius_nns` output.

**Wide keys (DBs past the packed-key capacity).** A single packed key can
only index `2**shift` rows (4.19M at 256-bit signatures). Instead of paying
for two-word (dist, row_hi/row_lo) keys everywhere, the scan is split into
*superblocks* of at most `2**shift` rows: the row bits of every key hold the
offset *within the current superblock* (so the in-kernel merge stays
pure int32), each superblock accumulates its own resident candidate
buffer, and full row ids are reconstructed on the host as
``superblock * superblock_rows + local_row``. The per-superblock top-K
buffers are then merged host-side by one *stable* sort on distance
(`merge_candidate_buffers`): each buffer is already (dist, row)-sorted and
superblock row ranges are disjoint and ascending, so stability alone
reproduces the exact global (distance, index) order. DB capacity becomes
int32 row ids (2**31 rows) rather than `2**shift`.

The per-block merge keeps the buffer sorted: `max_candidates` rounds each
take every query row's current minimum over the resident buffer and the
block's candidate keys into the next buffer slot and retire that key
(valid keys are unique, so exactly one leaves per round). Every value is a
2-D (block_q, lanes) array — lane reductions and selects that Mosaic
lowers on v5e, with no in-kernel sort, reshape or 3-D broadcast. Blocks
with no matches (the common case at selective radii) skip the merge
entirely under `pl.when`.

Grid: (q_blocks, n_blocks) with the DB dimension innermost and *sequential*
— the (1, block_q, K) candidate tile is revisited across its superblock's
blocks and stays resident in VMEM, the same accumulator pattern as the
embedding-pool kernel; it re-initializes when the scan crosses into the next
superblock. `n_valid` rides along as a dynamic scalar-prefetch operand
(SMEM) so the sharded path can mask per-shard padding rows with a traced
value.

**Row eligibility (`db_mask`).** The live-catalog layer (serving/catalog.py)
tombstones base rows that were deleted or overwritten by a delta row; those
rows must never match, wherever they sit in the DB — a prefix count
(`n_valid`) cannot express that. An optional (1, n) int32 mask operand rides
the scan blocked along the DB dimension exactly like the signature rows
((1, block_n) lane-aligned tiles, zero-padded past `n`): the matchline AND
is one extra elementwise compare per block, so masked and unmasked scans
cost the same. When no mask is passed the operand is omitted entirely (a
separate pallas_call signature), so frozen catalogs pay nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import cdiv, round_up

# THE invalid-slot distance sentinel: core/nns.py (dense padding) and
# kernels/ref.py (oracle decode) both import it, so the bit-match invariant
# between every path hangs off this one definition.
BIG_DIST = 2**30


def key_shift(words: int) -> int:
    """Bits reserved for the db row index in the packed (dist, row) key."""
    return 31 - (32 * words + 1).bit_length()


def big_key(words: int) -> int:
    """Sentinel key strictly greater than every valid (dist, row) key."""
    return (32 * words + 1) << key_shift(words)


def max_streamable_items(words: int) -> int:
    """Rows one packed int32 key can index == the max superblock size
    (4.19M at words=8). DBs beyond this scan as multiple superblocks."""
    return 1 << key_shift(words)


def pack_key(dist, row, words: int):
    """Pack (dist, superblock-local row) into one int32 sort key.

    Total preorder: key(a) < key(b) iff (dist_a, row_a) < (dist_b, row_b)
    lexicographically, for any dist <= 32*words and row < 2**key_shift.
    Works on ints and on jnp arrays alike.
    """
    return dist * (1 << key_shift(words)) + row


def unpack_key(key, words: int):
    """Inverse of `pack_key`: key -> (dist, superblock-local row)."""
    shift = key_shift(words)
    return key >> shift, key & ((1 << shift) - 1)


def superblock_rows(words: int, block_n: int = 1,
                    superblock: int | None = None) -> int:
    """Rows per superblock: the packed-key capacity (or the `superblock`
    testing override, clamped to it) floored to a multiple of `block_n` so
    superblock boundaries land on kernel block boundaries."""
    cap = max_streamable_items(words)
    sb = cap if superblock is None else min(int(superblock), cap)
    sb = (sb // block_n) * block_n
    if sb <= 0:
        raise ValueError(
            f"superblock {superblock} smaller than one block ({block_n} "
            f"rows) at words={words}")
    return sb


def merge_candidate_buffers(indices: jax.Array, distances: jax.Array,
                            max_candidates: int):
    """Merge per-superblock sorted candidate buffers into the global top-K.

    `indices` / `distances` are (q, S*K), the S per-superblock buffers
    concatenated in ascending-superblock order. Each buffer is sorted by
    (distance, row) with invalid slots (-1, BIG_DIST) at its tail, and the
    row ranges of successive superblocks are disjoint and ascending — so ONE
    stable sort on distance alone reproduces the exact lexicographic
    (distance, row) order: among equal distances, stability preserves
    ascending-superblock (hence ascending-row) order.
    """
    order = jnp.argsort(distances, axis=-1, stable=True)
    order = order[:, :max_candidates]
    return (jnp.take_along_axis(indices, order, axis=1),
            jnp.take_along_axis(distances, order, axis=1))


def merge_chunk_buffers(chunks, max_candidates: int):
    """Merge the per-chunk buffers of a host-driven out-of-core scan.

    `chunks` is a list of (indices, distances) pairs — each (q, K) with
    GLOBAL row ids — produced by scanning ascending, disjoint row ranges of
    one memmapped DB. That is exactly the superblock-merge precondition
    (per-buffer (dist, row) sort, invalids at the tail, ascending disjoint
    row ranges across buffers), so `merge_candidate_buffers` is exact here
    too: the out-of-core scan bit-matches the resident scan by the same
    argument that makes the multi-superblock kernel exact. An empty chunk
    list (every block pruned) yields the all-sentinel result.
    """
    if not chunks:
        raise ValueError("merge_chunk_buffers: no chunks (caller emits "
                         "the empty result for fully-pruned scans)")
    if len(chunks) == 1:
        idx, dist = chunks[0]
        return idx[:, :max_candidates], dist[:, :max_candidates]
    idx = jnp.concatenate([c[0] for c in chunks], axis=1)
    dist = jnp.concatenate([c[1] for c in chunks], axis=1)
    return merge_candidate_buffers(idx, dist, max_candidates)


def block_distances(q, db_t):
    """(bq, words) x (words, bn) packed signatures -> (bq, bn) int32.

    The XOR-popcount of both Hamming kernels, one signature word at a
    time over the word-major DB tile: every intermediate is 2-D with the
    DB rows on the lanes, which is what Mosaic lays out without reshapes.
    """
    d = jnp.zeros((q.shape[0], db_t.shape[1]), jnp.int32)
    for w in range(db_t.shape[0]):
        x = jnp.bitwise_xor(q[:, w:w + 1], db_t[w:w + 1, :])
        d = d + jax.lax.population_count(x).astype(jnp.int32)
    return d


def _merge_smallest(buf, new_keys, *, n_keep, big):
    """Sorted `n_keep` smallest keys of `buf` (bq, k_pad) + `new_keys`.

    Valid keys are unique (distinct rows), so each round takes every
    row's current minimum into the next slot and retires exactly that
    key; slots past the valid keys (and past `n_keep`) stay `big`.
    """
    slot = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)

    def take_min(k, carry):
        buf, new, out = carry
        mn = jnp.minimum(jnp.min(buf, axis=1, keepdims=True),
                         jnp.min(new, axis=1, keepdims=True))
        out = jnp.where(slot == k, mn, out)
        return (jnp.where(buf == mn, big, buf),
                jnp.where(new == mn, big, new), out)

    out = jnp.full(buf.shape, big, jnp.int32)
    return jax.lax.fori_loop(0, n_keep, take_min, (buf, new_keys, out))[2]


def _streaming_nns_kernel(limit_ref, q_ref, db_ref, *refs, radius, shift,
                          big, blocks_per_sb, n_keep, has_mask, has_prune):
    mask_ref = refs[0] if has_mask else None
    scan_ref = refs[int(has_mask)] if has_prune else None
    keys_ref, counts_ref = refs[-2:]
    j = pl.program_id(1)

    # buffer inits stay OUTSIDE the prune predicate: a superblock whose
    # every block is pruned for this query tile must still emit the empty
    # (all-sentinel) buffer and zero counts, not garbage
    @pl.when(j % blocks_per_sb == 0)
    def _init_keys():  # fresh candidate buffer per superblock
        keys_ref[...] = jnp.full(keys_ref.shape, big, jnp.int32)

    @pl.when(j == 0)
    def _init_counts():
        counts_ref[...] = jnp.zeros(counts_ref.shape, jnp.int32)

    def _scan_block():
        d = block_distances(q_ref[...], db_ref[...])  # (block_q, block_n)
        block_n = d.shape[1]
        iota = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        gidx = j * block_n + iota  # global row id (int32-safe to 2**31 rows)
        within = jnp.logical_and(d <= radius, gidx < limit_ref[0])
        if mask_ref is not None:  # tombstoned rows never match (matchline AND)
            within = jnp.logical_and(within, mask_ref[...] != 0)
        hits = within.astype(jnp.int32)
        counts_ref[...] += jnp.sum(hits, axis=1, keepdims=True)

        @pl.when(jnp.max(hits) > 0)
        def _merge():
            # row bits carry the superblock-LOCAL offset: int32 keys
            lidx = (j % blocks_per_sb) * block_n + iota
            new_keys = jnp.where(within, d * (1 << shift) + lidx, big)
            keys_ref[0] = _merge_smallest(keys_ref[0], new_keys,
                                          n_keep=n_keep, big=big)

    if scan_ref is None:
        _scan_block()
    else:
        # block-summary pruning: this (query-tile, db-block) cell was
        # proven empty of matches by the sound bound — skip all of it.
        # Cells are bit-packed 32 blocks per SMEM word.
        word = scan_ref[0, 0, j // 32]
        pl.when(jnp.right_shift(word, j % 32) & 1 != 0)(_scan_block)


@functools.partial(
    jax.jit,
    static_argnames=("radius", "max_candidates", "block_q", "block_n",
                     "superblock", "prune_block_rows", "interpret"),
)
def streaming_nns_pallas(
    queries: jax.Array,  # (q, words) uint32
    db: jax.Array,  # (n, words) uint32
    n_valid: jax.Array,  # () int32 — rows >= n_valid never match (dynamic)
    db_mask: jax.Array | None = None,  # (n,) bool/int — 0 rows never match
    *,
    radius: int,
    max_candidates: int,
    block_q: int = 8,
    block_n: int = 512,
    superblock: int | None = None,  # rows per superblock (testing override)
    prune_blocks: jax.Array | None = None,  # (q, nb) bool — True = skip
    prune_block_rows: int | None = None,  # rows per summary block
    interpret: bool = False,
):
    """Streaming fixed-radius NNS -> (indices, distances, counts).

    Bit-matches the dense hamming->threshold->top_k path: indices/distances
    are the `max_candidates` nearest matches sorted by (distance, index),
    padded with (-1, BIG_DIST); counts are total matches within radius.
    DBs larger than the packed-key capacity scan as multiple superblocks
    whose candidate buffers are merged host-side (see module docstring).
    `db_mask` marks per-row eligibility (tombstones); None scans unmasked
    through a mask-free kernel signature.

    **Block pruning.** `prune_blocks` ((q, nb) bool from the core
    `BlockSummary` bounds, `prune_block_rows` rows per summary block,
    which must be a multiple of `block_n`) adds a per-(query-tile,
    db-block) scan bit, packed 32 blocks per int32 word into one SMEM row
    per query tile: when every query of the tile prunes the block, the
    whole kernel body is predicated off with `pl.when` — no distance, no
    merge, no count. The candidate/count buffer inits stay outside the
    predicate so fully-pruned superblocks still emit well-formed (empty)
    buffers. Sound bound => bit-identical outputs.

    **Chip layout.** The kernel reads the DB word-major, as (words, n):
    XLA keeps a (n, words) signature array in exactly that physical order
    on TPU, so the transpose below is free there, and every DB tile is a
    lane-dense (words, block_n) block. `n_valid` rides in SMEM as a
    scalar-prefetch operand.
    """
    q, words = queries.shape
    n, words2 = db.shape
    assert words == words2, (words, words2)
    shift = key_shift(words)
    big = big_key(words)
    sb_rows = superblock_rows(words, block_n, superblock)
    blocks_per_sb = sb_rows // block_n

    # the resident buffer is lane-padded; extra slots decode to padding
    k_pad = max(128, round_up(max_candidates, 128))
    qp = round_up(q, block_q)
    np_ = round_up(n, block_n)
    n_blocks = np_ // block_n
    n_sb = cdiv(n_blocks, blocks_per_sb)
    queries_p = jnp.pad(queries, ((0, qp - q), (0, 0))) if qp > q else queries
    db_p = jnp.pad(db, ((0, np_ - n), (0, 0))) if np_ > n else db
    limit = jnp.reshape(
        jnp.minimum(jnp.asarray(n_valid, jnp.int32), n), (1,))

    operands = [limit, queries_p, db_p.T]
    in_specs = [
        pl.BlockSpec((block_q, words), lambda i, j, lim: (i, 0)),
        pl.BlockSpec((words, block_n), lambda i, j, lim: (0, j)),
    ]
    if db_mask is not None:
        mask = jnp.reshape(db_mask.astype(jnp.int32), (1, n))
        if np_ > n:  # pad rows ineligible (n_valid already excludes them)
            mask = jnp.pad(mask, ((0, 0), (0, np_ - n)))
        operands.append(mask)
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, lim: (0, j)))
    if prune_blocks is not None:
        if prune_block_rows is None or prune_block_rows % block_n:
            raise ValueError(
                f"prune_block_rows ({prune_block_rows}) must be a multiple "
                f"of block_n ({block_n}) — the ops adapter aligns them")
        # per-(query-tile, kernel-block) scan cells: a kernel block scans
        # unless EVERY query of its tile prunes the covering summary block
        needed = jnp.logical_not(prune_blocks)  # (q, nb)
        if qp > q:  # pad queries contribute nothing
            needed = jnp.pad(needed, ((0, qp - q), (0, 0)))
        nb = needed.shape[1]
        needed = jnp.any(needed.reshape(qp // block_q, block_q, nb), axis=1)
        cells = jnp.repeat(needed, prune_block_rows // block_n,
                           axis=1)[:, :n_blocks]
        n_words = cdiv(n_blocks, 32)
        # rows beyond the summary's coverage always scan
        cells = jnp.pad(cells, ((0, 0), (0, 32 * n_words - cells.shape[1])),
                        constant_values=True)
        bits = cells.reshape(-1, n_words, 32).astype(jnp.uint32)
        packed = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                         dtype=jnp.uint32)
        operands.append(jax.lax.bitcast_convert_type(packed, jnp.int32)
                        .reshape(-1, 1, n_words))
        in_specs.append(pl.BlockSpec((1, 1, n_words),
                                     lambda i, j, lim: (i, 0, 0),
                                     memory_space=pltpu.SMEM))

    kernel = functools.partial(
        _streaming_nns_kernel, radius=radius, shift=shift, big=big,
        blocks_per_sb=blocks_per_sb, n_keep=max_candidates,
        has_mask=db_mask is not None, has_prune=prune_blocks is not None)
    keys, counts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(qp // block_q, n_blocks),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, block_q, k_pad),
                             lambda i, j, lim: (j // blocks_per_sb, i, 0)),
                pl.BlockSpec((block_q, 1), lambda i, j, lim: (i, 0)),
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_sb, qp, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ),
        interpret=interpret,
    )(*operands)

    # buffers are sorted: first K slots of each superblock = its best K
    keys = keys[:, :q, :max_candidates]  # (n_sb, q, K)
    dist, local = unpack_key(keys, words)
    valid = keys < big
    offsets = (jnp.arange(n_sb, dtype=jnp.int32) * sb_rows)[:, None, None]
    indices = jnp.where(valid, local + offsets, -1)
    distances = jnp.where(valid, dist, BIG_DIST)
    if n_sb > 1:  # wide DB: merge the per-superblock buffers
        indices = jnp.moveaxis(indices, 0, 1).reshape(q, -1)
        distances = jnp.moveaxis(distances, 0, 1).reshape(q, -1)
        indices, distances = merge_candidate_buffers(
            indices, distances, max_candidates)
    else:
        indices, distances = indices[0], distances[0]
    return indices, distances, counts[:q, 0]
