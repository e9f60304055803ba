"""Nearest-neighbor search — the iMARS filtering-stage retrieval (Sec. III-B).

The paper replaces cosine top-k with *fixed-radius* Hamming NNS over 256-bit
LSH signatures (TCAM threshold match). We implement:

  * `fixed_radius_nns`       — single-device, two execution plans behind one
                               `scan_block` knob:
                                 dense     — (q, n) distance matrix via the
                                             Hamming kernel + threshold +
                                             top-k (fast for small DBs);
                                 streaming — fused blocked scan through
                                             `ops.streaming_nns`, O(q * K)
                                             memory for million-item catalogs.
                               `scan_block=None` routes automatically by DB
                               size (`STREAM_MIN_ITEMS`), `scan_block=0`
                               forces dense, any positive value forces
                               streaming with that chunk size. Both plans are
                               bit-identical.
  * `sharded_fixed_radius_nns` — the item database row-sharded over a mesh
                               axis: each shard scans locally (the "CMA bank")
                               — streaming *within* the shard composes with
                               sharding *across* devices — and contributes a
                               count-bounded candidate buffer that is
                               all-gathered: the communication pattern of the
                               paper's priority encoder + RSC. Optionally
                               *also* sharded over a query mesh axis
                               (`query_axis`): query blocks scan the banks in
                               parallel, composing both partitions.
  * `query_parallel_nns`     — queries sharded over a mesh axis with the DB
                               replicated: every device scans the full
                               catalog for its query block (the multi-bank
                               parallel-search mode of the paper's CMA
                               fabric, applied along the query dimension).
  * cosine references        — the paper's accuracy-baseline configs
                               (fp32/int8 cosine top-k).

Fixed-radius semantics are kept (not top-k) for the paper's reason: a radius
compare vectorizes to a pure elementwise op with no sort; we only sort the
(already tiny) bounded candidate set.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.streaming_nns import BIG_DIST
from repro.utils import cdiv, pytree_dataclass

# invalid-slot distance sentinel (single definition in
# kernels/streaming_nns.py), exported for tests
BIG = jnp.int32(BIG_DIST)
_BIG = BIG  # backwards-compatible alias

# dense materializes q*n int32 — above this DB size the O(q*K) streaming
# scan wins by default (a 256-query batch at 2**18 items is already 256 MiB)
STREAM_MIN_ITEMS = 1 << 18
DEFAULT_SCAN_BLOCK = 4096
# default BlockSummary granularity: one summary entry per 4096 rows (the
# default streaming chunk). Must stay a multiple of 128 so every viable
# Pallas tile divides it (see `build_block_summary`).
SUMMARY_BLOCK_ROWS = 4096


class NNSResult(NamedTuple):
    indices: jax.Array  # (q, max_candidates) int32, -1 padded
    distances: jax.Array  # (q, max_candidates) int32, BIG where invalid
    counts: jax.Array  # (q,) int32 — total matches within radius
    # (q,) int32 — summary blocks whose lower bound admitted the query, or
    # None when the scan ran unpruned (dense plan, no summary, prune=False)
    blocks_touched: jax.Array | None = None


# ---------------------------------------------------------------------------
# Block summaries: sound per-block Hamming lower bounds for scan pruning
# ---------------------------------------------------------------------------
def _popcount_u32(x: np.ndarray) -> np.ndarray:
    """Vectorized host-side popcount over uint32 arrays -> int32 counts."""
    x = x.astype(np.uint32)
    x = x - ((x >> np.uint32(1)) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> np.uint32(2))
                                       & np.uint32(0x33333333))
    x = (x + (x >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> np.uint32(24)).astype(np.int32)


@pytree_dataclass(meta_fields=("block_rows",))
class BlockSummary:
    """Per-block occupancy summary of a packed-signature DB, for pruning.

    For each block of `block_rows` consecutive DB rows it keeps, over the
    block's *eligible* rows (alive under the tombstone mask and below
    `n_valid`):

      * ``or_sigs`` / ``and_sigs`` — the bitwise OR / AND of the rows'
        packed signatures: any eligible row r satisfies
        ``and_sigs <= r <= or_sigs`` as bit sets;
      * ``min_pc`` / ``max_pc`` — per-word popcount range of the rows;
      * ``n_alive`` — eligible-row count (0 = the block can never match).

    `summary_block_bounds` turns these into a sound lower bound on the
    Hamming distance from any query to any eligible row of the block, so
    blocks whose bound exceeds the radius are skipped without changing a
    single output bit (see docs/KERNELS.md for the soundness argument).

    Soundness contract: the eligible-row set the summary was built over
    must be a SUPERSET of the rows the scan may match — then every pruned
    block is provably empty of matches. Equality keeps bounds tight;
    `update_block_summary` recomputes touched blocks exactly so tombstoned
    rows never loosen (or unsound-tighten) the bound.
    """

    or_sigs: jax.Array  # (n_blocks, words) uint32 — OR of eligible rows
    and_sigs: jax.Array  # (n_blocks, words) uint32 — AND of eligible rows
    min_pc: jax.Array  # (n_blocks, words) int32 — min per-word popcount
    max_pc: jax.Array  # (n_blocks, words) int32 — max per-word popcount
    n_alive: jax.Array  # (n_blocks,) int32 — eligible rows in the block
    block_rows: int = SUMMARY_BLOCK_ROWS

    @property
    def n_blocks(self) -> int:
        return self.or_sigs.shape[0]


# host-side builder passes this many blocks per vectorized sweep, bounding
# peak temp memory (64 blocks * 4096 rows * 8 words ~= 8 MiB per temporary)
_BUILD_CHUNK_BLOCKS = 64


def _summarize_blocks(sigs3: np.ndarray, elig3: np.ndarray):
    """(nb, block_rows, words) sigs + (nb, block_rows) eligibility ->
    the five per-block summary arrays (numpy)."""
    e = elig3[..., None]
    or_sigs = np.bitwise_or.reduce(
        np.where(e, sigs3, np.uint32(0)), axis=1).astype(np.uint32)
    and_sigs = np.bitwise_and.reduce(
        np.where(e, sigs3, np.uint32(0xFFFFFFFF)), axis=1).astype(np.uint32)
    pc = _popcount_u32(sigs3)
    min_pc = np.min(np.where(e, pc, np.int32(33)), axis=1).astype(np.int32)
    max_pc = np.max(np.where(e, pc, np.int32(-1)), axis=1).astype(np.int32)
    n_alive = elig3.sum(axis=1).astype(np.int32)
    return or_sigs, and_sigs, min_pc, max_pc, n_alive


def build_block_summary(
    db_sigs,  # (n, words) uint32 — packed signatures (numpy or jax)
    block_rows: int = SUMMARY_BLOCK_ROWS,
    *,
    db_mask=None,  # (n,) bool — rows eligible to match (tombstone mask)
    n_valid: int | None = None,  # rows >= n_valid are padding, ineligible
) -> BlockSummary:
    """Build a `BlockSummary` over `db_sigs` (pure, host-side).

    The eligibility set is ``db_mask AND (row < n_valid)`` — pass exactly
    what the scan will use so bounds stay tight; passing a superset is
    sound but looser. `block_rows` must be a positive multiple of 128 so
    any lane-aligned Pallas tile divides it (mask expansion stays a pure
    repeat). Runs in bounded chunks of blocks, so peak temporary memory is
    independent of the DB size.
    """
    block_rows = int(block_rows)
    if block_rows <= 0 or block_rows % 128:
        raise ValueError(
            f"block_rows must be a positive multiple of 128, got "
            f"{block_rows}")
    sigs = np.asarray(db_sigs)
    n, words = sigs.shape
    nb = max(1, cdiv(n, block_rows))
    elig = (np.ones(n, bool) if db_mask is None
            else np.asarray(db_mask, bool)[:n].copy())
    if n_valid is not None:
        elig &= np.arange(n) < int(n_valid)

    or_sigs = np.zeros((nb, words), np.uint32)
    and_sigs = np.full((nb, words), np.uint32(0xFFFFFFFF), np.uint32)
    min_pc = np.full((nb, words), 33, np.int32)
    max_pc = np.full((nb, words), -1, np.int32)
    n_alive = np.zeros((nb,), np.int32)
    for b0 in range(0, nb, _BUILD_CHUNK_BLOCKS):
        b1 = min(b0 + _BUILD_CHUNK_BLOCKS, nb)
        lo, hi = b0 * block_rows, min(b1 * block_rows, n)
        rows = (b1 - b0) * block_rows
        s = np.zeros((rows, words), np.uint32)
        e = np.zeros((rows,), bool)
        s[: hi - lo] = sigs[lo:hi]
        e[: hi - lo] = elig[lo:hi]
        (or_sigs[b0:b1], and_sigs[b0:b1], min_pc[b0:b1], max_pc[b0:b1],
         n_alive[b0:b1]) = _summarize_blocks(
            s.reshape(b1 - b0, block_rows, words),
            e.reshape(b1 - b0, block_rows))
    return BlockSummary(
        or_sigs=jnp.asarray(or_sigs), and_sigs=jnp.asarray(and_sigs),
        min_pc=jnp.asarray(min_pc), max_pc=jnp.asarray(max_pc),
        n_alive=jnp.asarray(n_alive), block_rows=block_rows)


def update_block_summary(summary: BlockSummary, db_sigs, db_mask,
                         touched_rows) -> BlockSummary:
    """Incrementally refresh a summary after rows changed eligibility.

    Recomputes — exactly, from `db_sigs`/`db_mask` — every block containing
    a row in `touched_rows` (host-side; cost is O(touched blocks), not
    O(n)). This is the upsert/delete maintenance rule: tombstoning a row
    must *tighten* (never loosen) the block's bound, and an incremental
    OR/AND cannot un-set bits, so touched blocks are rebuilt from scratch.
    The result is bit-identical to `build_block_summary` over the same
    (db_sigs, db_mask) — asserted by tests and benchmarks/catalog_churn.py.
    """
    rows = np.unique(np.asarray(touched_rows, np.int64).reshape(-1))
    sigs = np.asarray(db_sigs)
    n, words = sigs.shape
    br = summary.block_rows
    rows = rows[(rows >= 0) & (rows < n)]
    if rows.size == 0:
        return summary
    elig = (np.ones(n, bool) if db_mask is None
            else np.asarray(db_mask, bool)[:n])
    blocks = np.unique(rows // br)
    blocks = blocks[blocks < summary.n_blocks]
    or_sigs = np.asarray(summary.or_sigs).copy()
    and_sigs = np.asarray(summary.and_sigs).copy()
    min_pc = np.asarray(summary.min_pc).copy()
    max_pc = np.asarray(summary.max_pc).copy()
    n_alive = np.asarray(summary.n_alive).copy()
    for b in blocks:
        lo, hi = int(b) * br, min(int(b) * br + br, n)
        s = np.zeros((br, words), np.uint32)
        e = np.zeros((br,), bool)
        s[: hi - lo] = sigs[lo:hi]
        e[: hi - lo] = elig[lo:hi]
        (or_sigs[b], and_sigs[b], min_pc[b], max_pc[b],
         n_alive[b]) = (a[0] for a in _summarize_blocks(
            s[None], e[None]))
    return BlockSummary(
        or_sigs=jnp.asarray(or_sigs), and_sigs=jnp.asarray(and_sigs),
        min_pc=jnp.asarray(min_pc), max_pc=jnp.asarray(max_pc),
        n_alive=jnp.asarray(n_alive), block_rows=br)


def summary_block_bounds(query_sigs: jax.Array,
                         summary: BlockSummary) -> jax.Array:
    """(q, words) queries x summary -> (q, n_blocks) int32 lower bounds.

    For every (query, block) pair, a sound lower bound on the Hamming
    distance from the query to ANY eligible row of the block, combining
    two per-word bounds (the larger of the two per word, summed):

      * occupancy:  popcount(q & ~or) + popcount(~q & and) — bit positions
        where the query is 1 but no eligible row is (q & ~or), or where the
        query is 0 but every eligible row is 1 (~q & and), each contribute
        one mismatch to every row of the block;
      * popcount range: |popcount(q_w) - popcount(r_w)| <= d(q_w, r_w),
        and popcount(r_w) is within [min_pc, max_pc].

    Blocks with no eligible rows bound to `BIG` (always pruned).
    """
    pc = lambda x: jax.lax.population_count(x).astype(jnp.int32)  # noqa: E731
    q = query_sigs[:, None, :]  # (q, 1, words)
    occ = pc(q & ~summary.or_sigs[None]) + pc(~q & summary.and_sigs[None])
    pcq = pc(q)
    rng = jnp.maximum(pcq - summary.max_pc[None],
                      summary.min_pc[None] - pcq)
    per_word = jnp.maximum(occ, jnp.maximum(rng, 0))
    total = jnp.sum(per_word, axis=-1)
    return jnp.where(summary.n_alive[None] > 0, total, BIG)


def _prune_mask(query_sigs, summary, radius):
    """-> (prune (q, n_blocks) bool, blocks_touched (q,) int32)."""
    prune = summary_block_bounds(query_sigs, summary) > radius
    touched = jnp.sum((~prune).astype(jnp.int32), axis=-1)
    return prune, touched


def _plan_streams(n_rows: int, scan_block: int | None) -> bool:
    """Static mirror of `fixed_radius_nns`'s dense-vs-streaming routing."""
    if scan_block is None:
        return n_rows >= STREAM_MIN_ITEMS
    return scan_block != 0


def fixed_radius_nns(
    query_sigs: jax.Array,  # (q, words) uint32
    db_sigs: jax.Array,  # (n, words) uint32
    radius: int,
    max_candidates: int = 128,
    db_mask: jax.Array | None = None,  # (n,) bool — rows eligible to match
    *,
    scan_block: int | None = None,  # None=auto, 0=dense, >0=streaming chunk
    n_valid: jax.Array | int | None = None,  # rows >= n_valid never match
    superblock: int | None = None,  # streaming superblock rows (testing knob)
    summary: BlockSummary | None = None,  # block summary enabling pruning
    prune: bool | None = None,  # None=auto (prune when summary given), False=off
) -> NNSResult:
    """All db items within Hamming `radius` of each query (bounded, sorted).

    Args:
      query_sigs / db_sigs: (q, words) / (n, words) packed uint32 LSH
        signatures (words=8 for the paper's 256-bit signatures).
      radius: fixed match radius (the TCAM threshold), static.
      max_candidates: bounded candidate-set size K; output columns.
      db_mask: optional (n,) bool eligibility mask — rows where it is False
        (live-catalog tombstones) never match on either plan.
      scan_block: execution plan — None auto-routes by DB size
        (`STREAM_MIN_ITEMS`), 0 forces dense, >0 forces streaming with that
        chunk. Both plans return bit-identical results.
      n_valid: prefix count of real rows; rows >= n_valid never match
        (may be a traced scalar — used by the sharded paths for padding).
      superblock: streaming superblock size override (testing knob;
        results are superblock-invariant).
      summary: optional `BlockSummary` over `db_sigs` (built against an
        eligibility superset of this scan's (db_mask, n_valid)); enables
        block pruning on the streaming plan. Bit-identical results — the
        bound is sound — plus a per-query `blocks_touched` counter.
      prune: None (default) prunes whenever `summary` is given and the
        plan streams; False disables pruning even with a summary.
    Returns:
      NNSResult of (q, K) indices (-1 padded), (q, K) distances (`BIG`
      where invalid), (q,) total within-radius counts, and (pruned scans
      only) (q,) `blocks_touched`. Candidates are sorted by
      (distance, index) ascending — the exact dense threshold + top-k
      order, whatever the execution plan.
    """
    if isinstance(db_sigs, np.memmap):
        # out-of-core table handle: the host-driven scan loads admitted
        # summary blocks on demand instead of residing the DB (tiered
        # catalog cold shard). Same bits, different residency.
        return out_of_core_nns(
            query_sigs, db_sigs, radius, max_candidates, db_mask=db_mask,
            scan_block=scan_block, n_valid=n_valid, summary=summary,
            prune=prune)

    n, words = db_sigs.shape
    use_stream = _plan_streams(n, scan_block)
    block = DEFAULT_SCAN_BLOCK if not scan_block else scan_block

    if use_stream:
        prune_blocks = blocks_touched = block_rows = None
        if summary is not None and prune is not False:
            prune_blocks, blocks_touched = _prune_mask(
                query_sigs, summary, radius)
            block_rows = summary.block_rows
        indices, distances, counts = ops.streaming_nns(
            query_sigs, db_sigs, radius=radius,
            max_candidates=max_candidates, scan_block=block, n_valid=n_valid,
            superblock=superblock, db_mask=db_mask,
            prune_blocks=prune_blocks, prune_block_rows=block_rows)
        return NNSResult(indices=indices, distances=distances, counts=counts,
                         blocks_touched=blocks_touched)

    d = ops.hamming_distances(query_sigs, db_sigs)  # (q, n)
    within = d <= radius
    if n_valid is not None:
        within = jnp.logical_and(
            within, (jnp.arange(n) < n_valid)[None, :])
    if db_mask is not None:
        within = jnp.logical_and(within, db_mask[None, :])
    counts = jnp.sum(within, axis=-1).astype(jnp.int32)
    masked = jnp.where(within, d, BIG)
    # smallest distances first (threshold-match + priority encode)
    neg_top, idx = jax.lax.top_k(-masked, k=min(max_candidates, d.shape[-1]))
    dist = -neg_top
    valid = dist < BIG
    idx = jnp.where(valid, idx, -1)
    dist = jnp.where(valid, dist, BIG)
    if idx.shape[-1] < max_candidates:  # tiny db: pad out
        pad = max_candidates - idx.shape[-1]
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
        dist = jnp.pad(dist, ((0, 0), (0, pad)), constant_values=int(BIG))
    return NNSResult(indices=idx, distances=dist, counts=counts)


# pre-jitted prune-mask for host-driven scans (radius static)
_prune_mask_jit = jax.jit(_prune_mask, static_argnums=(2,))

# rows gathered per jitted chunk scan of the out-of-core driver: large
# enough to amortize dispatch (32 calls at 8M rows), small enough that the
# resident chunk buffers stay ~8 MB each at 256-bit signatures (the gather
# copy, its device image, and one in-flight predecessor pin ~4 of these at
# peak, so the chunk size bounds the scan's whole RSS footprint)
OUTOFCORE_CHUNK_ROWS = 1 << 18


def out_of_core_nns(
    query_sigs: jax.Array,  # (q, words) uint32
    db_sigs: np.ndarray,  # (n, words) uint32 host ndarray / np.memmap
    radius: int,
    max_candidates: int = 128,
    db_mask=None,  # (n,) bool HOST array — tombstones
    *,
    scan_block: int | None = None,  # inner chunk-scan block (>0 or None)
    n_valid: int | None = None,
    summary: BlockSummary | None = None,
    prune: bool | None = None,
    chunk_rows: int = OUTOFCORE_CHUNK_ROWS,
) -> NNSResult:
    """Fixed-radius NNS over a host-resident (memmapped) signature DB.

    The scan itself is already O(q*K) resident; this entry removes the
    last O(n) residency — the DB — by gathering only summary blocks at
    least one query admits, one fixed-size chunk per jitted call. Blocks
    every query prunes never have their memmap pages touched, so the
    process RSS tracks the admitted working set, not the catalog size.
    Results (including `blocks_touched`) are bit-identical to the resident
    streaming scan with the same mask/summary: admitted blocks are scanned
    in full with genuine rows, ascending disjoint row ranges merge exactly
    (`merge_chunk_buffers`), and prune soundness makes scanning a block
    some queries pruned a no-op for those queries. `fixed_radius_nns`
    routes here automatically when `db_sigs` is an `np.memmap`. The
    `superblock` knob does not apply (chunks are far below key capacity).
    """
    block = DEFAULT_SCAN_BLOCK if not scan_block else scan_block
    prune_np = blocks_touched = block_rows = None
    if summary is not None and prune is not False:
        pm, blocks_touched = _prune_mask_jit(
            jnp.asarray(query_sigs), summary, radius)
        prune_np = np.asarray(pm)
        block_rows = summary.block_rows
    indices, distances, counts = ops.streaming_nns_outofcore(
        query_sigs, db_sigs, radius=radius, max_candidates=max_candidates,
        scan_block=block, n_valid=n_valid, db_mask=db_mask,
        prune_blocks=prune_np, prune_block_rows=block_rows,
        chunk_rows=chunk_rows)
    return NNSResult(indices=indices, distances=distances, counts=counts,
                     blocks_touched=blocks_touched)


# pre-jitted entry for the scan: knobs that fix shapes/plans are static,
# signatures and n_valid stay traced, so repeat calls at one batch shape
# never retrace in the caller
_fixed_radius_nns_jit = jax.jit(
    fixed_radius_nns,
    static_argnames=("radius", "max_candidates", "scan_block", "superblock",
                     "prune"))


def fixed_radius_nns_async(
    query_sigs: jax.Array,  # (q, words) uint32
    db_sigs: jax.Array,  # (n, words) uint32
    radius: int,
    max_candidates: int = 128,
    db_mask: jax.Array | None = None,
    *,
    scan_block: int | None = None,
    n_valid: jax.Array | int | None = None,
    superblock: int | None = None,
    summary: BlockSummary | None = None,
    prune: bool | None = None,
) -> NNSResult:
    """Non-blocking filtering scan: dispatch and return device futures.

    Same arguments and bit-identical results as `fixed_radius_nns`, but
    the call never synchronizes with the host: it dispatches one pre-jitted
    scan (dense or streaming per `scan_block`) and immediately returns an
    `NNSResult` of in-flight device arrays. Callers overlap host work (or
    further dispatches) with the scan and pay the sync only when they read
    a result — e.g. `np.asarray(res.indices)` or `jax.block_until_ready`.
    This is the entry the pipelined `serving.AsyncServer` pattern builds
    on; use it directly when driving the scan outside an engine.
    """
    return _fixed_radius_nns_jit(
        query_sigs, db_sigs, radius=radius, max_candidates=max_candidates,
        db_mask=db_mask, scan_block=scan_block, n_valid=n_valid,
        superblock=superblock, summary=summary, prune=prune)


def _pad_queries_to_axis(mesh, query_axis, query_sigs):
    """Pad the query batch to a multiple of the query-axis size.

    Returns (padded queries, pad count); `_slice_query_pad` undoes it on
    the result so pad rows never leave the shard_map.
    """
    q = query_sigs.shape[0]
    pad = (-q) % mesh.shape[query_axis]
    if pad:
        query_sigs = jnp.pad(query_sigs, ((0, pad), (0, 0)))
    return query_sigs, pad


def _slice_query_pad(res: NNSResult, pad: int) -> NNSResult:
    if not pad:
        return res
    q = res.counts.shape[0] - pad
    bt = None if res.blocks_touched is None else res.blocks_touched[:q]
    return NNSResult(indices=res.indices[:q], distances=res.distances[:q],
                     counts=res.counts[:q], blocks_touched=bt)


def sharded_fixed_radius_nns(
    mesh: jax.sharding.Mesh,
    axis: str,
    query_sigs: jax.Array,  # (q, words) replicated (or query-sharded)
    db_sigs: jax.Array,  # (n, words) row-sharded over `axis`
    radius: int,
    max_candidates: int = 128,
    n_valid: int | None = None,  # rows >= n_valid are padding, never match
    *,
    scan_block: int | None = None,  # forwarded to the per-shard scan
    query_axis: str | None = None,  # also shard queries over this mesh axis
    superblock: int | None = None,  # forwarded to the streaming scan
    db_mask: jax.Array | None = None,  # (n,) bool, row-sharded like db_sigs
    summary: BlockSummary | None = None,  # block summary over the padded DB
    prune: bool | None = None,  # None=auto, False=off
):
    """Fixed-radius NNS with the item DB sharded across the mesh.

    Each shard = one "bank" scanning its rows in parallel; per-shard bounded
    candidates (local priority encode) are all-gathered and re-selected.
    Within a shard the scan routes dense vs streaming via `scan_block`
    exactly like `fixed_radius_nns`, so sharding-over-devices composes with
    streaming-within-shard. Returned indices are global row ids. `n_valid`
    lets callers pad the DB to a multiple of the shard count without the pad
    rows ever matching; `db_mask` (optional, padded to the same length as
    `db_sigs` by the caller) additionally tombstones arbitrary rows — each
    bank sees its slice of the mask.

    `query_axis` additionally blocks the *query* batch over a second mesh
    axis: each (query-block, bank) device pair scans independently and the
    candidate gather stays confined to the bank axis, composing both
    partitions. Queries are padded to a multiple of the query-axis size and
    the pad rows sliced off the result.

    `summary` (a `BlockSummary` over the padded DB) enables block pruning
    inside each bank when the per-shard scan streams AND the shard size is
    a multiple of `summary.block_rows` (so each bank owns whole summary
    blocks); otherwise it is silently ignored (unpruned scan, no error).
    Per-bank `blocks_touched` counters psum into global per-query counts.
    """
    n = db_sigs.shape[0]
    n_shards = mesh.shape[axis]
    per_shard = n // n_shards
    local_k = min(max_candidates, per_shard)
    n_valid = n if n_valid is None else n_valid
    q_pad = 0
    if query_axis is not None:
        query_sigs, q_pad = _pad_queries_to_axis(mesh, query_axis,
                                                 query_sigs)
    use_prune = (
        summary is not None and prune is not False
        and _plan_streams(per_shard, scan_block)
        and per_shard % summary.block_rows == 0
        and summary.n_blocks * summary.block_rows == n)

    def local_scan(q_local, db_local, *rest):
        rest = list(rest)
        mask_local = rest.pop(0) if db_mask is not None else None
        sum_local = (BlockSummary(*rest, block_rows=summary.block_rows)
                     if use_prune else None)
        shard = jax.lax.axis_index(axis)
        # prefix count of real (non-padding) rows within this shard
        local_valid = jnp.clip(n_valid - shard * per_shard, 0, per_shard)
        res = fixed_radius_nns(q_local, db_local, radius, local_k,
                               scan_block=scan_block, n_valid=local_valid,
                               superblock=superblock, db_mask=mask_local,
                               summary=sum_local,
                               prune=True if use_prune else False)
        gidx = jnp.where(
            res.indices >= 0, res.indices + shard * per_shard, -1
        )
        # gather the bounded buffers from every shard (RSC bus)
        all_idx = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        all_dist = jax.lax.all_gather(res.distances, axis, axis=1, tiled=True)
        counts = jax.lax.psum(res.counts, axis)
        blocks_touched = (jax.lax.psum(res.blocks_touched, axis)
                          if use_prune else None)
        # tiny shards can gather fewer slots than max_candidates: select
        # what exists, pad the rest with (-1, BIG)
        k = min(max_candidates, all_dist.shape[-1])
        neg_top, pos = jax.lax.top_k(-all_dist, k=k)
        dist = -neg_top
        idx = jnp.take_along_axis(all_idx, pos, axis=1)
        idx = jnp.where(dist < BIG, idx, -1)
        if k < max_candidates:
            pad = max_candidates - k
            idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
            dist = jnp.pad(dist, ((0, 0), (0, pad)),
                           constant_values=int(BIG))
        return NNSResult(indices=idx, distances=dist, counts=counts,
                         blocks_touched=blocks_touched)

    q_spec = P(query_axis)  # P(None) == replicated when query_axis is None
    specs_in = (q_spec, P(axis, None))
    args = (query_sigs, db_sigs)
    if db_mask is not None:
        if db_mask.shape[0] != n:
            raise ValueError(
                f"db_mask must be padded like db_sigs: {db_mask.shape[0]} "
                f"!= {n}")
        specs_in = (*specs_in, P(axis))
        args = (*args, db_mask)
    if use_prune:
        # summary arrays row-shard with the DB: each bank sees the summary
        # blocks covering exactly its rows (per_shard % block_rows == 0)
        specs_in = (*specs_in, P(axis, None), P(axis, None), P(axis, None),
                    P(axis, None), P(axis))
        args = (*args, summary.or_sigs, summary.and_sigs, summary.min_pc,
                summary.max_pc, summary.n_alive)
    specs_out = NNSResult(
        indices=q_spec, distances=q_spec, counts=q_spec,
        blocks_touched=q_spec if use_prune else None)
    fn = jax.shard_map(
        local_scan, mesh=mesh, in_specs=specs_in, out_specs=specs_out,
        check_vma=False,
    )
    return _slice_query_pad(fn(*args), q_pad)


def query_parallel_nns(
    mesh: jax.sharding.Mesh,
    query_axis: str,
    query_sigs: jax.Array,  # (q, words) sharded over `query_axis`
    db_sigs: jax.Array,  # (n, words) replicated
    radius: int,
    max_candidates: int = 128,
    *,
    scan_block: int | None = None,  # forwarded to the per-block scan
    n_valid: jax.Array | int | None = None,
    superblock: int | None = None,
    db_mask: jax.Array | None = None,  # (n,) bool, replicated like db_sigs
    summary: BlockSummary | None = None,  # replicated with the catalog
    prune: bool | None = None,  # None=auto, False=off
):
    """Fixed-radius NNS with the QUERY batch sharded over `mesh[query_axis]`.

    The catalog is replicated and every device scans all of it for its own
    query block — the dual of `sharded_fixed_radius_nns`: no cross-device
    candidate gather at all, so it parallelizes the streaming scan across
    host/device cores at zero communication cost. Queries are padded to a
    multiple of the axis size; pad rows are sliced off the result.
    `db_mask` tombstones rows and replicates with the catalog; `summary`
    (replicated too) enables block pruning when the scan streams.
    """
    padded, pad = _pad_queries_to_axis(mesh, query_axis, query_sigs)
    nv = jnp.asarray(
        db_sigs.shape[0] if n_valid is None else n_valid, jnp.int32)
    use_prune = (summary is not None and prune is not False
                 and _plan_streams(db_sigs.shape[0], scan_block))

    def local_scan(q_local, db_local, nv_local, *rest):
        rest = list(rest)
        mask_local = rest.pop(0) if db_mask is not None else None
        sum_local = (BlockSummary(*rest, block_rows=summary.block_rows)
                     if use_prune else None)
        return fixed_radius_nns(q_local, db_local, radius, max_candidates,
                                scan_block=scan_block, n_valid=nv_local,
                                superblock=superblock, db_mask=mask_local,
                                summary=sum_local,
                                prune=True if use_prune else False)

    q_spec = P(query_axis)
    specs_in = (q_spec, P(), P())
    args = (padded, db_sigs, nv)
    if db_mask is not None:
        specs_in = (*specs_in, P())
        args = (*args, db_mask)
    if use_prune:
        specs_in = (*specs_in, P(), P(), P(), P(), P())
        args = (*args, summary.or_sigs, summary.and_sigs, summary.min_pc,
                summary.max_pc, summary.n_alive)
    fn = jax.shard_map(
        local_scan, mesh=mesh, in_specs=specs_in,
        out_specs=NNSResult(indices=q_spec, distances=q_spec, counts=q_spec,
                            blocks_touched=q_spec if use_prune else None),
        check_vma=False,
    )
    return _slice_query_pad(fn(*args), pad)


# ---------------------------------------------------------------------------
# Delta-aware NNS (live catalogs: read-only base + bounded delta shard)
# ---------------------------------------------------------------------------
# empty-delta-slot sentinel: sorts AFTER every real item id, so a delta shard
# kept sorted-by-id has its live slots in a contiguous ascending prefix and
# `searchsorted` membership probes stay valid (serving/catalog.py)
EMPTY_ID = 2**31 - 1


def delta_scan(
    query_sigs: jax.Array,  # (q, words) uint32
    delta_sigs: jax.Array,  # (D, words) uint32 — the delta shard signatures
    delta_ids: jax.Array,  # (D,) int32 — global item id per slot, EMPTY_ID
    radius: int,
    max_candidates: int = 128,
) -> NNSResult:
    """Scan the delta shard; returned indices are GLOBAL item ids.

    The shard is bounded (D rows), so the dense plan is always right.
    Precondition (kept by `serving/catalog.py`): live slots are sorted by
    item id — slot order == id order, so the bounded (distance, slot)
    truncation selects exactly the entries a (distance, id) truncation
    would, and the merge below stays bit-exact vs a from-scratch rebuild.
    Empty slots (`EMPTY_ID`) never match and never count.
    """
    k = min(max_candidates, delta_sigs.shape[0])
    res = fixed_radius_nns(query_sigs, delta_sigs, radius, k,
                           db_mask=delta_ids != EMPTY_ID, scan_block=0)
    gids = jnp.where(res.indices >= 0,
                     delta_ids[jnp.maximum(res.indices, 0)], -1)
    if k < max_candidates:
        pad = max_candidates - k
        gids = jnp.pad(gids, ((0, 0), (0, pad)), constant_values=-1)
        dist = jnp.pad(res.distances, ((0, 0), (0, pad)),
                       constant_values=int(BIG))
        return NNSResult(indices=gids, distances=dist, counts=res.counts)
    return NNSResult(indices=gids, distances=res.distances,
                     counts=res.counts)


def merge_delta_candidates(base: NNSResult, delta: NNSResult,
                           max_candidates: int) -> NNSResult:
    """Merge base-scan and delta-scan candidate buffers, bit-exactly.

    Both buffers carry global item ids; an id appears in at most one of
    them (a base row overwritten by a delta row is tombstoned out of the
    base scan). The exact global order is lexicographic (distance, id) —
    the dense rebuild order — which one stable distance sort alone cannot
    recover from the concatenation, because delta ids (overwrites land
    anywhere in the id space) interleave with base ids. So: pre-permute the
    concatenated buffers into ascending-id order (one stable argsort on id,
    invalid slots pushed to the end), then reuse
    `kernels.streaming_nns.merge_candidate_buffers` — its stable sort on
    distance now breaks ties by ascending id, reproducing the exact
    (distance, id) order. Counts add (the id sets are disjoint).
    """
    from repro.kernels.streaming_nns import merge_candidate_buffers

    ids = jnp.concatenate([base.indices, delta.indices], axis=1)
    dist = jnp.concatenate([base.distances, delta.distances], axis=1)
    order = jnp.argsort(jnp.where(ids < 0, jnp.int32(EMPTY_ID), ids),
                        axis=-1, stable=True)
    ids = jnp.take_along_axis(ids, order, axis=1)
    dist = jnp.take_along_axis(dist, order, axis=1)
    idx, d = merge_candidate_buffers(ids, dist, max_candidates)
    # the bounded delta scans dense (never pruned): the merged result
    # carries the base scan's blocks_touched counter through unchanged
    return NNSResult(indices=idx, distances=d,
                     counts=base.counts + delta.counts,
                     blocks_touched=base.blocks_touched)


def query_parallel_delta_scan(
    mesh: jax.sharding.Mesh,
    query_axis: str,
    query_sigs: jax.Array,  # (q, words) sharded over `query_axis`
    delta_sigs: jax.Array,  # (D, words) replicated
    delta_ids: jax.Array,  # (D,) int32 replicated, EMPTY_ID = free slot
    radius: int,
    max_candidates: int = 128,
) -> NNSResult:
    """`delta_scan` with the QUERY batch sharded over `mesh[query_axis]`.

    The delta shard is bounded and replicated with the catalog, so each
    device scans all of it for its own query block — zero communication,
    the exact dual of `query_parallel_nns`. The scan is per-query
    independent, so results bit-match the replicated `delta_scan` while
    doing 1/P of the work per device instead of all of it on every device
    (the ROADMAP delta-scan sharding item). Queries are padded to a
    multiple of the axis size; pad rows are sliced off the result.
    """
    padded, pad = _pad_queries_to_axis(mesh, query_axis, query_sigs)

    def local_scan(q_local, dsigs, dids):
        return delta_scan(q_local, dsigs, dids, radius, max_candidates)

    q_spec = P(query_axis)
    fn = jax.shard_map(
        local_scan, mesh=mesh, in_specs=(q_spec, P(), P()),
        out_specs=NNSResult(indices=q_spec, distances=q_spec,
                            counts=q_spec, blocks_touched=None),
        check_vma=False,
    )
    return _slice_query_pad(fn(padded, delta_sigs, delta_ids), pad)


def delta_aware_nns(
    query_sigs: jax.Array,  # (q, words) uint32
    db_sigs: jax.Array,  # (n, words) uint32 — read-only base epoch
    delta_sigs: jax.Array,  # (D, words) uint32 — bounded delta shard
    delta_ids: jax.Array,  # (D,) int32 — global ids, EMPTY_ID = free slot
    radius: int,
    max_candidates: int = 128,
    *,
    db_mask: jax.Array | None = None,  # (n,) bool — base tombstones
    scan_block: int | None = None,
    n_valid: jax.Array | int | None = None,
    superblock: int | None = None,
    summary: BlockSummary | None = None,  # block summary over the base
    prune: bool | None = None,
) -> NNSResult:
    """Fixed-radius NNS over (read-only base) + (bounded delta shard).

    The base scans with its usual execution plan (dense / streaming /
    superblocked, with tombstoned rows masked, optionally block-pruned via
    `summary`), the delta scans dense, and one `merge_candidate_buffers`
    reuse fuses the two bounded buffers — results bit-match
    `fixed_radius_nns` over a from-scratch rebuilt table (delta rows folded
    in, tombstones dropped). This is the serving entry the live-catalog
    engine routes through while updates are pending.
    """
    base = fixed_radius_nns(query_sigs, db_sigs, radius, max_candidates,
                            db_mask=db_mask, scan_block=scan_block,
                            n_valid=n_valid, superblock=superblock,
                            summary=summary, prune=prune)
    delta = delta_scan(query_sigs, delta_sigs, delta_ids, radius,
                       max_candidates)
    return merge_delta_candidates(base, delta, max_candidates)


# ---------------------------------------------------------------------------
# Cosine baselines (paper accuracy configs 1 & 2)
# ---------------------------------------------------------------------------
def cosine_topk(
    query_vecs: jax.Array,  # (q, d) f32
    db_vecs: jax.Array,  # (n, d) f32
    k: int,
):
    """Exact cosine top-k (FAISS-equivalent flat search)."""
    qn = query_vecs / jnp.maximum(
        jnp.linalg.norm(query_vecs, axis=-1, keepdims=True), 1e-12
    )
    dn = db_vecs / jnp.maximum(
        jnp.linalg.norm(db_vecs, axis=-1, keepdims=True), 1e-12
    )
    sims = qn @ dn.T
    vals, idx = jax.lax.top_k(sims, k)
    return vals, idx
