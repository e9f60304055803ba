"""jit'd public wrappers with a kernel backend registry.

Every kernel is registered once via `register_kernel(name, ref=..., pallas=...)`
and all public ops share one dispatch path instead of copy-pasted mode
branches. Three backends per op:

  * ``pallas``    — compiled Pallas kernel (the TPU fast path)
  * ``interpret`` — the same kernel through the Pallas interpreter
                    (CPU correctness checks of the real kernel code)
  * ``ref``       — the pure-jnp oracle in kernels/ref.py (fast to compile,
                    same numerics; the default CPU execution path)

Mode resolution, most-specific first:

  1. ``REPRO_PALLAS_<OP>`` — per-op override, e.g.
     ``REPRO_PALLAS_STREAMING_NNS=interpret`` or
     ``REPRO_PALLAS_HAMMING_DISTANCES=ref``
  2. ``REPRO_PALLAS`` — global override (``pallas`` | ``interpret`` | ``ref``)
  3. auto: ``pallas`` on TPU backends, ``ref`` everywhere else

Model code always calls the public wrappers below and never cares which
backend ran. Ops with no Pallas implementation fall back to their ref.
"""
from __future__ import annotations

import functools
import math
import mmap
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.embedding_pool import embedding_pool_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hamming_nns import hamming_distances_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.streaming_nns import (
    BIG_DIST,
    merge_chunk_buffers,
    streaming_nns_pallas,
)
from repro.utils import round_up

_MODES = ("pallas", "interpret", "ref")


class KernelOp(NamedTuple):
    ref: Callable
    pallas: Callable | None  # called with an extra interpret= kwarg


_REGISTRY: dict[str, KernelOp] = {}


def register_kernel(name: str, *, ref: Callable,
                    pallas: Callable | None = None) -> None:
    """Register one kernel's backends under `name` (see module docstring)."""
    if name in _REGISTRY:
        raise ValueError(f"kernel {name!r} already registered")
    _REGISTRY[name] = KernelOp(ref=ref, pallas=pallas)


def registered_kernels() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def kernel_mode(name: str) -> str:
    """'pallas' | 'interpret' | 'ref' for op `name` (env overrides, then auto)."""
    for env in (f"REPRO_PALLAS_{name.upper()}", "REPRO_PALLAS"):
        value = os.environ.get(env, "")
        if value in _MODES:
            return value
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def dispatch(name: str, *args, **kwargs):
    """Route one op call to its registered backend for the current mode."""
    op = _REGISTRY[name]
    mode = kernel_mode(name)
    if mode == "ref" or op.pallas is None:
        return op.ref(*args, **kwargs)
    return op.pallas(*args, interpret=(mode == "interpret"), **kwargs)


# ---------------------------------------------------------------------------
# per-op pallas adapters (block sizing + input massaging live here)
# ---------------------------------------------------------------------------
def _hamming_block_n(n: int) -> int:
    """DB-block rows: 1024 cap, 128-lane aligned, never rounded past the
    128-aligned row count (n=300 used to get a 512 block via next-pow2)."""
    return min(1024, max(128, round_up(n, 128)))


def _hamming_pallas(queries, db, *, interpret):
    return hamming_distances_pallas(
        queries, db, block_n=_hamming_block_n(db.shape[0]),
        interpret=interpret)


def _embedding_pool_pallas(table_values, table_scales, ids, weights=None, *,
                           interpret):
    d = table_values.shape[1]
    block_d = d if d <= 512 else 512
    if d % block_d != 0:
        block_d = d  # fall back to unblocked when not divisible
    valid = (ids >= 0).astype(jnp.float32)
    w = valid if weights is None else weights.astype(jnp.float32) * valid
    return embedding_pool_pallas(
        table_values, table_scales, ids, w, block_d=block_d,
        interpret=interpret)


def _flash_attention_pallas(q, k, v, *, causal=True, scale=None, interpret):
    b, h, sq, d = q.shape
    out = flash_attention_pallas(
        q.reshape(b * h, sq, d),
        k.reshape(b * h, k.shape[2], d),
        v.reshape(b * h, v.shape[2], d),
        causal=causal, scale=scale, interpret=interpret)
    return out.reshape(b, h, sq, d)


def _streaming_nns_ref(queries, db, *, radius, max_candidates, scan_block,
                       n_valid, superblock=None, db_mask=None,
                       prune_blocks=None, prune_block_rows=None):
    return ref.streaming_nns_ref(
        queries, db, radius, max_candidates, scan_block=scan_block,
        n_valid=n_valid, superblock=superblock, db_mask=db_mask,
        prune_blocks=prune_blocks, prune_block_rows=prune_block_rows)


# the kernel's merge holds (block_q, k_pad + block_n) int32 keys per round,
# so its VMEM footprint is small; 512 rows bounds the per-round lane
# reductions of a merging block. The `scan_block` knob sizes the *ref*
# lax.scan chunk; the pallas tile is derived independently (128-lane
# aligned, capped) so any host-side chunk — huge or oddly-sized — maps to
# a viable on-chip tile. Results are block-size invariant, so the remap
# never changes output.
_STREAM_PALLAS_MAX_BLOCK_N = 512


def _streaming_nns_pallas(queries, db, *, radius, max_candidates, scan_block,
                          n_valid, superblock=None, db_mask=None,
                          prune_blocks=None, prune_block_rows=None,
                          interpret):
    limit = db.shape[0] if n_valid is None else n_valid
    block_n = min(max(128, round_up(scan_block, 128)),
                  _STREAM_PALLAS_MAX_BLOCK_N)
    if superblock is not None:
        # superblock boundaries must land on block boundaries: lane-align the
        # override, then shrink the tile to a 128-multiple dividing it (any
        # superblock <= capacity yields identical results, so the remap is
        # output-invariant exactly like the scan_block -> block_n remap)
        superblock = max(128, round_up(superblock, 128))
        block_n = math.gcd(block_n, superblock)
    if prune_blocks is not None:
        # summary blocks must cover whole kernel tiles so the per-cell prune
        # mask expands by pure repetition; block_rows is a multiple of 128
        # by construction (core.nns.build_block_summary), so the gcd stays
        # lane-aligned and the remap stays output-invariant
        block_n = math.gcd(block_n, int(prune_block_rows))
    return streaming_nns_pallas(
        queries, db, jnp.asarray(limit, jnp.int32), db_mask, radius=radius,
        max_candidates=max_candidates, block_n=block_n,
        superblock=superblock, prune_blocks=prune_blocks,
        prune_block_rows=prune_block_rows, interpret=interpret)


register_kernel("hamming_distances", ref=ref.hamming_distance_ref,
                pallas=_hamming_pallas)
register_kernel("embedding_pool", ref=ref.embedding_pool_ref,
                pallas=_embedding_pool_pallas)
register_kernel("int8_matmul", ref=ref.int8_matmul_ref,
                pallas=int8_matmul_pallas)
register_kernel("flash_attention", ref=ref.blocked_attention_ref,
                pallas=_flash_attention_pallas)
register_kernel("streaming_nns", ref=_streaming_nns_ref,
                pallas=_streaming_nns_pallas)


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------
def embedding_pool(table_values, table_scales, ids, weights=None):
    """Fused int8 dequant-gather-pool: (n,d) int8 table, (B,L) ids -> (B,d)."""
    return dispatch("embedding_pool", table_values, table_scales, ids, weights)


def hamming_distances(queries, db):
    """(q,w) x (n,w) packed uint32 signatures -> (q,n) int32 distances."""
    return dispatch("hamming_distances", queries, db)


def streaming_nns(queries, db, *, radius, max_candidates,
                  scan_block=4096, n_valid=None, superblock=None,
                  db_mask=None, prune_blocks=None, prune_block_rows=None):
    """Streaming fixed-radius NNS over the full DB, O(q*max_candidates) mem.

    Returns (indices, distances, counts) bit-matching the dense
    hamming_distances -> threshold -> top_k path; `n_valid` (dynamic ok)
    masks trailing padding rows, `scan_block` sets the scan chunk size.
    DBs beyond the packed-key capacity (4.19M rows at 256-bit signatures)
    scan as multiple superblocks transparently; `superblock` shrinks the
    superblock size below capacity (a pure execution knob for tests —
    results are superblock-invariant). `db_mask` ((n,) bool, optional)
    marks per-row eligibility — the tombstone mask of the live-catalog
    layer; False rows never match and never count.

    `prune_blocks` ((q, nb) bool, True = skip) + `prune_block_rows` (rows
    per summary block, a multiple of 128) carry the core `BlockSummary`
    pruning decision: both backends skip chunks/blocks every query prunes
    (lax.cond in the ref, pl.when predication in the kernel). The caller
    (core.nns.fixed_radius_nns) guarantees the mask is sound, so outputs
    stay bit-identical to the unpruned scan on either backend.
    """
    return dispatch("streaming_nns", queries, db, radius=radius,
                    max_candidates=max_candidates, scan_block=scan_block,
                    n_valid=n_valid, superblock=superblock, db_mask=db_mask,
                    prune_blocks=prune_blocks,
                    prune_block_rows=prune_block_rows)


# chunk scans allowed on the async dispatch queue at once; each pins its
# (chunk_rows, words) input buffer until it retires
_OUTOFCORE_INFLIGHT = 2


def madvise_dontneed(arr) -> bool:
    """Drop a memmapped array's resident page cache (MADV_DONTNEED).

    The out-of-core scan copies the pages it needs before scanning, so
    dropping them immediately keeps a shard's resident set at O(one
    gather) instead of accumulating every admitted page across batches.
    No-op (returns False) for plain ndarrays or platforms without
    madvise; the data is never modified, only evicted.
    """
    mm = getattr(arr, "_mmap", None)
    if mm is None or not hasattr(mmap, "MADV_DONTNEED"):
        return False
    try:
        mm.madvise(mmap.MADV_DONTNEED)
        return True
    except (ValueError, OSError):
        return False


def madvise_random(arr) -> bool:
    """Disable kernel readahead on a memmapped array (MADV_RANDOM).

    Scattered candidate-row gathers fault one 4KB page at a time, but a
    default (MADV_NORMAL) mapping pulls up to 128KB of readahead per
    fault — a few thousand scattered faults can drag hundreds of MB of
    dead neighbours into the page cache. Out-of-core access to a shard
    is either scattered (candidate rows) or an explicit block-sized
    gather copy (the streaming scan), so readahead never helps and the
    resident set shrinks ~30x with it off. Same no-op guards as
    `madvise_dontneed`.
    """
    mm = getattr(arr, "_mmap", None)
    if mm is None or not hasattr(mmap, "MADV_RANDOM"):
        return False
    try:
        mm.madvise(mmap.MADV_RANDOM)
        return True
    except (ValueError, OSError):
        return False


@functools.partial(jax.jit,
                   static_argnames=("radius", "max_candidates", "scan_block"))
def _outofcore_chunk_scan(queries, chunk, n_rows, db_mask, row_map, *,
                          radius, max_candidates, scan_block):
    """One resident-chunk scan of the out-of-core driver: the usual
    streaming dispatch plus the local->global row remap (row_map is the
    monotonically increasing gather index, so the remap preserves the
    buffer's (distance, row) sort order)."""
    idx, dist, counts = dispatch(
        "streaming_nns", queries, chunk, radius=radius,
        max_candidates=max_candidates, scan_block=scan_block,
        n_valid=n_rows, db_mask=db_mask)
    gidx = jnp.where(idx >= 0, jnp.take(row_map, jnp.clip(idx, 0, None)), -1)
    return gidx, dist, counts


def streaming_nns_outofcore(queries, db, *, radius, max_candidates,
                            scan_block=4096, n_valid=None, db_mask=None,
                            prune_blocks=None, prune_block_rows=None,
                            chunk_rows=1 << 18):
    """`streaming_nns` over a host-resident (typically `np.memmap`) DB.

    The driver walks the signature DB in admitted summary blocks: blocks
    every query prunes are never gathered, so their memmap pages are never
    touched — the resident set is O(admitted blocks), not O(n). Admitted
    blocks are compacted into fixed-(q, chunk_rows) buffers (zero-padded,
    padding masked ineligible via `n_valid`/`db_mask`) so the whole scan
    compiles once; each buffer holds only genuine DB rows, so no per-query
    prune mask is needed downstream — prune soundness guarantees a pruned
    block contains no matches for that query, hence scanning it anyway is
    a no-op on the output. Per-chunk buffers merge exactly via
    `merge_chunk_buffers` (ascending disjoint row ranges).

    `db`: (n, words) uint32 ndarray/memmap. `db_mask`/`prune_blocks` are
    host arrays. Returns (indices, distances, counts) bit-identical to the
    resident `streaming_nns` with the same mask and a sound prune mask.

    Two bounds keep peak RSS at O(chunk), not O(admitted set): at most
    `_OUTOFCORE_INFLIGHT` chunk scans ride the async dispatch queue (each
    pins its (chunk_rows, words) input buffer until it retires), and a
    memmapped `db`'s page cache is dropped (MADV_DONTNEED) after each
    group's gather copy — the admitted pages of group g are dead the
    moment the copy exists, so they never accumulate across groups or
    batches.
    """
    n = int(db.shape[0])
    q = int(queries.shape[0])
    limit = n if n_valid is None else int(n_valid)
    queries = jnp.asarray(queries)
    mask_np = None if db_mask is None else np.asarray(db_mask, bool)

    if prune_blocks is not None:
        br = int(prune_block_rows)
        prune_np = np.asarray(prune_blocks, bool)
        kept = np.nonzero(~prune_np.all(axis=0))[0]
    else:
        br = max(1, int(chunk_rows))
        kept = np.arange(-(-n // br))
    kept = kept[kept * br < limit]

    if kept.size == 0 or limit <= 0:
        return (jnp.full((q, max_candidates), -1, jnp.int32),
                jnp.full((q, max_candidates), BIG_DIST, jnp.int32),
                jnp.zeros((q,), jnp.int32))

    group = max(1, int(chunk_rows) // br)  # admitted blocks per jit call
    cap = group * br
    chunks, counts = [], jnp.zeros((q,), jnp.int32)
    for g in range(0, kept.size, group):
        blk = kept[g:g + group]
        idx = (blk[:, None] * br + np.arange(br)).reshape(-1)
        within = idx < limit
        idx_c = np.minimum(idx, n - 1)
        rows = np.asarray(db[idx_c])  # memmap gather: pages of kept blocks
        elig = within if mask_np is None else (within & mask_np[idx_c])
        n_rows = rows.shape[0]
        if n_rows < cap:  # final short group: zero-pad to the fixed shape
            rows = np.concatenate(
                [rows, np.zeros((cap - n_rows,) + rows.shape[1:], rows.dtype)])
            elig = np.concatenate([elig, np.zeros(cap - n_rows, bool)])
            idx_c = np.concatenate(
                [idx_c, np.zeros(cap - n_rows, idx_c.dtype)])
        gidx, dist, c = _outofcore_chunk_scan(
            queries, jnp.asarray(rows), jnp.int32(n_rows),
            jnp.asarray(elig), jnp.asarray(idx_c.astype(np.int32)),
            radius=radius, max_candidates=max_candidates,
            scan_block=scan_block)
        del rows
        madvise_dontneed(db)
        chunks.append((gidx, dist))
        counts = counts + c
        if len(chunks) >= _OUTOFCORE_INFLIGHT:
            chunks[-_OUTOFCORE_INFLIGHT][0].block_until_ready()
    gidx, dist = merge_chunk_buffers(chunks, max_candidates)
    return gidx, dist, counts


def int8_matmul(x, w, x_scale, w_scale):
    """int8 (m,k) @ int8 (k,n) with per-row/col f32 scales -> f32 (m,n)."""
    return dispatch("int8_matmul", x, w, x_scale, w_scale)


def flash_attention(q, k, v, *, causal=True, scale=None):
    """(b,h,s,d) attention; flash kernel on TPU, blocked ref elsewhere."""
    return dispatch("flash_attention", q, k, v, causal=causal, scale=scale)
