"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

These are the ground truth for the per-kernel allclose tests and the CPU
execution path of the framework (kernels/ops.py dispatches here when not on
TPU). They are written for clarity first, but the blocked attention variant
is production-grade (online softmax, O(S) memory) because it is the actual
CPU/compile-time path for 32k prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.streaming_nns import (
    BIG_DIST,
    big_key,
    key_shift,
    merge_candidate_buffers,
    pack_key,
    superblock_rows,
    unpack_key,
)


# ---------------------------------------------------------------------------
# Embedding pool (iMARS CMA RAM-mode lookup + in-memory adder pooling)
# ---------------------------------------------------------------------------
def embedding_pool_ref(
    table_values: jax.Array,  # (n, d) int8
    table_scales: jax.Array,  # (n, 1) f32
    ids: jax.Array,  # (B, L) int32, -1 = padding
    weights: jax.Array | None = None,  # (B, L) f32
) -> jax.Array:
    """Fused int8 dequant-gather-pool -> (B, d) f32.

    The slot contraction runs at full f32 precision (a TPU matmul would
    otherwise round its inputs to bf16), so a single-slot bag is exactly
    row * scale * weight — the kernel's value bit for bit.
    """
    valid = (ids >= 0).astype(jnp.float32)
    safe_ids = jnp.maximum(ids, 0)
    rows = table_values[safe_ids].astype(jnp.float32)  # (B, L, d)
    scales = table_scales[safe_ids]  # (B, L, 1)
    w = valid if weights is None else weights * valid
    return jnp.einsum("bld,bl->bd", rows * scales, w,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Hamming distance (iMARS TCAM threshold search)
# ---------------------------------------------------------------------------
def hamming_distance_ref(queries: jax.Array, db: jax.Array) -> jax.Array:
    """queries (q, w) uint32, db (n, w) uint32 -> (q, n) int32 distances."""
    x = jnp.bitwise_xor(queries[:, None, :], db[None, :, :])
    return jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Streaming fixed-radius NNS (iMARS TCAM search + priority encoder, fused)
# ---------------------------------------------------------------------------
def streaming_nns_ref(
    queries: jax.Array,  # (q, w) uint32
    db: jax.Array,  # (n, w) uint32
    radius: int,
    max_candidates: int,
    *,
    scan_block: int = 4096,
    n_valid: jax.Array | int | None = None,
    superblock: int | None = None,  # rows per superblock (testing override)
    db_mask: jax.Array | None = None,  # (n,) bool — 0/False rows never match
    prune_blocks: jax.Array | None = None,  # (q, nb) bool — True = skip block
    prune_block_rows: int | None = None,  # rows per summary block
):
    """`lax.scan`-chunked streaming NNS oracle, O(q * max_candidates) memory.

    Bit-matches the dense path (hamming_distance_ref -> threshold -> top_k):
    returns (indices, distances, counts) with the `max_candidates` nearest
    matches per query sorted by (distance, index), padded with (-1, 2**30).
    Candidates are tracked as packed int32 keys `dist << shift | row` (see
    kernels/streaming_nns.py for the encoding) so one top_k per chunk merges
    the running buffer with the chunk's matches exactly.

    Mirrors the kernel's wide-key scheme: DBs larger than the packed-key
    capacity scan as superblocks of `superblock_rows` rows each, whose row
    bits hold superblock-local offsets; global ids are reconstructed from
    the superblock offset and the per-superblock top-K buffers are merged
    with one stable sort on distance (`merge_candidate_buffers`). No row
    cap remains beyond int32 indexing. `db_mask` mirrors the kernel's
    optional row-eligibility operand (live-catalog tombstones): masked
    rows never match and never count.

    `prune_blocks` ((q, nb) bool, `prune_block_rows` rows per summary
    block) mirrors the kernel's block-pruning cells (core.nns
    `BlockSummary` bounds): a scan chunk whose rows are all inside blocks
    pruned for EVERY query is skipped via `lax.cond` — zero distance work —
    which cannot change outputs because the bound is sound (pruned blocks
    hold no within-radius rows for any query). Rows beyond the summary's
    coverage are always scanned.
    """
    q, words = queries.shape
    n = db.shape[0]
    shift = key_shift(words)  # the one key encoding, shared with the kernel
    big = big_key(words)
    sb_rows = superblock_rows(words, superblock=superblock)
    limit = jnp.minimum(
        jnp.asarray(n if n_valid is None else n_valid, jnp.int32), n)

    row_needed = None
    if prune_blocks is not None:
        # per-row "some query still needs this row": expand the per-block
        # mask (ORed over queries) by block_rows, pad uncovered tail rows
        # with True (a stale/short summary is sound, never wrong)
        needed_b = jnp.any(jnp.logical_not(prune_blocks), axis=0)  # (nb,)
        cover = needed_b.shape[0] * int(prune_block_rows)
        row_needed = jnp.repeat(needed_b, int(prune_block_rows))
        if cover < n:
            row_needed = jnp.concatenate(
                [row_needed, jnp.ones((n - cover,), jnp.bool_)])
        else:
            row_needed = row_needed[:n]

    def scan_superblock(db_s, limit_s, mask_s, needed_s):
        """One packed-key lax.scan over <= sb_rows rows -> ((q, K), (q,))."""
        n_s = db_s.shape[0]
        # chunks never need to exceed the superblock: an oversized
        # scan_block would round the padding up to itself and scan the
        # (all-masked) pad rows too
        block = max(1, min(scan_block, n_s))
        n_blocks = max(1, -(-n_s // block))
        pad = n_blocks * block - n_s
        db_p = jnp.pad(db_s, ((0, pad), (0, 0))) if pad else db_s
        blocks = db_p.reshape(n_blocks, block, words)
        if mask_s is None:
            mask_blocks = jnp.ones((n_blocks, 1), jnp.bool_)  # broadcast no-op
        else:
            mask_p = jnp.pad(mask_s, (0, pad)) if pad else mask_s
            mask_blocks = mask_p.reshape(n_blocks, block).astype(jnp.bool_)

        def scan_chunk(keys, counts, db_blk, mask_blk, j):
            d = hamming_distance_ref(queries, db_blk)  # (q, block)
            lidx = j * block + jnp.arange(block, dtype=jnp.int32)
            within = jnp.logical_and(d <= radius, (lidx < limit_s)[None, :])
            within = jnp.logical_and(within, mask_blk[None, :])
            counts = counts + jnp.sum(within, axis=-1).astype(jnp.int32)
            new_keys = jnp.where(
                within, pack_key(d, lidx[None, :], words), big)
            merged = jnp.concatenate([keys, new_keys], axis=1)
            neg_top, _ = jax.lax.top_k(-merged, max_candidates)
            return -neg_top, counts

        if needed_s is None:
            def step(carry, blk):
                db_blk, mask_blk, j = blk
                return scan_chunk(*carry, db_blk, mask_blk, j), None

            xs = (blocks, mask_blocks,
                  jnp.arange(n_blocks, dtype=jnp.int32))
        else:
            needed_p = (jnp.pad(needed_s, (0, pad)) if pad else needed_s)
            chunk_needed = jnp.any(
                needed_p.reshape(n_blocks, block), axis=1)

            def step(carry, blk):
                db_blk, mask_blk, needed, j = blk
                # pruned chunk: the sound bound guarantees zero matches
                # here, so skipping is a pure execution shortcut
                return jax.lax.cond(
                    needed,
                    lambda c: scan_chunk(*c, db_blk, mask_blk, j),
                    lambda c: c, carry), None

            xs = (blocks, mask_blocks, chunk_needed,
                  jnp.arange(n_blocks, dtype=jnp.int32))

        keys0 = jnp.full((q, max_candidates), big, jnp.int32)
        counts0 = jnp.zeros((q,), jnp.int32)
        (keys, counts), _ = jax.lax.scan(step, (keys0, counts0), xs)
        return keys, counts

    all_idx, all_dist = [], []
    counts = jnp.zeros((q,), jnp.int32)
    for off in range(0, max(n, 1), sb_rows):
        db_s = db[off:off + sb_rows]
        keys, cnt = scan_superblock(
            db_s, jnp.clip(limit - off, 0, db_s.shape[0]),
            None if db_mask is None else db_mask[off:off + sb_rows],
            None if row_needed is None else row_needed[off:off + sb_rows])
        dist, local = unpack_key(keys, words)
        valid = keys < big
        all_idx.append(jnp.where(valid, local + off, -1))
        all_dist.append(jnp.where(valid, dist, jnp.int32(BIG_DIST)))
        counts = counts + cnt
    if len(all_idx) == 1:
        return all_idx[0], all_dist[0], counts
    indices, distances = merge_candidate_buffers(
        jnp.concatenate(all_idx, axis=1), jnp.concatenate(all_dist, axis=1),
        max_candidates)
    return indices, distances, counts


# ---------------------------------------------------------------------------
# int8 matmul (iMARS crossbar MVM analogue)
# ---------------------------------------------------------------------------
def int8_matmul_ref(
    x: jax.Array,  # (m, k) int8
    w: jax.Array,  # (k, n) int8
    x_scale: jax.Array,  # (m, 1) f32
    w_scale: jax.Array,  # (1, n) f32
) -> jax.Array:
    acc = jax.lax.dot_general(
        x,
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * x_scale * w_scale


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,  # (b, h, sq, d)
    k: jax.Array,  # (b, h, sk, d)
    v: jax.Array,  # (b, h, sk, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Full-materialization softmax attention (oracle)."""
    d = q.shape[-1]
    scale = (d**-0.5) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        rows = jnp.arange(sq)[:, None] + q_offset
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(cols <= rows, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def blocked_attention_ref(
    q: jax.Array,  # (b, h, sq, d)
    k: jax.Array,  # (b, h, sk, d)
    v: jax.Array,  # (b, h, sk, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    block_k: int = 1024,
) -> jax.Array:
    """Online-softmax attention, O(sq * block_k) memory (flash-style, pure jnp).

    This is the production CPU/lowering path for long sequences; it is also
    the numerical contract the Pallas flash kernel must match.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = (d**-0.5) if scale is None else scale
    qf = q.astype(jnp.float32) * scale

    n_blocks = -(-sk // block_k)
    pad = n_blocks * block_k - sk
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, 0), (0, pad), (0, 0)))
    kf = kf.reshape(b, h, n_blocks, block_k, d)
    vf = vf.reshape(b, h, n_blocks, block_k, d)

    rows = jnp.arange(sq)[:, None] + q_offset  # (sq, 1)

    def body(carry, blk):
        m_prev, l_prev, acc_prev = carry
        kb, vb, blk_idx = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)  # (b,h,sq,block_k)
        cols = blk_idx * block_k + jnp.arange(block_k)[None, :]
        mask = cols <= rows if causal else (cols < sk)
        # always mask k-padding
        mask = jnp.logical_and(mask, cols < sk)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # guard -inf rows (no valid key yet)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, -jnp.inf))
        alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc_prev * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, h, sq, d), dtype=jnp.float32)
    kb = jnp.moveaxis(kf, 2, 0)  # (n_blocks, b, h, block_k, d)
    vb = jnp.moveaxis(vf, 2, 0)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # (b, h, 1, d)
    k: jax.Array,  # (b, h, s, d)
    v: jax.Array,  # (b, h, s, d)
    length_mask: jax.Array | None = None,  # (b, s) bool — valid cache slots
    scale: float | None = None,
) -> jax.Array:
    d = q.shape[-1]
    scale = (d**-0.5) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if length_mask is not None:
        s = jnp.where(length_mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
