"""The end-to-end iMARS serving pipeline (paper Fig. 3 computation flow).

Deployment flow (Sec. III-B/C): take a *trained* YoutubeDNN, quantize every
ET to int8 (1a: tables into CMA banks), build 256-bit LSH signatures for the
ItET rows, then per query:

  (1a/1b*) sparse lookups + pooling through the fused int8 kernel path
  (1b/1c)  filtering DNN -> user embedding u_i
  (1d)     fixed-radius Hamming NNS over the ItET signatures -> candidates
  (2a-2d)  ranking: candidate embeddings + ranking UIETs -> CTR per item
  (2e)     CTR-buffer threshold top-k -> final items

Serving architecture (this module + serving/batcher.py + serving/hot_cache.py):

  * `RecSysEngine` is a **registered pytree** — all tables/params/signatures
    are leaves, all scalar knobs (cfg, radius, k, mesh) are static metadata —
    so the whole engine passes through `jax.jit` as a plain argument and
    `serve_step` / `filter_step` / `rank_step` are jit-compiled pure
    functions over it.
  * UIET/ItET lookups go through a `HotRowCache` (RecNMP/MicroRec-style
    top-K hot rows pinned dense f32; cold rows via the int8 `embedding_pool`
    path); measured hit rates ride along in every serve result.
  * The filtering NNS optionally shards `item_sigs` row-wise over a mesh
    axis (`RecSysEngine.shard`): each device scans its bank and bounded
    per-shard candidates are all-gathered + re-selected, the paper's
    priority-encoder + RSC communication pattern.

The engine also composes the hardware cost model per query so every served
batch reports (latency_us, energy_uj) the iMARS fabric would have spent —
the software pipeline and the analytic model stay in lockstep.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.core.embedding import embedding_bag
from repro.core.lsh import lsh_signature, make_lsh_projections
from repro.core.nns import (
    NNS_COUNTERS,
    NNSResult,
    build_block_summary,
    delta_scan,
    fixed_radius_nns,
    merge_delta_candidates,
    query_parallel_delta_scan,
    query_parallel_nns,
    sharded_fixed_radius_nns,
)
from repro.core.quantization import QuantizedTensor, quantize_rowwise
from repro.core.topk import TopKResult, threshold_topk
from repro.models import recsys as rs
from repro.serving.hot_cache import (
    CacheStats,
    HotRowCache,
    build_hot_cache,
    cached_embedding_bag,
    cached_rows,
)
from repro.serving.server import Dispatched, Field
from repro.utils import FrozenMapping, pytree_dataclass


class ServeResult(NamedTuple):
    items: jax.Array  # (B, top_k) final item ids, -1 padded
    topk: TopKResult  # per-candidate CTR top-k
    nns: NNSResult  # filtering-stage candidates
    cost: cm.OpCost  # hardware cost model for this query shape
    stats: CacheStats  # hot-cache hits/lookups for this batch


@pytree_dataclass(meta_fields=(
    "cfg", "radius", "n_candidates", "top_k", "nns_mesh", "nns_axis",
    "scan_block", "nns_query_axis"))
class RecSysEngine:
    """The deployed iMARS pipeline as a jit-able pytree.

    Array fields (quantized tables, LSH signatures, MLP params, hot-row
    caches) are pytree leaves; scalar knobs are static jit metadata:

      * ``radius`` / ``n_candidates`` / ``top_k`` — filtering-NNS radius,
        bounded candidate-set size, and final recommendation count;
      * ``scan_block`` — filtering-stage NNS execution plan: ``None`` routes
        dense vs streaming automatically by catalog size, ``0`` forces the
        dense (q, n) path, a positive value forces the streaming scan with
        that chunk size. A pure execution knob: every plan serves
        bit-identical results (tested);
      * ``nns_mesh`` / ``nns_axis`` / ``nns_query_axis`` — set by
        :meth:`shard`; route the NNS onto a device mesh (bank-sharded DB,
        query-parallel blocks, or both). Also execution-only: sharded
        serving bit-matches local serving.

    Build with :meth:`build` (quantizes a trained YoutubeDNN), distribute
    with :meth:`shard`, serve with :meth:`serve` / `MicroBatcher` /
    `AsyncServer`.
    """

    tables_q: dict  # name -> QuantizedTensor (int8 UIETs)
    item_table_q: QuantizedTensor  # int8 ItET
    genre_table_q: QuantizedTensor
    item_sigs: jax.Array  # (n_items, 8) packed 256-bit LSH signatures
    params: dict  # trained MLP weights (crossbar stack)
    lsh_proj: jax.Array
    item_hot: HotRowCache  # hot ItET rows (history pooling + ranking)
    uiet_hot: dict  # name -> HotRowCache for the user-feature ETs
    # live-catalog state (serving/catalog.py): a bounded DeltaShard overlay
    # of pending item updates + the base-row tombstone mask; None for a
    # frozen engine (zero serving overhead). Both are pytree leaves, so
    # epoch/update swaps never retrace the jitted serve steps.
    delta: object = None  # catalog.DeltaShard | None
    item_mask: jax.Array | None = None  # (n,) bool — alive base rows
    # per-block occupancy summary of item_sigs (core.nns.BlockSummary) —
    # a pytree leaf like delta/item_mask, kept fresh by serving/catalog.py
    # on upsert/delete/compact; streaming scans prune when it is set, and
    # None scans unpruned
    block_summary: object = None  # core.nns.BlockSummary | None
    cfg: rs.YoutubeDNNConfig = None
    radius: int = 96
    n_candidates: int = 50
    top_k: int = 10
    nns_mesh: jax.sharding.Mesh | None = None
    nns_axis: str | None = None
    scan_block: int | None = None  # filtering NNS: None=auto, 0=dense, >0=chunk
    nns_query_axis: str | None = None  # mesh axis scanning query blocks in parallel

    @staticmethod
    def build(params: dict, cfg: rs.YoutubeDNNConfig, *, lsh_bits: int = 256,
              radius: int = 96, n_candidates: int = 50, top_k: int = 10,
              hot_rows: int = 0, item_freqs=None, uiet_freqs: dict | None = None,
              scan_block: int | None = None, key=None) -> "RecSysEngine":
        """Quantize a trained YoutubeDNN into a serving engine.

        hot_rows: capacity of the per-table hot-row caches (0 disables).
        item_freqs / uiet_freqs: lookup-frequency histograms (e.g. bincounts
        over training histories) selecting which rows get pinned.
        scan_block: filtering-stage NNS execution plan — None routes dense vs
        streaming automatically by catalog size, 0 forces the dense (q, n)
        path, a positive value forces the streaming scan with that chunk.
        The engine carries a block summary, so streaming scans prune
        (bit-identical, plus per-query `blocks_touched`); an unpruned
        reference is `dataclasses.replace(engine, block_summary=None)`.
        """
        key = jax.random.key(7) if key is None else key
        # cfg is static jit metadata -> its feature map must be hashable
        if not isinstance(cfg.user_features, FrozenMapping):
            cfg = cfg._replace(user_features=FrozenMapping(cfg.user_features))
        tables_q = {k: quantize_rowwise(v) for k, v in params["tables"].items()}
        item_q = quantize_rowwise(params["item_table"])
        genre_q = quantize_rowwise(params["genre_table"])
        proj = make_lsh_projections(key, cfg.embed_dim, lsh_bits)
        # signatures of the int8-dequantized rows (what the CMA stores)
        from repro.core.quantization import dequantize_rowwise

        sigs = lsh_signature(dequantize_rowwise(item_q), proj)
        uiet_freqs = uiet_freqs or {}
        item_hot = build_hot_cache(item_q, item_freqs, hot_rows)
        uiet_hot = {name: build_hot_cache(tables_q[name],
                                          uiet_freqs.get(name), hot_rows)
                    for name in tables_q}
        return RecSysEngine(
            cfg=cfg, tables_q=tables_q, item_table_q=item_q,
            genre_table_q=genre_q, item_sigs=sigs, params=params,
            lsh_proj=proj, item_hot=item_hot, uiet_hot=uiet_hot,
            block_summary=build_block_summary(sigs),
            radius=radius, n_candidates=n_candidates, top_k=top_k,
            scan_block=scan_block)

    def shard(self, mesh: jax.sharding.Mesh, axis: str | None = None, *,
              query_axis: str | None = None) -> "RecSysEngine":
        """Distribute the filtering-stage NNS over `mesh`.

        `axis` row-shards the signature DB (pads `item_sigs` to a multiple
        of the axis size — pad rows are excluded from matching via
        `n_valid` — and places it with a NamedSharding); `query_axis` scans
        query blocks in parallel over a second mesh axis with each block
        seeing its bank (or, with `axis=None`, the whole replicated
        catalog). Both compose: `shard(mesh, "banks", query_axis="qp")`
        partitions (query block x bank).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        if axis is None and query_axis is None:
            raise ValueError("shard() needs a db axis, a query_axis, or both")
        sigs, mask = self.item_sigs, self.item_mask
        summary = self.block_summary
        if axis is not None:
            n_shards = mesh.shape[axis]
            n = sigs.shape[0]
            pad = (-n) % n_shards
            sigs = jnp.pad(sigs, ((0, pad), (0, 0)))
            if mask is not None:  # tombstones ride the banks (pad rows dead)
                mask = jnp.pad(mask[: n], (0, pad))
            if summary is not None:
                # the summary must cover the PADDED layout so each bank owns
                # whole summary blocks; pad rows are ineligible via n_valid.
                # Misaligned shard sizes drop the summary (unpruned banks —
                # a pure execution fallback, results unchanged).
                br = summary.block_rows
                per_shard = sigs.shape[0] // n_shards
                if per_shard % br == 0:
                    summary = build_block_summary(
                        np.asarray(sigs), br, db_mask=mask, n_valid=n)
                else:
                    summary = None
            sigs = jax.device_put(sigs, NamedSharding(mesh, P(axis, None)))
            if mask is not None:
                mask = jax.device_put(mask, NamedSharding(mesh, P(axis)))
        kw = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        kw.update(item_sigs=sigs, item_mask=mask, block_summary=summary,
                  nns_mesh=mesh, nns_axis=axis, nns_query_axis=query_axis)
        return RecSysEngine(**kw)

    # ------------------------------------------------------------------
    # live-catalog plumbing (serving/catalog.py holds the mechanics)
    # ------------------------------------------------------------------
    def live(self, delta_capacity: int = 1024) -> "RecSysEngine":
        """A live-catalog view of this engine: empty bounded delta shard +
        all-alive tombstone mask (one-time treedef change; see
        `catalog.ensure_live`). Usually reached via `catalog.LiveCatalog`.
        """
        from repro.serving.catalog import ensure_live

        return ensure_live(self, delta_capacity)

    def apply_updates(self, upsert_ids=None, upsert_rows=None,
                      delete_ids=None) -> "RecSysEngine":
        """New engine with the update batch folded into the delta shard
        (upserts re-embed/extend, deletes tombstone; touched rows leave the
        hot cache). The old engine value stays valid — callers swap
        atomically between buckets. See `catalog.engine_apply_updates`."""
        from repro.serving.catalog import engine_apply_updates

        return engine_apply_updates(self, upsert_ids, upsert_rows,
                                    delete_ids)

    def compact(self) -> "RecSysEngine":
        """New-epoch engine with the delta folded into a fresh read-only
        base (sharded engines re-shard onto their mesh). The old epoch
        stays serveable. See `catalog.compact_engine`."""
        from repro.serving.catalog import compact_engine

        return compact_engine(self)

    # ------------------------------------------------------------------
    # thin object API over the jitted pure functions below
    # ------------------------------------------------------------------
    def user_embedding(self, batch: dict) -> jax.Array:
        """(1a)-(1c): quantized lookups/pooling + filtering DNN."""
        u, _, _ = _features(self, batch)
        return u

    def filter_stage(self, batch: dict) -> NNSResult:
        """(1d): fixed-radius Hamming NNS -> candidate item ids."""
        nns, _ = filter_step(self, batch)
        return nns

    def rank_stage(self, batch: dict, cand: jax.Array) -> TopKResult:
        """(2a)-(2e): CTR per candidate + threshold top-k."""
        top, _ = rank_step(self, batch, cand)
        return top

    def serve(self, batch: dict) -> ServeResult:
        """Serve one padded batch through the full query pipeline.

        Args:
          batch: dict with one (B,) int32 array per user feature named in
            ``cfg.user_features``, a (B, L) int32 ``history``, a (B,) int32
            ``genre``, and optionally a (B,) bool ``valid`` row mask
            (rows with ``valid=False`` — or with all ids -1 — are padding:
            they read zero rows, never touch the hot-cache counters, and
            their outputs are discarded by callers).
        Returns:
          ServeResult with (B, top_k) final item ids (-1 padded), the
          per-candidate CTR top-k, the filtering-stage NNS candidates, the
          per-query hardware cost model, and the hot-cache CacheStats for
          this batch. One fused jitted step (`serve_step`); bit-identical
          to running `lookup_step` -> `scan_step` -> `rank_stage_step`.
        """
        items, top, nns, stats = serve_step(self, batch, CacheStats.zero())
        return ServeResult(items=items, topk=top, nns=nns,
                           cost=self.query_cost(), stats=stats)

    # ------------------------------------------------------------------
    # the front ends' engine protocol (serving/server.py `ServingEngine`)
    # ------------------------------------------------------------------
    @property
    def request_fields(self) -> tuple[Field, ...]:
        """One int32 id a user feature (sorted) and the genre, then the
        (history_len,) history; padding rows hold -1 (they read zero
        rows and count no cache lookups)."""
        scalars = (*sorted(self.cfg.user_features), "genre")
        return (*(Field(name, (), np.int32, -1) for name in scalars),
                Field("history", (self.cfg.history_len,), np.int32, -1))

    @property
    def coalesce(self) -> int:
        """Buckets a dispatch carries: the query-mesh axis size of an
        engine sharded with `query_axis=...`, else 1."""
        if self.nns_mesh is None or self.nns_query_axis is None:
            return 1
        return self.nns_mesh.shape[self.nns_query_axis]

    def dispatch(self, batch: dict, acc: CacheStats, *,
                 fused: bool = False) -> Dispatched:
        """Dispatch one padded batch: `lookup_step` -> `scan_step` ->
        `rank_stage_step`, or with `fused` the one program `serve_step`
        (bit-identical). The meters are the scan's counters (`nns.*`);
        the edge is the candidates."""
        if fused:
            items, top, nns, acc = serve_step(self, batch, acc)
        else:
            u, pooled, acc = lookup_step(self, batch, acc)
            nns = scan_step(self, u)
            items, top, acc = rank_stage_step(self, batch, nns.indices, u,
                                              pooled, acc)
        return Dispatched(items=items, scores=top.scores,
                          meters=scan_meters(nns), acc=acc,
                          edge=nns.indices)

    def gauges(self) -> dict:
        return {"nns.summary_blocks": n_summary_blocks(self)}

    @staticmethod
    def looked_up_ids(queries: list[dict], items) -> np.ndarray:
        """The item ids served requests looked up: their history rows
        and the items served back to them (the `observer` hook's feed)."""
        hist = [np.asarray(q["history"], np.int64).reshape(-1)
                for q in queries]
        return np.concatenate(
            hist + [np.asarray(items, np.int64).reshape(-1)])

    # ------------------------------------------------------------------
    # hardware cost accounting (per query)
    # ------------------------------------------------------------------
    def query_cost(self) -> cm.OpCost:
        e2e = cm.end_to_end_movielens(n_candidates=self.n_candidates)
        return cm.OpCost(latency_ns=e2e["imars_latency_us"] * 1e3,
                         energy_pj=e2e["imars_energy_uj"] * 1e6)


# ---------------------------------------------------------------------------
# jit-compiled pure stages over the engine pytree
# ---------------------------------------------------------------------------
def _features(engine: RecSysEngine, batch: dict):
    """Cached lookups + filtering DNN -> (u, pooled_history, CacheStats).

    `batch["valid"]` (optional, (B,) bool) marks real rows; padding rows'
    ids are dropped to -1 so they never count as cache lookups (and read
    zero rows, which the caller discards anyway).
    """
    valid = batch.get("valid")

    def mask(ids):
        if valid is None:
            return ids
        return jnp.where(valid[:, None], ids, -1)

    stats = CacheStats.zero()
    feats = []
    for name in sorted(engine.cfg.user_features.keys()):
        emb, st = cached_embedding_bag(
            engine.uiet_hot.get(name), engine.tables_q[name],
            mask(batch[name][:, None]))
        feats.append(emb)
        stats = stats + st
    # history pooling reads the ITEM table -> must see pending delta rows
    # (a re-embedded item in someone's history pools its new embedding,
    # exactly as a rebuilt engine would)
    pooled, st = cached_embedding_bag(
        engine.item_hot, engine.item_table_q, mask(batch["history"]),
        mode="mean", overlay=engine.delta)
    stats = stats + st
    feats.append(pooled)
    x = jnp.concatenate(feats, axis=-1)
    u = rs._mlp_apply(engine.params["filter_mlp"], x)
    return u, pooled, stats


def _nns(engine: RecSysEngine, q_sigs: jax.Array) -> NNSResult:
    """Filtering scan: routed base scan + (live engines) delta scan + merge.

    The base epoch scans through whichever execution plan the engine is
    configured for, with tombstoned rows masked out; pending delta rows
    scan densely (the shard is bounded) and the two candidate buffers merge
    into the exact rebuilt-table (distance, id) order
    (`core.nns.merge_delta_candidates`).

    Every plan threads the engine's `block_summary` down to the scan:
    streaming plans skip summary blocks whose sound lower bound exceeds
    the radius (bit-identical results, `blocks_touched` counters in the
    NNSResult); dense plans and engines without a summary scan unpruned.
    """
    summary = engine.block_summary
    if engine.nns_mesh is not None and engine.nns_axis is not None:
        base = sharded_fixed_radius_nns(
            engine.nns_mesh, engine.nns_axis, q_sigs, engine.item_sigs,
            engine.radius, engine.n_candidates,
            n_valid=engine.item_table_q.shape[0],
            scan_block=engine.scan_block,
            query_axis=engine.nns_query_axis,
            db_mask=engine.item_mask, summary=summary)
    elif engine.nns_mesh is not None:  # query-parallel only, db replicated
        # n_valid still matters: item_sigs may carry pad rows from an
        # earlier bank-sharded incarnation of this engine
        base = query_parallel_nns(
            engine.nns_mesh, engine.nns_query_axis, q_sigs, engine.item_sigs,
            engine.radius, engine.n_candidates, scan_block=engine.scan_block,
            n_valid=engine.item_table_q.shape[0],
            db_mask=engine.item_mask, summary=summary)
    else:
        base = fixed_radius_nns(q_sigs, engine.item_sigs, engine.radius,
                                engine.n_candidates,
                                scan_block=engine.scan_block,
                                db_mask=engine.item_mask,
                                summary=summary)
    if engine.delta is None or engine.delta.capacity == 0:
        return base
    if engine.nns_mesh is not None and engine.nns_query_axis is not None:
        # mesh plans with a query axis: shard the (per-query independent)
        # delta scan along it too — 1/P of the shard per device instead of
        # every device scanning all of it replicated. Bank-only meshes keep
        # the replicated scan (no query axis to split over).
        pending = query_parallel_delta_scan(
            engine.nns_mesh, engine.nns_query_axis, q_sigs,
            engine.delta.sigs, engine.delta.ids, engine.radius,
            engine.n_candidates)
    else:
        pending = _per_device(
            engine, partial(delta_scan, radius=engine.radius,
                            max_candidates=engine.n_candidates),
            q_sigs, engine.delta.sigs, engine.delta.ids)
    return merge_delta_candidates(base, pending, engine.n_candidates)


def _per_device(engine: RecSysEngine, fn, *args):
    """`fn(*args)` on replicated array inputs, once per device of the
    engine's NNS mesh.

    A program that touches a mesh-placed array spans the whole mesh, and
    XLA cannot partition a Pallas (Mosaic) kernel, so on a sharded engine
    every kernel call outside the scan's own `shard_map` must sit inside
    one: each device then runs the kernel on its full replica. Local
    engines call `fn` directly.
    """
    if engine.nns_mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(fn, mesh=engine.nns_mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(*args)


def _filter_step(engine: RecSysEngine, batch: dict):
    """Features + filtering NNS in one jitted call -> (NNSResult, stats).

    The retrieval-only entry (`hit_rate` evaluation, filter-stage tests);
    the serving path uses `serve_step` or the staged split instead.
    """
    u, _, stats = _features(engine, batch)
    q_sigs = lsh_signature(u, engine.lsh_proj)
    return _nns(engine, q_sigs), stats


def _rank(engine: RecSysEngine, batch: dict, cand: jax.Array,
          u: jax.Array, pooled: jax.Array):
    """CTR + threshold top-k given precomputed user features."""
    valid = batch.get("valid")
    if valid is not None:  # padding rows: no candidate lookups, no stats
        cand = jnp.where(valid[:, None], cand, -1)
    # -1 candidates read zero rows and don't count as lookups; their CTR
    # is masked to -inf below either way. Candidate rows resolve through
    # the delta overlay (pending re-embeds/new items rank on their
    # current rows, not the stale base).
    items, st = cached_rows(engine.item_hot, engine.item_table_q, cand,
                            overlay=engine.delta)
    genre = _per_device(engine, embedding_bag, engine.genre_table_q,
                        batch["genre"][:, None])
    B, N = cand.shape
    ctx = jnp.concatenate([u, genre, pooled], axis=-1)
    x = jnp.concatenate(
        [jnp.broadcast_to(ctx[:, None], (B, N, ctx.shape[-1])), items],
        axis=-1)
    logits = rs._mlp_apply(engine.params["rank_mlp"], x)[..., 0]
    ctr = jax.nn.sigmoid(logits)
    ctr = jnp.where(cand >= 0, ctr, -jnp.inf)  # mask padding candidates
    return threshold_topk(ctr, threshold=0.0, k=engine.top_k), st


def _rank_step(engine: RecSysEngine, batch: dict, cand: jax.Array):
    """Rank externally-supplied candidates -> (TopKResult, stats).

    Recomputes the user features for `batch`; use `rank_stage_step` with
    the outputs of `lookup_step` to avoid the recompute when pipelining.
    """
    u, pooled, stats = _features(engine, batch)
    top, st = _rank(engine, batch, cand, u, pooled)
    return top, stats + st


def _serve_step(engine: RecSysEngine, batch: dict, stats: CacheStats):
    """One fused serving step: features -> NNS -> rank -> final ids.

    `stats` is a running hot-cache hit accumulator; callers jit this with
    the accumulator donated so it updates in place across batches. Composes
    the three stage functions below, so the fused step and the pipelined
    lookup/scan/rank split are the same computation by construction.
    """
    u, pooled, stats = _lookup_stage(engine, batch, stats)
    nns = _scan_stage(engine, u)
    final, top, stats = _rank_stage(engine, batch, nns.indices, u, pooled,
                                    stats)
    return final, top, nns, stats


def _lookup_stage(engine: RecSysEngine, batch: dict, stats: CacheStats):
    """Stage 1 — ET lookups + pooling + filtering DNN.

    Returns (u, pooled, stats'): the user embedding, the pooled history
    (both needed again by the ranking stage), and the donated hot-cache
    accumulator advanced by this batch's feature lookups.
    """
    with jax.named_scope("lookup"):
        u, pooled, st = _features(engine, batch)
        return u, pooled, stats + st


def _scan_stage(engine: RecSysEngine, u: jax.Array) -> NNSResult:
    """Stage 2 — the filtering NNS scan, given stage 1's user embedding.

    LSH-signs `u` and runs the fixed-radius Hamming scan (dense, streaming,
    bank-sharded, or query-parallel per the engine's knobs). Pure function
    of (engine, u): no batch dict, no cache counters — so a caller can keep
    bucket i's scan in flight while bucket i+1 runs `lookup_step`.
    """
    with jax.named_scope("lsh_sign"):
        q_sigs = lsh_signature(u, engine.lsh_proj)
    with jax.named_scope("scan"):
        return _nns(engine, q_sigs)


def _rank_stage(engine: RecSysEngine, batch: dict, cand: jax.Array,
                u: jax.Array, pooled: jax.Array, stats: CacheStats):
    """Stage 3 — rank candidates and pick the final items.

    Takes stage 1's (u, pooled) and stage 2's candidate ids; returns
    (final_items, topk, stats') exactly like the tail of `serve_step`.
    Composing the three stages bit-matches the fused step (tested).
    """
    with jax.named_scope("rank"):
        top, st = _rank(engine, batch, cand, u, pooled)
        final = jnp.where(
            top.indices >= 0,
            jnp.take_along_axis(cand, jnp.maximum(top.indices, 0), 1), -1)
        return final, top, stats + st


filter_step = jax.jit(_filter_step)
rank_step = jax.jit(_rank_step)
serve_step = jax.jit(_serve_step, donate_argnums=(2,))
# the same pipeline split at its stage boundaries, for pipelined serving
# (serving/async_server.py): lookup -> scan -> rank compose to exactly
# serve_step, but each stage dispatches separately so a driver can overlap
# host-side work (and the next bucket's lookup) with an in-flight scan.
lookup_step = jax.jit(_lookup_stage, donate_argnums=(2,))
scan_step = jax.jit(_scan_stage)
rank_stage_step = jax.jit(_rank_stage, donate_argnums=(5,))


def scan_meters(nns: NNSResult) -> dict:
    """The counters a scan's result holds, as registry counters: the
    pruned scan's blocks touched and the queries they are over, and the
    streaming kernel's cells and merge rounds (`core.nns.NNS_COUNTERS`);
    device futures, read once the batch retires."""
    meters = {}
    for name in NNS_COUNTERS:
        value = getattr(nns, name, None)
        if value is None:
            continue
        meters[f"nns.{name}"] = value
        if name == "blocks_touched":
            meters["nns.block_scan_queries"] = int(np.prod(value.shape))
    return meters


def n_summary_blocks(engine: RecSysEngine) -> int:
    """Total block-summary blocks of the engine's catalog (0 when no
    summary is attached — dense plans can't prune). The denominator for
    the ``scan_frac`` telemetry: blocks touched / summary blocks."""
    summary = engine.block_summary
    return 0 if summary is None else int(summary.n_blocks)


def hit_rate(engine: RecSysEngine, data, batch_size: int = 256,
             k: int = 10, mode: str = "lsh", max_users: int | None = None
             ) -> float:
    """YoutubeDNN leave-one-out HR@k over the test labels.

    mode: "fp32" (cosine, fp32 tables), "int8" (cosine over dequantized
    int8), "lsh" (the iMARS fixed-radius Hamming path) — the three accuracy
    configurations of paper Sec. IV-B.

    Evaluation runs through the batched serving path: users are chunked into
    fixed `batch_size` device batches (last chunk padded, results masked) and
    each chunk goes through one jitted retrieval step.
    """
    n = data.n_users if max_users is None else min(max_users, data.n_users)
    hits = 0
    for lo in range(0, n, batch_size):
        hi = min(lo + batch_size, n)
        idx = np.arange(lo, hi)
        # pad to the fixed batch shape so the jitted step compiles once
        pad_idx = np.concatenate(
            [idx, np.full(batch_size - idx.size, idx[-1], idx.dtype)])
        batch = {
            **{k2: jnp.asarray(v[pad_idx]) for k2, v in data.user_feats.items()},
            "history": jnp.asarray(data.histories[pad_idx]),
            "genre": jnp.asarray(data.genres[pad_idx]),
        }
        got = np.asarray(_hr_step(engine, batch, mode, k))[: idx.size]
        labels = data.test_labels[idx]
        hits += int((got == labels[:, None]).any(axis=1).sum())
    return hits / n


@partial(jax.jit, static_argnames=("mode", "k"))
def _hr_step(engine: RecSysEngine, batch: dict, mode: str, k: int):
    """Top-k retrieved item ids (B, k) for one padded batch."""
    from repro.core.nns import cosine_topk
    from repro.core.quantization import dequantize_rowwise

    if mode == "fp32":
        u = rs.user_tower(engine.params, engine.cfg, batch)
        _, top = cosine_topk(u, engine.params["item_table"], k)
        return top
    if mode == "int8":
        u, _, _ = _features(engine, batch)
        _, top = cosine_topk(u, dequantize_rowwise(engine.item_table_q), k)
        return top
    nns, _ = _filter_step(engine, batch)
    return nns.indices[:, :k]
