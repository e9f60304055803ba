"""The DLRM ranking engine: multi-hot int8 lookups, then the dense stack.

DLRM ranks; it does not filter. A request is one ranking query: `candidates`
samples, each of ``n_dense`` dense features and one id a bag slot (table
i's ``bags[i]`` ids at its slice of ``sum(bags)`` slots). The engine
answers with the `top_k` candidate positions by logit. It serves through
`make_server` like `RecSysEngine`, in every mode, through the front ends'
`ServingEngine` protocol (serving/server.py), in two staged programs:

  * lookup (`dlrm_lookup_step`, program ``_dlrm_lookup_stage``): every
    bag sum-pooled from its int8 table by XLA's gather, a slot at a time,
    with the scaling and sums fused onto it (`bag_sums`):
    (B, C, sum(bags)) ids -> (B, C, tables, d) f32;
  * rank (`dlrm_rank_step`, program ``_dlrm_rank_stage``): the bottom MLP,
    DCNv2's low-rank cross network, the top MLP, and the top-k of each
    request's logits.

The tables are row-wise int8 with f32 scales, quantized at ingestion
(`core.quantization.quantize_rowwise`), and stored back to back: table i
starts at row ``offsets[i]``. The scales are stored 128 to a row
(`PackedRows`), so that a slot's rows and its scales are two row
gathers. There is no hot-row cache and no live catalog on this path: on
the device a cached row saves no bytes. Padding requests hold id 0 in
every slot (a real row, read and discarded).
"""
from __future__ import annotations

import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import quantize_rowwise
from repro.models import recsys as rs
from repro.serving.hot_cache import CacheStats
from repro.serving.server import Dispatched, Field
from repro.utils import cdiv, pytree_dataclass

SCALE_LANES = 128  # scales a row of scales holds


@pytree_dataclass
class PackedRows:
    """Row-wise int8 rows and their f32 scales, the scales packed for
    XLA's row gather.

    ``values`` (rows, d) int8, as `quantize_rowwise` makes them;
    ``scales`` (rows / 128, 128) f32: row ``r``'s scale at
    ``[r // 128, r % 128]``, where `quantize_rowwise` gives (rows, 1).
    The same bytes, so ``nbytes`` are the same.
    """

    values: jax.Array
    scales: jax.Array


@pytree_dataclass(meta_fields=("cfg", "candidates", "top_k"))
class DLRMEngine:
    """A DLRM-DCNv2 ranking engine as a jit-able pytree.

    Array fields: the int8 tables (one `PackedRows`, tables back to back:
    (rows, d) int8 values and (rows / 128, 128) f32 scales) and the dense
    weights; `cfg`, `candidates` and `top_k` are static. Build with
    `from_row_blocks` (float rows streamed in fixed-size blocks, for
    tables too large to hold in float), or `build` (float tables in the
    params, one block).
    """

    table_q: PackedRows
    params: dict  # "bottom", "cross", "top" (models.recsys.init_dlrm_dense)
    cfg: rs.DLRMConfig = None
    candidates: int = 512
    top_k: int = 10

    def __post_init__(self):
        if self.cfg.interaction != "cross":
            raise ValueError("DLRMEngine serves the cross interaction "
                             f"(DCNv2), not {self.cfg.interaction!r}")

    @staticmethod
    def build(params: dict, cfg: rs.DLRMConfig, *, candidates: int = 512,
              top_k: int = 10) -> "DLRMEngine":
        """Quantize the float tables of `params` (``cat_00`` ...) row-wise
        into one int8 array."""
        rows = jnp.concatenate([params["tables"][f"cat_{i:02d}"]
                                for i in range(cfg.n_sparse)])
        dense = {k: v for k, v in params.items() if k != "tables"}
        return DLRMEngine.from_row_blocks(dense, cfg, [(0, rows)],
                                          candidates=candidates, top_k=top_k)

    @staticmethod
    def from_row_blocks(dense: dict, cfg: rs.DLRMConfig,
                        blocks: Iterable, *, candidates: int = 512,
                        top_k: int = 10) -> "DLRMEngine":
        """Ingest the tables from `blocks`: (first row, float rows) pairs
        of one size, covering rows 0 .. sum(cfg.rows) of the tables back
        to back. Each block is quantized on the device into place, so no
        float copy of a table is ever held; rows past the last table's
        end (the last block's tail) are kept and never read. Each block
        is waited for before the next is asked of `blocks`: otherwise
        the host runs ahead and every float block is held at once."""
        values = scales = None
        n_rows = sum(cfg.rows)
        for lo, rows in blocks:
            if values is None:
                size = cdiv(n_rows, rows.shape[0]) * rows.shape[0]
                values = jnp.zeros((size, cfg.embed_dim), jnp.int8)
                scales = jnp.zeros((cdiv(size, SCALE_LANES), SCALE_LANES),
                                   jnp.float32)
            values, scales = _ingest(values, scales, rows, lo)
            del rows
            scales.block_until_ready()
        return DLRMEngine(table_q=PackedRows(values=values, scales=scales),
                          params=dense, cfg=cfg, candidates=candidates,
                          top_k=top_k)

    # ------------------------------------------------------------------
    # the front ends' engine protocol (serving/server.py `ServingEngine`)
    # ------------------------------------------------------------------
    @property
    def request_fields(self) -> tuple[Field, ...]:
        c, cfg = self.candidates, self.cfg
        return (Field("dense", (c, cfg.n_dense), np.float32, 0.0),
                Field("sparse", (c, sum(cfg.bags)), np.int32, 0))

    @property
    def coalesce(self) -> int:
        return 1

    def dispatch(self, batch: dict, acc: CacheStats, *,
                 fused: bool = False) -> Dispatched:
        """`dlrm_lookup_step` -> `dlrm_rank_step`, `fused` or not (there
        is no one-program path). The accumulator passes through: there
        is no cache. The meters are ``dlrm.samples`` (the real requests'
        candidates) and ``dlrm.bag_rows`` (their bag slots); the edge is
        the pooled bags."""
        pooled = dlrm_lookup_step(self, batch)
        items, scores, _, meters = dlrm_rank_step(self, batch, pooled)
        return Dispatched(items=items, scores=scores, meters=meters,
                          acc=acc, edge=pooled)

    def gauges(self) -> dict:
        return {}

    def looked_up_ids(self, queries: list[dict], items) -> np.ndarray:
        """The rows served requests looked up, as ids into the tables
        back to back (the `observer` hook's feed). The items are
        candidate positions, not rows, and are left out."""
        offsets = slot_offsets(self.cfg).astype(np.int64)
        return np.concatenate([(np.asarray(q["sparse"], np.int64)
                                + offsets).reshape(-1) for q in queries])


def slot_offsets(cfg: rs.DLRMConfig) -> np.ndarray:
    """(sum(bags),) int32: the first row of each bag slot's table in the
    back-to-back table array."""
    starts = np.cumsum((0,) + tuple(cfg.rows[:-1]))
    return np.repeat(starts, cfg.bags).astype(np.int32)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _ingest(values, scales, rows, lo):
    """Quantize float `rows` row-wise and write them in place at row `lo`
    of the int8 tables, and their scales at flat position `lo`."""
    q = quantize_rowwise(rows)
    flat = jax.lax.dynamic_update_slice(scales.reshape(-1),
                                        q.scales.reshape(-1), (lo,))
    return (jax.lax.dynamic_update_slice(values, q.values, (lo, 0)),
            flat.reshape(scales.shape))


def bag_sums(values: jax.Array, scales: jax.Array, ids: jax.Array,
             bags: tuple) -> jax.Array:
    """Fixed-size multi-hot int8 bags by XLA's gather and sum, a slot at a
    time: (n, d) int8 rows, (n / 128, 128) f32 scales (`PackedRows`),
    (N, sum(bags)) row ids, bag after bag -> (N, len(bags), d) f32. Bag j
    of sample i is the sum, added one slot after the other, of its rows'
    int8 values times their row scales.

    Id ``r``'s scale is lane ``r % 128`` of scale row ``r // 128``. Each
    slot gathers its own N rows and scale rows, small enough for XLA to
    keep in VMEM, and XLA fuses the scaling and sums onto them: no f32
    copy of the gathered rows is written. On a v5e a gather costs about
    the same a row whether the row holds 4 B or 512 B, and slot-sized
    gathers cost less a row than one gather of every slot (PERF.md §6).

    Not `kernels.embedding_pool`, which DMAs a 128-row tile a bag slot. A
    Pallas kernel of row-granular DMAs measured 2.3 times slower than
    XLA's gather of int8 rows on a v5e, and a Pallas pass pooling XLA's
    gathered rows slower than this (PERF.md), so no kernel is registered
    for this op."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SCALE_LANES), 1)

    def slot(j):
        r = ids[:, j]
        scale = jnp.sum(jnp.where(lane == (r % SCALE_LANES)[:, None],
                                  scales[r // SCALE_LANES], 0.0),
                        axis=-1, keepdims=True)
        return values[r].astype(jnp.float32) * scale

    out, lo = [], 0
    for n in bags:
        acc = slot(lo)
        for j in range(lo + 1, lo + n):
            acc = acc + slot(j)
        out.append(acc)
        lo += n
    return jnp.stack(out, axis=1)


def _dlrm_lookup_stage(engine: DLRMEngine, batch: dict) -> jax.Array:
    """Stage 1: (B, C, sum(bags)) ids -> (B, C, tables, d) pooled bags."""
    with jax.named_scope("lookup"):
        ids = batch["sparse"] + slot_offsets(engine.cfg)
        b, c, s = ids.shape
        pooled = bag_sums(engine.table_q.values, engine.table_q.scales,
                          ids.reshape(b * c, s), engine.cfg.bags)
        return pooled.reshape(b, c, *pooled.shape[1:])


def _dlrm_rank_stage(engine: DLRMEngine, batch: dict, pooled: jax.Array):
    """Stage 2: bottom MLP, interaction, top MLP, top-k of each request's
    logits -> (items (B, top_k) candidate positions, scores (B, top_k)
    logits, logits (B, C), meters)."""
    cfg, p = engine.cfg, engine.params
    b, c = pooled.shape[:2]
    with jax.named_scope("bottom"):
        dense = rs._mlp_apply(p["bottom"], batch["dense"], final_act=True)
    with jax.named_scope("cross"):
        x0 = jnp.concatenate([dense, pooled.reshape(b, c, -1)], axis=-1)
        x = rs.cross_net(p["cross"], x0)
    with jax.named_scope("top"):
        logits = rs._mlp_apply(p["top"], x)[..., 0]
        scores, items = jax.lax.top_k(logits, engine.top_k)
    samples = jnp.sum(batch["valid"], dtype=jnp.int32) * c
    meters = {"dlrm.samples": samples,
              "dlrm.bag_rows": samples * sum(cfg.bags)}
    return items.astype(jnp.int32), scores, logits, meters


dlrm_lookup_step = jax.jit(_dlrm_lookup_stage)
dlrm_rank_step = jax.jit(_dlrm_rank_stage)
