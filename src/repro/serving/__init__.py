"""Serving: KV caches (bf16 / int8 — the paper's ET quantization applied to
the per-session cache), prefill/decode steps, and the batched RecSys
subsystem (micro-batching queue + hot-row cache + jitted serve step, plus
the pipelined `AsyncServer` that overlaps host-side batching with the
in-flight NNS scan via the staged lookup/scan/rank steps, the threaded
multi-tenant `ConcurrentFrontend` with bounded per-tenant queues and load
shedding, and the `LiveCatalog` versioned embedding store: bounded delta
shard + tombstones + epoch compaction over a read-only base, serving
bit-identically to a from-scratch rebuild while the catalog churns, and
the `TieredCatalog` frequency-tiered out-of-core store: memmapped base
shard + int8 RAM pool + f32 hot cache, migrating rows between tiers at
epoch compaction from measured lookup frequencies, plus the
train-while-serve pair: `OnlineTrainer` folding filtering-model gradient
steps into the live catalog concurrently with serving, and the
`ShadowHarness` freshness oracle asserting live quality tracks a cold
rebuild of the current parameters).

Every front-end implements the one `Server` protocol (submit -> ticket,
result(ticket), flush, close, stats) and is constructed through
`make_server(engine, mode="sync" | "pipelined" | "concurrent", **knobs)`
— see serving/server.py and docs/SERVING.md. The engine is any
`ServingEngine`: the YoutubeDNN `RecSysEngine` or the ranking-only
`DLRMEngine` (DLRM-DCNv2's multi-hot int8 lookups and cross network)."""
from repro.serving.async_server import AsyncServer
from repro.serving.batcher import MicroBatcher, ServedQuery, default_buckets
from repro.serving.frontend import ConcurrentFrontend, TicketTrace
from repro.serving.load_gen import LoadGen, LoadSummary, summarize_trace
from repro.serving.server import (
    STATUS_ERROR,
    STATUS_OK,
    STATUS_SHED,
    Dispatched,
    Field,
    QueueFullError,
    SchemaMismatchError,
    Server,
    ServerClosedError,
    ServerConfigError,
    ServingEngine,
    ServingError,
    make_server,
    stats_view,
)
from repro.serving.dlrm_engine import (
    DLRMEngine,
    dlrm_lookup_step,
    dlrm_rank_step,
)
from repro.serving.catalog import (
    DeltaFullError,
    DeltaShard,
    LiveCatalog,
    compact_engine,
    empty_delta,
    engine_apply_updates,
    engine_refresh_model,
    materialize,
    rebuild_reference,
)
from repro.serving.online import OnlineTrainer
from repro.serving.shadow import (
    ShadowHarness,
    ShadowRecord,
    rebuild_from_params,
)
from repro.serving.hot_cache import (
    CacheStats,
    HotRowCache,
    build_hot_cache,
    cached_embedding_bag,
    invalidate_rows,
    pin_rows,
    top_ids_by_freq,
)
from repro.serving.tiered import (
    BaseShard,
    BaseShardWriter,
    TieredCatalog,
    open_base_shard,
    write_base_shard,
)
from repro.serving.recsys_engine import (
    RecSysEngine,
    ServeResult,
    filter_step,
    hit_rate,
    lookup_step,
    rank_stage_step,
    rank_step,
    scan_step,
    serve_step,
)

__all__ = [
    "STATUS_ERROR",
    "STATUS_OK",
    "STATUS_SHED",
    "AsyncServer",
    "BaseShard",
    "BaseShardWriter",
    "CacheStats",
    "ConcurrentFrontend",
    "DLRMEngine",
    "DeltaFullError",
    "DeltaShard",
    "Dispatched",
    "Field",
    "HotRowCache",
    "LiveCatalog",
    "LoadGen",
    "LoadSummary",
    "MicroBatcher",
    "OnlineTrainer",
    "QueueFullError",
    "RecSysEngine",
    "SchemaMismatchError",
    "ServeResult",
    "ServedQuery",
    "Server",
    "ShadowHarness",
    "ShadowRecord",
    "ServerClosedError",
    "ServerConfigError",
    "ServingEngine",
    "ServingError",
    "TicketTrace",
    "TieredCatalog",
    "build_hot_cache",
    "cached_embedding_bag",
    "compact_engine",
    "default_buckets",
    "dlrm_lookup_step",
    "dlrm_rank_step",
    "empty_delta",
    "engine_apply_updates",
    "engine_refresh_model",
    "filter_step",
    "hit_rate",
    "invalidate_rows",
    "lookup_step",
    "make_server",
    "materialize",
    "open_base_shard",
    "pin_rows",
    "rank_stage_step",
    "rank_step",
    "rebuild_from_params",
    "rebuild_reference",
    "scan_step",
    "serve_step",
    "stats_view",
    "summarize_trace",
    "top_ids_by_freq",
    "write_base_shard",
]
