"""Docs stay alive: the public serving/NNS API surface must carry real
docstrings, and every path referenced from docs/*.md + ROADMAP.md must
exist (tools/check_docs.py — also a CI step)."""
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _public_api():
    """(name, object) pairs whose docstrings the docs sweep guarantees."""
    from repro import obs
    from repro.core import nns
    from repro.kernels import ops
    from repro.obs import registry as obs_registry
    from repro.obs import tracing as obs_tracing
    from repro.serving import (
        AsyncServer,
        ConcurrentFrontend,
        DLRMEngine,
        DeltaShard,
        Dispatched,
        Field,
        LiveCatalog,
        LoadGen,
        MicroBatcher,
        RecSysEngine,
        Server,
        async_server,
        batcher,
        catalog,
        filter_step,
        frontend,
        hot_cache,
        load_gen,
        lookup_step,
        make_server,
        rank_stage_step,
        rank_step,
        recsys_engine,
        scan_step,
        ServingEngine,
        dlrm_engine,
        dlrm_lookup_step,
        dlrm_rank_step,
        serve_step,
        server,
        stats_view,
        summarize_trace,
    )

    return [
        # observability layer
        ("obs", obs),
        ("obs.registry", obs_registry),
        ("obs.tracing", obs_tracing),
        ("MetricsRegistry", obs.MetricsRegistry),
        ("MetricsRegistry.count", obs.MetricsRegistry.count),
        ("MetricsRegistry.observe", obs.MetricsRegistry.observe),
        ("MetricsRegistry.gauge", obs.MetricsRegistry.gauge),
        ("MetricsRegistry.event", obs.MetricsRegistry.event),
        ("MetricsRegistry.register_collector",
         obs.MetricsRegistry.register_collector),
        ("MetricsRegistry.snapshot", obs.MetricsRegistry.snapshot),
        ("MetricsRegistry.to_prometheus", obs.MetricsRegistry.to_prometheus),
        ("TicketTrace", obs.TicketTrace),
        ("stage_durations", obs.stage_durations),
        ("well_ordered", obs.well_ordered),
        ("dump_trace", obs.dump_trace),
        ("stats_view", stats_view),
        ("MicroBatcher.snapshot", MicroBatcher.snapshot),
        ("MicroBatcher.take_trace", MicroBatcher.take_trace),
        # modules
        ("serving.batcher", batcher),
        ("serving.async_server", async_server),
        ("serving.recsys_engine", recsys_engine),
        ("serving.hot_cache", hot_cache),
        ("serving.catalog", catalog),
        ("core.nns", nns),
        ("kernels.ops", ops),
        # live catalog subsystem
        ("LiveCatalog", LiveCatalog),
        ("LiveCatalog.attach", LiveCatalog.attach),
        ("LiveCatalog.apply_updates", LiveCatalog.apply_updates),
        ("LiveCatalog.upsert", LiveCatalog.upsert),
        ("LiveCatalog.delete", LiveCatalog.delete),
        ("LiveCatalog.compact", LiveCatalog.compact),
        ("LiveCatalog.snapshot", LiveCatalog.snapshot),
        ("LiveCatalog.restore", LiveCatalog.restore),
        ("DeltaShard", DeltaShard),
        ("catalog.materialize", catalog.materialize),
        ("catalog.rebuild_reference", catalog.rebuild_reference),
        ("catalog.engine_apply_updates", catalog.engine_apply_updates),
        ("catalog.compact_engine", catalog.compact_engine),
        ("core.nns.delta_aware_nns", nns.delta_aware_nns),
        ("core.nns.delta_scan", nns.delta_scan),
        ("core.nns.merge_delta_candidates", nns.merge_delta_candidates),
        ("hot_cache.invalidate_rows", hot_cache.invalidate_rows),
        ("hot_cache.pin_rows", hot_cache.pin_rows),
        ("MicroBatcher.swap_engine", MicroBatcher.swap_engine),
        ("RecSysEngine.live", RecSysEngine.live),
        ("RecSysEngine.apply_updates", RecSysEngine.apply_updates),
        ("RecSysEngine.compact", RecSysEngine.compact),
        # engine + methods
        ("RecSysEngine", RecSysEngine),
        ("RecSysEngine.build", RecSysEngine.build),
        ("RecSysEngine.shard", RecSysEngine.shard),
        ("RecSysEngine.serve", RecSysEngine.serve),
        ("RecSysEngine.filter_stage", RecSysEngine.filter_stage),
        ("RecSysEngine.rank_stage", RecSysEngine.rank_stage),
        # batching front-ends
        ("MicroBatcher", MicroBatcher),
        ("MicroBatcher.submit", MicroBatcher.submit),
        ("MicroBatcher.result", MicroBatcher.result),
        ("MicroBatcher.serve_many", MicroBatcher.serve_many),
        ("MicroBatcher.flush", MicroBatcher.flush),
        ("AsyncServer", AsyncServer),
        ("AsyncServer.flush", AsyncServer.flush),
        ("AsyncServer.in_flight", AsyncServer.in_flight.fget),
        # the unified Server API + concurrent tier + load harness
        ("serving.server", server),
        ("serving.frontend", frontend),
        ("serving.load_gen", load_gen),
        ("Server", Server),
        ("make_server", make_server),
        ("MicroBatcher.close", MicroBatcher.close),
        ("MicroBatcher.stats", MicroBatcher.stats),
        ("ConcurrentFrontend", ConcurrentFrontend),
        ("ConcurrentFrontend.submit", ConcurrentFrontend.submit),
        ("ConcurrentFrontend.result", ConcurrentFrontend.result),
        ("ConcurrentFrontend.flush", ConcurrentFrontend.flush),
        ("ConcurrentFrontend.close", ConcurrentFrontend.close),
        ("ConcurrentFrontend.stats", ConcurrentFrontend.stats),
        ("ConcurrentFrontend.swap_engine", ConcurrentFrontend.swap_engine),
        ("ConcurrentFrontend.take_trace", ConcurrentFrontend.take_trace),
        # the serving thread's phase spans + the collector's pauses
        ("PhaseSpans", obs.PhaseSpans),
        ("PhaseSpans.enter", obs.PhaseSpans.enter),
        ("PhaseSpans.chain", obs.PhaseSpans.chain),
        ("phase_spans", obs.phase_spans),
        ("watch_gc", obs.watch_gc),
        ("unwatch_gc", obs.unwatch_gc),
        ("MetricsRegistry.histogram", obs.MetricsRegistry.histogram),
        ("LoadGen", LoadGen),
        ("LoadGen.schedule", LoadGen.schedule),
        ("LoadGen.replay", LoadGen.replay),
        ("summarize_trace", summarize_trace),
        # jitted steps (fused + staged)
        ("serve_step", serve_step),
        ("filter_step", filter_step),
        ("rank_step", rank_step),
        ("lookup_step", lookup_step),
        ("scan_step", scan_step),
        ("rank_stage_step", rank_stage_step),
        # the front ends' engine protocol and the DLRM engine
        ("ServingEngine", ServingEngine),
        ("Field", Field),
        ("Dispatched", Dispatched),
        ("RecSysEngine.dispatch", RecSysEngine.dispatch),
        ("RecSysEngine.request_fields", RecSysEngine.request_fields),
        ("serving.dlrm_engine", dlrm_engine),
        ("DLRMEngine", DLRMEngine),
        ("DLRMEngine.from_row_blocks", DLRMEngine.from_row_blocks),
        ("DLRMEngine.dispatch", DLRMEngine.dispatch),
        ("dlrm_lookup_step", dlrm_lookup_step),
        ("dlrm_rank_step", dlrm_rank_step),
        ("embedding_bag", dlrm_engine.bag_sums),
        ("PackedRows", dlrm_engine.PackedRows),
        # NNS entries
        ("fixed_radius_nns", nns.fixed_radius_nns),
        ("BlockSummary", nns.BlockSummary),
        ("build_block_summary", nns.build_block_summary),
        ("update_block_summary", nns.update_block_summary),
        ("summary_block_bounds", nns.summary_block_bounds),
        ("sharded_fixed_radius_nns", nns.sharded_fixed_radius_nns),
        ("query_parallel_nns", nns.query_parallel_nns),
        ("cosine_topk", nns.cosine_topk),
        # hot cache
        ("build_hot_cache", hot_cache.build_hot_cache),
        ("cached_rows", hot_cache.cached_rows),
        ("cached_embedding_bag", hot_cache.cached_embedding_bag),
        # kernel registry
        ("register_kernel", ops.register_kernel),
        ("dispatch", ops.dispatch),
        ("kernel_mode", ops.kernel_mode),
        ("streaming_nns", ops.streaming_nns),
        ("hamming_distances", ops.hamming_distances),
    ]


@pytest.mark.parametrize("name,obj", _public_api(),
                         ids=[n for n, _ in _public_api()])
def test_public_api_has_docstrings(name, obj):
    """Every public object documents itself: a real docstring, not a stub."""
    doc = getattr(obj, "__doc__", None)
    assert doc and len(doc.strip()) >= 20, (
        f"{name} is public API but has no (or a trivial) docstring")


def test_docs_tree_exists():
    for f in ("ARCHITECTURE.md", "KERNELS.md", "BENCHMARKS.md"):
        assert (REPO / "docs" / f).is_file(), f"docs/{f} missing"


def test_docs_references_resolve():
    """tools/check_docs.py over docs/*.md + ROADMAP.md finds no dangling
    file references (same command the CI docs step runs)."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, f"dangling docs refs:\n{proc.stdout}"


def test_docs_checker_catches_dangling_refs(tmp_path):
    """The checker actually fails on a dead reference (no silent passes)."""
    bad = tmp_path / "BAD.md"
    bad.write_text("see [x](src/repro/does_not_exist.py) and "
                   "`tests/nope_missing.py`\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py"), str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    assert "does_not_exist" in proc.stdout
    assert "nope_missing" in proc.stdout


def test_docs_checker_catches_absolute_paths(tmp_path):
    """Machine-local absolute paths are flagged even when they exist on the
    machine running the checker — they reference an author's box, not the
    repo."""
    bad = tmp_path / "ABS.md"
    bad.write_text("data lives in /tmp/scratch/data and the checkout at "
                   "/home/someone/repo; a URL http://x/usr/share is fine\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py"), str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    assert "/tmp/scratch/data" in proc.stdout
    assert "/home/someone/repo" in proc.stdout
    assert "absolute path" in proc.stdout
    # URLs whose path component merely contains /usr/... are not flagged
    assert "/usr/share" not in proc.stdout
