"""Compile rehearsal for TPU v5e: the main path's kernels at real widths.

Each test lowers and compiles — never runs — a Pallas kernel, or the
paper engine's jitted serve step, for one chip of a described `v5e:2x2`
topology, or a sharded engine's serve steps for all four of its chips. The TPU compiler refuses here what it would refuse on the chip
(block shapes off the (8, 128) tiling, unsupported vector layouts, too
much VMEM or SMEM), so these guard every change at no chip time. A compile
that passes is not a chip run: it says nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library. Code that asks
`jax.default_backend()` still sees the CPU here, so each test selects the
Pallas backend with `REPRO_PALLAS=pallas` for its own compile.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

WORDS = 8  # 256-bit signatures
QUERIES = 256
K = 50
CATALOG = 1 << 23  # two superblocks of 2**22 rows at 256-bit signatures
SUMMARY_ROWS = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's programs can be written to the persistent cache
    # but never read back: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "pallas")


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    """Fresh jit (no trace shared with CPU runs) -> compiled executable,
    checked to hold at least one Mosaic kernel."""
    compiled = jax.jit(lambda *a: fn(*a)).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("masked,pruned", [
    (False, False), (True, False), (False, True), (True, True)],
    ids=["plain", "masked", "pruned", "masked_pruned"])
def test_streaming_nns_compiles_two_superblocks(one_chip, pallas, masked,
                                                pruned):
    specs = [_spec(one_chip, (QUERIES, WORDS), jnp.uint32),
             _spec(one_chip, (CATALOG, WORDS), jnp.uint32)]
    if masked:
        specs.append(_spec(one_chip, (CATALOG,), jnp.bool_))
    if pruned:
        specs.append(_spec(one_chip, (QUERIES, CATALOG // SUMMARY_ROWS),
                           jnp.bool_))

    def scan(q, db, *rest):
        mask = rest[0] if masked else None
        prune = rest[-1] if pruned else None
        return ops.streaming_nns(
            q, db, radius=72, max_candidates=K, db_mask=mask,
            prune_blocks=prune,
            prune_block_rows=SUMMARY_ROWS if pruned else None)

    compiled = _compile(scan, *specs)
    text = compiled.as_text()
    assert "%streaming_nns." in text  # the kernel's name=
    # the kernel's outputs hold the stats tile of each 8-query tile
    tile = rf"s32\[{QUERIES // 8},8,128\]"
    assert re.search(rf"%streaming_nns\.\d+ = \(.*{tile}.*custom-call",
                     text)
    mem = compiled.memory_analysis()
    # the DB is read in place: no relayout copy of the 256 MiB table
    assert mem.temp_size_in_bytes < CATALOG * WORDS * 4 // 8


@pytest.mark.parametrize("slots", [1, 20])
def test_embedding_pool_compiles(one_chip, pallas, slots):
    n, d = 3883, 32
    compiled = _compile(ops.embedding_pool,
                        _spec(one_chip, (n, d), jnp.int8),
                        _spec(one_chip, (n, 1), jnp.float32),
                        _spec(one_chip, (QUERIES, slots), jnp.int32),
                        _spec(one_chip, (QUERIES, slots), jnp.float32))
    assert "%embedding_pool." in compiled.as_text()


def test_hamming_distances_compiles(one_chip, pallas):
    compiled = _compile(ops.hamming_distances,
                        _spec(one_chip, (QUERIES, WORDS), jnp.uint32),
                        _spec(one_chip, (3000, WORDS), jnp.uint32))
    assert "%hamming_distances." in compiled.as_text()


def test_paper_serve_step_compiles(one_chip, pallas):
    """The fused serve step of the paper-sized engine (dense plan: the
    Hamming kernel, plus the genre pool on the ranking path)."""
    from repro.models import recsys as rs
    from repro.serving.hot_cache import CacheStats
    from repro.serving.recsys_engine import RecSysEngine, _serve_step

    cfg = rs.default_youtubednn_config()
    engine = RecSysEngine.build(rs.init_youtubednn(jax.random.key(0), cfg),
                                cfg, n_candidates=K)
    as_spec = lambda x: _spec(one_chip, x.shape, x.dtype)  # noqa: E731
    batch = {name: _spec(one_chip, (QUERIES,), jnp.int32)
             for name in (*cfg.user_features, "genre")}
    batch["history"] = _spec(one_chip, (QUERIES, cfg.history_len), jnp.int32)
    batch["valid"] = _spec(one_chip, (QUERIES,), jnp.bool_)
    compiled = _compile(_serve_step, jax.tree.map(as_spec, engine), batch,
                        jax.tree.map(as_spec, CacheStats.zero()))
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("axis", ["banks", "qp"])
def test_sharded_catalog_steps_compile_on_four_chips(topo, pallas, axis):
    """The staged serve steps of a 2**23-item live catalog engine sharded
    over all four chips of the topology, bank-wise or query-parallel.
    Such a program spans the mesh, and XLA cannot partition a Mosaic
    kernel: every kernel must sit inside a `shard_map` (the scan's own,
    or the per-device wrapper of the off-scan calls)."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.models import recsys as rs
    from repro.serving import recsys_engine as rse
    from repro.serving.hot_cache import CacheStats
    from repro.serving.recsys_engine import RecSysEngine

    cfg = rs.default_youtubednn_config()
    engine = RecSysEngine.build(rs.init_youtubednn(jax.random.key(0), cfg),
                                cfg, n_candidates=K, hot_rows=256).live(1024)
    mesh = make_mesh((4,), (axis,), devices=topo.devices)
    rep = NamedSharding(mesh, P())

    def grown(x, sharding=rep):  # a catalog leaf at 2**23 rows
        rows = CATALOG if x.shape[0] == engine.item_sigs.shape[0] else (
            CATALOG // SUMMARY_ROWS)
        return _spec(sharding, (rows,) + x.shape[1:], x.dtype)

    specs = jax.tree.map(lambda x: _spec(rep, x.shape, x.dtype), engine)
    banked = axis == "banks"
    specs = dataclasses.replace(
        specs,
        item_table_q=jax.tree.map(grown, engine.item_table_q),
        item_sigs=grown(engine.item_sigs, NamedSharding(
            mesh, P("banks", None)) if banked else rep),
        item_mask=grown(engine.item_mask, NamedSharding(
            mesh, P("banks")) if banked else rep),
        block_summary=jax.tree.map(grown, engine.block_summary),
        nns_mesh=mesh, nns_axis="banks" if banked else None,
        nns_query_axis=None if banked else "qp")
    b = QUERIES * (1 if banked else 4)  # qp coalesces one bucket a device
    batch = {name: _spec(rep, (b,), jnp.int32)
             for name in (*cfg.user_features, "genre")}
    batch["history"] = _spec(rep, (b, cfg.history_len), jnp.int32)
    batch["valid"] = _spec(rep, (b,), jnp.bool_)
    stats = jax.tree.map(lambda x: _spec(rep, x.shape, x.dtype),
                         CacheStats.zero())
    u = _spec(rep, (b, cfg.embed_dim), jnp.float32)
    cand = _spec(rep, (b, K), jnp.int32)
    _compile(rse._scan_stage, specs, u)
    _compile(rse._rank_stage, specs, batch, cand, u, u, stats)


DLRM_BAGS = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1)
DLRM_ROWS = (10_000_000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
             10_000_000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
             10_000_000, 10_000_000, 10_000_000, 590152, 12973, 108, 36)


@pytest.mark.parametrize("stage", ["lookup", "rank"])
def test_dlrm_stages_compile(one_chip, stage):
    """DLRM-DCNv2's staged programs at published widths, for one chip's
    tables (54,184,588 int8 rows of 128, their scales 128 to a row) and
    the cell's bucket of 16
    requests of 512 candidates, at highest precision: they fit the chip."""
    from repro.models import recsys as rs
    from repro.serving import dlrm_engine as de

    cfg = rs.DLRMConfig(n_dense=13, n_sparse=26, embed_dim=128,
                        bottom_dims=(512, 256, 128),
                        top_dims=(1024, 1024, 512, 256, 1),
                        table_rows=DLRM_ROWS, bag_sizes=DLRM_BAGS,
                        interaction="cross", cross_layers=3, cross_rank=512)
    dense = jax.eval_shape(lambda: rs.init_dlrm_dense(jax.random.key(0),
                                                      cfg))
    as_spec = lambda x: _spec(one_chip, x.shape, x.dtype)  # noqa: E731
    rows = sum(DLRM_ROWS)
    engine = de.DLRMEngine(
        table_q=de.PackedRows(
            values=_spec(one_chip, (rows, 128), jnp.int8),
            scales=_spec(one_chip, (-(-rows // 128), 128), jnp.float32)),
        params=jax.tree.map(as_spec, dense), cfg=cfg)
    b, c = 16, 512
    batch = {"dense": _spec(one_chip, (b, c, 13), jnp.float32),
             "sparse": _spec(one_chip, (b, c, sum(DLRM_BAGS)), jnp.int32),
             "valid": _spec(one_chip, (b,), jnp.bool_)}
    with jax.default_matmul_precision("highest"):
        if stage == "lookup":
            lowered = jax.jit(de._dlrm_lookup_stage).lower(engine, batch)
        else:
            pooled = _spec(one_chip, (b, c, 26, 128), jnp.float32)
            lowered = jax.jit(de._dlrm_rank_stage).lower(engine, batch,
                                                         pooled)
        mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9 * 0.9
