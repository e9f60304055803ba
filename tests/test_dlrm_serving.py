"""DLRM-DCNv2 through `make_server`, against the plain `dlrm_forward`.

A tiny DLRM-DCNv2 (multi-hot bags of several sizes, a low-rank cross
network) on seeded random weights, served through the same front-end
classes as YoutubeDNN in every mode. The plain reference reads the same
int8 rows dequantized, so logits agree to float32 rounding (1e-5
absolute: the logits are O(1), and the program's gather-and-sum and
matmuls reorder float32 additions only) and the top-k is equal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import dequantize_rowwise, quantize_rowwise
from repro.models import recsys as rs
from repro.serving import (
    DLRMEngine,
    RecSysEngine,
    SchemaMismatchError,
    make_server,
)
from repro.serving import dlrm_engine as de

CANDIDATES, TOP_K = 24, 5
LOGIT_ATOL = 1e-5
CFG = rs.DLRMConfig(n_dense=13, n_sparse=4, embed_dim=16,
                    bottom_dims=(32, 16), top_dims=(64, 32, 1),
                    table_rows=(50, 7, 300, 20), bag_sizes=(3, 1, 5, 2),
                    interaction="cross", cross_layers=2, cross_rank=8)


def _requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = np.concatenate(
            [rng.integers(0, r, (CANDIDATES, b))
             for r, b in zip(cfg.rows, cfg.bags)], 1).astype(np.int32)
        out.append({"dense": rng.random((CANDIDATES, cfg.n_dense),
                                        np.float32),
                    "sparse": ids})
    return out


def _setup(cfg=CFG):
    params = rs.init_dlrm(jax.random.key(0), cfg)
    engine = DLRMEngine.build(params, cfg, candidates=CANDIDATES,
                              top_k=TOP_K)
    # the reference reads the rows the engine holds: int8, dequantized
    plain = dict(params, tables={
        k: dequantize_rowwise(quantize_rowwise(v))
        for k, v in params["tables"].items()})
    return engine, plain


def _reference(plain, cfg, query):
    logits = np.asarray(rs.dlrm_forward(
        plain, cfg, {k: jnp.asarray(v) for k, v in query.items()}))
    return logits, np.argsort(-logits, kind="stable")[:TOP_K]


@pytest.mark.parametrize("mode", ["sync", "pipelined", "concurrent"])
def test_serves_like_dlrm_forward(mode):
    engine, plain = _setup()
    queries = _requests(CFG, 11)
    server = make_server(engine, mode, max_batch=4)
    served = server.serve_many(queries)
    server.close()
    for q, got in zip(queries, served):
        logits, want = _reference(plain, CFG, q)
        assert got.ok
        np.testing.assert_array_equal(got.items, want)
        np.testing.assert_allclose(got.scores, logits[want],
                                   atol=LOGIT_ATOL, rtol=0)


def test_engine_refuses_the_dot_interaction():
    """The engine serves DCNv2's cross network; the paper's pairwise-dot
    DLRM stays the plain model's (`dlrm_forward`) alone."""
    cfg = CFG._replace(interaction="dot")
    params = rs.init_dlrm(jax.random.key(0), cfg)
    assert rs.dlrm_forward(params, cfg, {
        "dense": jnp.zeros((2, 13)),
        "sparse": jnp.zeros((2, sum(cfg.bags)), jnp.int32)}).shape == (2,)
    with pytest.raises(ValueError, match="cross"):
        DLRMEngine.build(params, cfg)


def test_cross_layer_matches_its_equation():
    """x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l, written out in numpy."""
    rng = np.random.default_rng(3)
    d, r = 12, 4
    layers = [{"V": rng.normal(size=(d, r)), "W": rng.normal(size=(r, d)),
               "b": rng.normal(size=d)} for _ in range(3)]
    x0 = rng.normal(size=(5, d))
    want = x0.copy()
    for p in layers:
        want = x0 * (p["W"].T @ (p["V"].T @ want.T) + p["b"][:, None]).T \
            + want
    got = rs.cross_net(jax.tree.map(jnp.asarray, layers), jnp.asarray(x0))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_bags_with_repeated_ids():
    """A bag that names one row k times pools k times that row."""
    engine, plain = _setup()
    q = _requests(CFG, 1, seed=2)[0]
    q["sparse"][:, :3] = 7  # table 0's bag of 3: row 7 thrice
    q["sparse"][:, 4:9] = q["sparse"][:, 4:5]  # table 2's bag: one row x5
    pooled = np.asarray(de.dlrm_lookup_step(
        engine, {"sparse": jnp.asarray(q["sparse"][None])}))[0]
    row7 = np.asarray(plain["tables"]["cat_00"][7])
    np.testing.assert_allclose(pooled[:, 0], np.broadcast_to(3 * row7,
                                                             (CANDIDATES, 16)),
                               rtol=1e-6)
    row = np.asarray(plain["tables"]["cat_02"])[q["sparse"][:, 4]]
    np.testing.assert_allclose(pooled[:, 2], 5 * row, rtol=1e-6, atol=1e-7)
    logits, want = _reference(plain, CFG, q)
    got = make_server(engine, "sync", max_batch=1).serve_many([q])[0]
    np.testing.assert_array_equal(got.items, want)


@pytest.mark.parametrize("mode", ["sync", "pipelined", "concurrent"])
def test_counters_count_samples_and_bag_rows(mode):
    """Δdlrm.bag_rows = samples x sum(bag sizes); padding requests count
    nothing."""
    engine, _ = _setup()
    server = make_server(engine, mode, max_batch=4)
    before = server.snapshot()
    server.serve_many(_requests(CFG, 7))
    after = server.snapshot()
    server.close()
    samples = after.get("dlrm.samples", 0) - before.get("dlrm.samples", 0)
    rows = after.get("dlrm.bag_rows", 0) - before.get("dlrm.bag_rows", 0)
    assert samples == 7 * CANDIDATES
    assert rows == samples * sum(CFG.bags)


@pytest.mark.parametrize("mode", ["sync", "pipelined"])
def test_observer_sees_the_rows_bags_read(mode):
    """A lookup-frequency observer is fed, per served chunk, every bag
    slot's row as an id into the tables back to back."""
    engine, _ = _setup()
    queries = _requests(CFG, 7)
    server = make_server(engine, mode, max_batch=4)
    seen = []
    server.observer = seen.append
    server.serve_many(queries)
    server.close()
    offsets = de.slot_offsets(CFG)
    want = np.sort(np.concatenate([(q["sparse"] + offsets).reshape(-1)
                                   for q in queries]))
    np.testing.assert_array_equal(np.sort(np.concatenate(seen)), want)


def _unpacked(table):
    """The tables (`PackedRows`) as (rows, d) int8 values and (rows, 1)
    f32 scales, scale ``r`` from ``[r // 128, r % 128]``."""
    return (np.asarray(table.values),
            np.asarray(table.scales).reshape(-1, 1))


def _table_rows(params, cfg=CFG):
    return np.concatenate([np.asarray(params["tables"][f"cat_{i:02d}"])
                           for i in range(cfg.n_sparse)])


def _streamed(params, cfg=CFG, block=64):
    """`from_row_blocks` over the tables in blocks of `block` rows."""
    rows = _table_rows(params, cfg)
    rows = np.concatenate([rows, np.zeros((-len(rows) % block,
                                           rows.shape[1]), np.float32)])
    blocks = ((lo, jnp.asarray(rows[lo:lo + block]))
              for lo in range(0, len(rows), block))
    dense = {k: v for k, v in params.items() if k != "tables"}
    return DLRMEngine.from_row_blocks(dense, cfg, blocks)


def test_row_blocks_ingest_like_build():
    """Streaming float row blocks into place gives the rows `build`
    quantizes from whole tables."""
    params = rs.init_dlrm(jax.random.key(0), CFG)
    built = DLRMEngine.build(params, CFG)
    streamed = _streamed(params)
    n = sum(CFG.rows)
    for got, want in zip(_unpacked(streamed.table_q),
                         _unpacked(built.table_q)):
        np.testing.assert_array_equal(got[:n], want[:n])


@pytest.mark.parametrize("ingest", ["build", "from_row_blocks"])
def test_packing_round_trips(ingest):
    """The int8 rows and packed scales unpack to `quantize_rowwise`'s
    rows and scales (jitted, as ingestion runs it), bit for bit; the
    scales take no more room than the rows' own, but for padding to a
    whole row of 128."""
    params = rs.init_dlrm(jax.random.key(0), CFG)
    engine = (DLRMEngine.build(params, CFG) if ingest == "build"
              else _streamed(params))
    want = jax.jit(quantize_rowwise)(jnp.asarray(_table_rows(params)))
    n = sum(CFG.rows)
    values, scales = _unpacked(engine.table_q)
    np.testing.assert_array_equal(values[:n], np.asarray(want.values))
    np.testing.assert_array_equal(scales[:n], np.asarray(want.scales))
    assert 0 <= engine.table_q.scales.size - len(values) < 128


def _oracle_bag_sums(values, scales, ids, bags):
    """The lookup as it was written for (rows, d) int8 rows and (rows, 1)
    scales: gather, scale, and add a bag's slots one after the other."""
    rows = values[ids].astype(jnp.float32) * scales[ids]
    out, lo = [], 0
    for n in bags:
        acc = rows[:, lo]
        for j in range(lo + 1, lo + n):
            acc = acc + rows[:, j]
        out.append(acc)
        lo += n
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("embed_dim", [16, 128])
def test_bag_sums_match_int8_rows_bit_for_bit(embed_dim):
    """`bag_sums` over the tables and packed scales gives the sums of the
    int8 rows times their (rows, 1) scales, to the bit: ids of every
    residue mod 128, id 0, the last row held, and one row repeated in a
    bag.

    Both sides are jitted: XLA's CPU backend may fuse a product into the
    sum it feeds, rounding once, and does so alike for both at these
    bag sizes (at the cell's 100-slot bag it fuses one and not the
    other; the chip's comparison at the cell's shape is in PERF.md)."""
    cfg = CFG._replace(embed_dim=embed_dim,
                       bottom_dims=CFG.bottom_dims[:-1] + (embed_dim,))
    params = rs.init_dlrm(jax.random.key(1), cfg)
    engine = _streamed(params, cfg)
    q = jax.jit(quantize_rowwise)(jnp.asarray(_table_rows(params, cfg)))
    n, s = sum(cfg.rows), sum(cfg.bags)
    rng = np.random.default_rng(4)
    ids = rng.integers(0, n, (40, s)).astype(np.int32)
    ids[0] = np.arange(s) + 100  # across a row of 128 scales
    ids[1] = (np.arange(s) * 37) % 128 + 256  # scattered lanes of a row
    ids[2] = 0  # padding requests read row 0
    ids[3] = n - 1  # the last row held, in every slot
    ids[4, 4:9] = 201  # one row five times in the bag of 5
    bags = cfg.bags
    got = jax.jit(de.bag_sums, static_argnums=3)(
        engine.table_q.values, engine.table_q.scales, jnp.asarray(ids), bags)
    want = jax.jit(_oracle_bag_sums, static_argnums=3)(
        q.values, q.scales, jnp.asarray(ids), bags)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("direction", ["dlrm_to_recsys", "recsys_to_dlrm"])
def test_swap_engine_refuses_other_schema(direction):
    from repro.data import synthetic

    engine, _ = _setup()
    data = synthetic.make_movielens(n_users=40, n_items=60, history_len=6)
    ycfg = rs.YoutubeDNNConfig(
        n_items=data.n_items,
        user_features={k: int(v.max()) + 1
                       for k, v in data.user_feats.items()},
        history_len=6)
    recsys = RecSysEngine.build(rs.init_youtubednn(jax.random.key(1), ycfg),
                                ycfg)
    first, other = ((engine, recsys) if direction == "dlrm_to_recsys"
                    else (recsys, engine))
    for mode in ("sync", "concurrent"):
        server = make_server(first, mode)
        with pytest.raises(SchemaMismatchError):
            server.swap_engine(other)
        server.close()


def test_stage_programs_carry_named_scopes():
    """The lookup and rank programs carry the scopes the trace names."""
    engine, _ = _setup()
    q = _requests(CFG, 2)
    batch = {"dense": jnp.asarray(np.stack([x["dense"] for x in q])),
             "sparse": jnp.asarray(np.stack([x["sparse"] for x in q])),
             "valid": jnp.ones(2, bool)}
    lookup = de.dlrm_lookup_step.lower(engine, batch).as_text(
        debug_info=True)
    pooled = de.dlrm_lookup_step(engine, batch)
    rank = de.dlrm_rank_step.lower(engine, batch, pooled).as_text(
        debug_info=True)
    assert "lookup" in lookup
    for scope in ("bottom", "cross", "top"):
        assert scope in rank
