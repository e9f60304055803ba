"""The DLRM-DCNv2 cell end to end on the CPU, at a size a test can hold.

A tiny DLRM-DCNv2 configuration and traffic mix, written into a scratch
checkout as new files beside the benchmark's own, run through the same
harness as every cell (`require_chip=False`): the seeded build through the
program's `DLRMEngine`, `make_server`, the closed-loop window, and the
comparison with the plain reference (`bench/models/dlrm_ref.py`). With a
table row read from the wrong id, or a cross layer dropped, `correct` has
to come out false; so does the control. The work counts are checked at
the published widths.
"""
import json
import pathlib
import shutil
import sys
import types

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run, spec  # noqa: E402

V5E = "TPU v5 lite"
CONFIG = json.loads((ROOT / "bench/configs/dlrm-dcnv2-criteo1tb.json")
                    .read_text())
LIMITS = CONFIG["checks"]
TINY = dict(CONFIG, embed_dim=16, bottom_dims=[32, 16], top_dims=[64, 32, 1],
            cross_rank=8, cross_layers=2, bag_sizes=[3, 1, 5, 2],
            table_rows=[500, 7, 3000, 20], held_rows=[200, 7, 1000, 20],
            row_block=256, candidates=24, pool_requests=32)
TRAFFIC = json.loads((ROOT / "bench/traffic/closed-dlrm64.json").read_text())
TINY_TRAFFIC = dict(TRAFFIC, outstanding=16, warm_buckets=[4], sample=16,
                    server=dict(TRAFFIC["server"], max_batch=4))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    (root / "bench/configs/tiny-dlrm.json").write_text(json.dumps(TINY))
    (root / "bench/traffic/tiny-dlrm.json").write_text(
        json.dumps(TINY_TRAFFIC))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dlrm", "source": "test",
                             "file": "bench/configs/tiny-dlrm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-dlrm", "config": "tiny-dlrm",
                               "traffic": "tiny-dlrm", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dlrmv2-closed" in m.get("workloads", []):
            m["workloads"].append("tiny-dlrm")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(autouse=True)
def jax_settings(monkeypatch, tmp_path):
    """A run sets process-wide JAX options; put them back afterwards, and
    keep the persistent compile cache off."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_default_matmul_precision",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def tiny_run(checkout, seed=2 ** 40 + 5, **kw):
    return bench_run.run("tiny-dlrm", seed, 1.5, False, root=checkout,
                         require_chip=False, device_kind=V5E, **kw)


def test_dlrm_cell_files_are_found_by_name(checkout):
    bench = spec.load_spec(checkout)
    cell = spec.cell(bench, "dlrmv2-closed", checkout)
    assert cell["config"]["builder"] == "dlrm"
    assert cell["traffic"]["outstanding"] == 64
    build, ref = spec.builder(cell["config"])
    assert build.TRACE_PROGRAMS == {"lookup": "_dlrm_lookup_stage",
                                    "rank": "_dlrm_rank_stage"}
    assert ref.__name__ == "bench.models.dlrm_ref"
    names = {m["name"] for m, _ in spec.metrics(bench, "dlrmv2-closed",
                                                "per_layer", checkout)}
    assert {"dlrm_mfu", "lookup_roofline", "rank_roofline"} <= names


def test_sound_dlrm_run_is_correct(checkout):
    out = tiny_run(checkout)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def _wrong_row(monkeypatch):
    """Table 0's first bag slot reads the row after the one it names."""
    from repro.serving import dlrm_engine as de

    real = de.dlrm_lookup_step

    def broken(engine, batch):
        sparse = batch["sparse"].at[..., 0].set(
            (batch["sparse"][..., 0] + 1) % engine.cfg.rows[0])
        return real(engine, dict(batch, sparse=sparse))

    monkeypatch.setattr(de, "dlrm_lookup_step", broken)


def _dropped_cross_layer(monkeypatch):
    """The served engine runs one cross layer fewer."""
    from bench.models import dlrm

    real = dlrm.build

    def broken(cfg, log):
        out = real(cfg, log)
        engine = out["engine"]
        params = dict(engine.params, cross=engine.params["cross"][:-1])
        out["engine"] = type(engine)(**dict(vars(engine), params=params))
        return out

    monkeypatch.setattr(dlrm, "build", broken)


@pytest.mark.parametrize("fault", [_wrong_row, _dropped_cross_layer])
def test_broken_dlrm_path_is_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    out = tiny_run(checkout)
    assert not out["correct"], out["checks"]


def test_dlrm_control_is_not_correct(checkout):
    """The reference at three bf16 passes, in the program's place, fails
    the configuration's limits; the program on the same requests does
    not."""
    out = tiny_run(checkout, control=True)
    assert all(out["numbers"][k] <= v for k, v in LIMITS.items()), \
        out["numbers"]
    assert any(out["control"][k] > v for k, v in LIMITS.items()), \
        out["control"]


def test_dlrm_work_at_published_widths():
    """32,060,928 flops and 214 rows a sample; 132 B a row plus its id."""
    count = spec.count("dlrm")
    assert count.rank_flops(CONFIG) == 32_060_928
    assert count.lookup_rows(CONFIG) == 214
    assert count.lookup_bytes(CONFIG) == 214 * (128 + 4 + 4)
    peaks = spec.peaks(V5E)
    flops_s = 512 * 32_060_928 / peaks["bf16_flops"]
    assert count.request_seconds(CONFIG, peaks) == pytest.approx(flops_s)


def _ctx(trace, qps=100.0, served=160, padded=0, batches=10):
    snap0 = {"serving.batches": 0, "serving.served": 0, "serving.padded": 0}
    snap1 = {"serving.batches": batches, "serving.served": served,
             "serving.padded": padded}
    return types.SimpleNamespace(
        trace=trace, values={"qps": qps}, snap0=snap0, snap1=snap1,
        work={"config": CONFIG}, peaks=spec.peaks(V5E),
        count=lambda n: spec.count(n))


@pytest.mark.parametrize("metric,stage,work", [
    ("lookup_roofline", "lookup", "lookup_seconds"),
    ("rank_roofline", "rank", "rank_seconds")])
def test_dlrm_roofline_readers(metric, stage, work):
    """Least time of a batch's samples over the stage's device time a
    batch; nothing to read without a trace or a batch."""
    reader = spec.reader(metric)
    trace = {"programs": {"lookup": {"s": 0.02, "n": 10},
                          "rank": {"s": 0.08, "n": 10}}}
    least = getattr(spec.count("dlrm"), work)(CONFIG, 16 * 512,
                                              spec.peaks(V5E))
    per_batch = trace["programs"][stage]["s"] / 10
    assert reader.read(_ctx(trace)) == pytest.approx(
        100 * least / per_batch)
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx(trace, batches=0)) is None


def test_dlrm_mfu_reader():
    reader = spec.reader("dlrm_mfu")
    least = spec.count("dlrm").request_seconds(CONFIG, spec.peaks(V5E))
    assert reader.read(_ctx(None, qps=250.0)) == pytest.approx(
        100 * 250.0 * least)
    assert reader.read(_ctx(None, qps=None)) is None
