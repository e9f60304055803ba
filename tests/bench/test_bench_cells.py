"""The benchmark harness end to end on the CPU, at a size a test can hold.

A cell is found by the names in a `BENCHMARK.json`: these tests write a
new configuration, traffic mix and metric reader as files into a scratch
checkout and run them, with no edit to any file of the benchmark. The
runs skip the look for a chip (`require_chip=False`); everything else is
the run the chip makes: the seeded build, the server, the window, and the
comparison with the plain reference. With the timed path broken
underneath, `correct` has to come out false; so does the control.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run, spec  # noqa: E402

V5E = "TPU v5 lite"
# the real configurations' limits, held at the tiny size below
LIMITS = json.loads((ROOT / "bench/configs/youtubednn-catalog8m.json")
                    .read_text())["checks"]

TINY_CONFIG = {
    "builder": "youtubednn", "precision": "highest", "model_seed": 3,
    "user_features": {"age": 7, "gender": 3, "occupation": 21,
                      "user_id": 48, "zip_bucket": 250},
    "trained_items": 160, "catalog_items": 2048, "catalog_chunk": 512,
    "clusters": 4, "history_len": 6, "embed_dim": 32,
    "filter_dims": [128, 64, 32], "rank_dims": [128, 1],
    "train_steps": 4, "train_batch": 16, "radius": 100, "n_candidates": 16,
    "top_k": 4, "hot_rows": 8, "live": True, "delta_capacity": 32,
    "updates": 8, "checks": LIMITS}
TINY_CLOSED = {"loop": "closed", "outstanding": 16, "users_zipf": 1.1,
               "server": {"mode": "concurrent", "max_batch": 8,
                          "queue_depth": None, "autostart": False},
               "warm_buckets": [8], "sample": 16}
TINY_OPEN = {"loop": "open", "rate": 150.0, "users_zipf": 1.1,
             "server": {"mode": "concurrent", "max_batch": 8,
                        "queue_depth": None},
             "warm_buckets": "pow2", "sample": 16}
# a metric a later PR might add: one new file, found by its name
NEW_METRIC = '''
from bench import loadgen


def read(ctx):
    return float((ctx.records.status == loadgen.OK).sum())
'''


def write_checkout(root: pathlib.Path) -> None:
    """A checkout holding the benchmark and the program, plus one new
    configuration, two new traffic mixes and one new metric as files."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tiny-closed.json").write_text(
        json.dumps(TINY_CLOSED))
    (root / "bench/traffic/tiny-open.json").write_text(json.dumps(TINY_OPEN))
    (root / "bench/metrics/served_n.py").write_text(NEW_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny-closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny-open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("tiny-closed")
        if m["name"] in ("p50_ms", "p90_ms"):
            m["workloads"].append("tiny-open")
    bench["end_to_end"].append({"name": "served_n", "unit": "requests",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny-closed", "tiny-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    write_checkout(root)
    return root


@pytest.fixture(autouse=True)
def jax_settings(monkeypatch, tmp_path):
    """A run sets process-wide JAX options; put them back afterwards, and
    keep the persistent compile cache off (the variable is read only when
    JAX starts, so pointing it elsewhere leaves the cache off)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_default_matmul_precision",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def tiny_run(checkout, workload, seed=5, **kw):
    return bench_run.run(workload, seed, 1.5, False, root=checkout,
                         require_chip=False, device_kind=V5E, **kw)


def test_new_files_are_found_by_name(checkout):
    bench = spec.load_spec(checkout)
    cell = spec.cell(bench, "tiny-closed", checkout)
    assert cell["config"]["catalog_items"] == 2048
    assert cell["traffic"]["outstanding"] == 16
    names = [m["name"] for m, _ in spec.metrics(bench, "tiny-closed",
                                                "end_to_end", checkout)]
    assert names == ["qps", "setup_s", "served_n"]
    with pytest.raises(spec.BenchError, match="no workload"):
        spec.cell(bench, "absent", checkout)


def test_split_metric_reads_with_its_base_reader(checkout):
    """`served_n.open` has no file: it reads with `served_n.py`; a name
    with neither its own file nor a base one is an error naming it."""
    assert spec.reader("served_n.open", checkout).__name__ == \
        "bench_metric_served_n"
    assert spec.reader("idle_frac.closed", checkout).__name__ == \
        "bench_metric_idle_frac"
    with pytest.raises(spec.BenchError, match="absent.part.py"):
        spec.reader("absent.part", checkout)


@pytest.mark.parametrize("workload,metric", [("tiny-closed", "qps"),
                                             ("tiny-open", "p90_ms")])
def test_sound_run_is_correct(checkout, workload, metric):
    out = tiny_run(checkout, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {metric, "setup_s", "served_n"} | (
        {"p50_ms"} if metric == "p90_ms" else set())
    assert list(out)[-1] == "checks"
    assert out["checks"]["u_gap"]["value"] <= LIMITS["u_gap"]
    assert out["device"]["platform"] == "cpu"


def _alter_items(monkeypatch):
    """A served answer altered where it is produced: each request's first
    item replaced by the next id."""
    from repro.serving import async_server, recsys_engine as rse

    real = rse.rank_stage_step

    def broken(*args):
        items, top, stats = real(*args)
        return items.at[:, 0].add(1), top, stats

    monkeypatch.setattr(async_server, "rank_stage_step", broken)
    monkeypatch.setattr(rse, "rank_stage_step", broken)


def _half_catalog(monkeypatch):
    """Half of the work left out: the scan drops every candidate from the
    second half of the catalog."""
    from repro.serving import async_server, recsys_engine as rse

    real = rse.scan_step

    def broken(engine, u):
        nns = real(engine, u)
        half = engine.item_table_q.shape[0] // 2
        idx = np.where(np.asarray(nns.indices) >= half, -1, nns.indices)
        return nns._replace(indices=jax.numpy.asarray(idx))

    monkeypatch.setattr(async_server, "scan_step", broken)
    monkeypatch.setattr(rse, "scan_step", broken)


@pytest.mark.parametrize("fault", [_alter_items, _half_catalog])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, fault):
    fault(monkeypatch)
    out = tiny_run(checkout, "tiny-closed")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("config", ["youtubednn-catalog8m", "youtubednn-ml1m"])
def test_control_is_not_correct(checkout, config):
    """The reference at three bf16 passes, in the program's place, fails
    each configuration's limits; the program on the same requests does
    not."""
    limits = json.loads((ROOT / f"bench/configs/{config}.json")
                        .read_text())["checks"]
    out = tiny_run(checkout, "tiny-closed", control=True)
    assert all(out["numbers"][k] <= v for k, v in limits.items()), \
        out["numbers"]
    assert any(out["control"][k] > v for k, v in limits.items()), \
        out["control"]


def test_off_chip_run_is_refused(checkout):
    with pytest.raises(spec.BenchError, match="not a TPU"):
        bench_run.run("tiny-closed", 1, 1.0, False, root=checkout)


def test_unknown_device_kind_is_refused(checkout):
    with pytest.raises(spec.BenchError, match="no peaks"):
        bench_run.run("tiny-closed", 1, 1.0, False, root=checkout,
                      require_chip=False, device_kind="TPU v9")


def test_kernel_override_is_refused(checkout, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    with pytest.raises(spec.BenchError, match="overrides"):
        tiny_run(checkout, "tiny-closed")


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths:
    the command exits non-zero and prints no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no program" in proc.stderr
