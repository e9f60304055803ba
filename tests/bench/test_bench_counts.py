"""The benchmark's work counts and peaks table."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def scan():
    return spec.count("streaming_nns")


@pytest.fixture(scope="module")
def peaks():
    return spec.peaks(V5E)


def test_scan_count_depends_only_on_queries_rows_bits(scan):
    ops, nbytes = scan.work(256, 1 << 23, 256)
    assert ops == 2 * 256 * (1 << 23) * 256
    assert nbytes == (1 << 23) * 33 + 256 * 32
    # the same pairs in another shape do the same operations
    assert scan.work(128, 1 << 24, 256)[0] == ops
    assert scan.work(256, 1 << 23, 128)[0] == ops / 2


def test_catalog8m_least_time_is_compute_bound(scan, peaks):
    least, bound = scan.least_seconds(256, 1 << 23, 256, peaks)
    assert bound == "compute"
    assert least == pytest.approx(2.80e-3, rel=0.01)
    _, nbytes = scan.work(256, 1 << 23, 256)
    assert nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(3.4e-4,
                                                              rel=0.05)


def test_small_scan_is_memory_bound(scan, peaks):
    assert scan.least_seconds(1, 1 << 20, 256, peaks)[1] == "memory"


def test_dense_stages_count(peaks):
    mlps = spec.count("serve_mlps")
    sizes = {"embed_dim": 32, "features": 5, "filter_dims": [128, 64, 32],
             "rank_dims": [128, 1], "bits": 256, "n_candidates": 50,
             "history_len": 20}
    ops, _ = mlps.work(sizes)
    want = 2 * (192 * 128 + 128 * 64 + 64 * 32 + 32 * 256
                + 50 * (128 * 128 + 128))
    assert ops == want
    assert mlps.least_seconds(sizes, peaks) > 0


def test_peaks_of_v5e(peaks):
    assert peaks == {"bf16_flops": 197e12, "int8_ops": 393e12,
                     "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(spec.BenchError, match="no peaks"):
        spec.peaks(kind)
