"""`merge_frac`, the streaming kernel's share of scanned cells whose
candidate merge ran, read from a registry snapshot pair."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


def _ctx(snap0, snap1):
    return types.SimpleNamespace(snap0=snap0, snap1=snap1, trace=None)


def test_merge_frac_reads_merged_over_scanned_cells_in_the_window():
    reader = spec.reader("merge_frac", ROOT)
    snap0 = {"nns.scan_cells": 1000, "nns.merge_cells": 900,
             "nns.merge_rounds": 45000}
    snap1 = {"nns.scan_cells": 5000, "nns.merge_cells": 1500,
             "nns.merge_rounds": 48000}
    # (1500 - 900) merged of (5000 - 1000) scanned; rounds do not enter
    assert reader.read(_ctx(snap0, snap1)) == pytest.approx(0.15)
    # counters first written inside the window count from zero
    assert reader.read(_ctx({}, snap1)) == pytest.approx(0.3)


_SCANNED = {"nns.scan_cells": 1000, "nns.merge_cells": 900}


@pytest.mark.parametrize("snap0,snap1", [
    ({}, {"serving.served": 512}),  # a program without the counters
    (_SCANNED, _SCANNED),  # no cell scanned in the window
], ids=["no_counters", "no_scans"])
def test_merge_frac_gives_nothing_without_scanned_cells(snap0, snap1):
    assert spec.reader("merge_frac", ROOT).read(_ctx(snap0, snap1)) is None
