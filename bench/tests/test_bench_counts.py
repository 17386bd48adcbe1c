"""Least-work counts against hand counts, and the least time they give."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402


def test_extend_count():
    # N = 8, D = 1024, float32: read X, G (8 rows each), write Z (8) and
    # the new row (x, g): 26 rows of 4 KiB; Z = A^-1 (...): 2 * 64 * 1024
    c = harness.load_module("counts", "extend").count(
        {}, n=8, d=1024, itemsize=4)
    assert c == {"bytes": 26 * 4096, "flops": 131072}


def test_query_count():
    # Q = 8 points, N = 8, D = 1024: read Xq, X, Z, write grad (8 rows
    # each) and 8 values; flops 8 Q N D
    c = harness.load_module("counts", "query").count(
        {}, n=8, d=1024, q=8, itemsize=4)
    assert c == {"bytes": 32 * 4096 + 32, "flops": 524288}


@pytest.mark.parametrize("chips", [1, 4])
def test_least_seconds_splits_over_chips(chips):
    shapes = {"extend": {"n": 8, "d": 2 ** 24, "itemsize": 4},
              "query": {"n": 8, "d": 2 ** 24, "q": 8, "itemsize": 4}}
    got = harness.least_seconds({}, shapes, {"extend": 3, "query": 5},
                                "TPU v5 lite", chips)
    # bytes bound: 26 and 32 rows of 64 MiB at 819 GB/s
    ext = 4 * 2 ** 24 * 26 / 819e9
    qry = (4 * 2 ** 24 * 32 + 32) / 819e9
    assert got["extend"] == pytest.approx(3 * ext / chips)
    assert got["query"] == pytest.approx(5 * qry / chips)


def test_unknown_chip_is_refused():
    with pytest.raises(harness.Refused):
        harness.least_seconds({}, {}, {}, "TPU v9 imaginary", 1)


def test_mfu_reads_least_over_window():
    rec = {"least_s": {"extend": 0.002, "query": 0.003}, "window_s": 0.5}
    for name in ("mfu.observe", "mfu.serve"):
        assert harness.load_module("metrics", name).read(rec) \
            == pytest.approx(1.0)
