"""``counts/gnmf.py`` against the arithmetic PERF.md gives (a file of its
own: ``test_counts.py`` is the accepted benchmark's and is left as it
is)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")


def counts(kernel, **shapes):
    return harness.load_module(
        os.path.join(BENCH, "counts", kernel + ".py")).counts(**shapes)


def test_gnmf_counts_at_the_cell_s_size():
    """Three iterations at the Netflix shape and rank 128: six sparse
    products of 25.7 G operations and 1.2 GB of coordinates each, the
    four dense products and two element-wise passes of an iteration
    beside them; free of any layout (what the program says of its plans
    is taken and not read)."""
    shapes = dict(users=480_189, movies=17_770, entries=100_480_507,
                  rank=128, iterations=3)
    c = counts("gnmf", plans={"forward": {"slots": 1}}, **shapes)
    assert c == counts("gnmf", **shapes) and c["precision"] == "highest"
    sparse_flops = 3 * 2 * 2 * 100_480_507 * 128
    dense_flops = 3 * 4 * 128 * 128 * (480_189 + 17_770)
    assert c["flops"] == sparse_flops + dense_flops == 252_240_781_824
    rows = 480_189 + 17_770
    assert c["bytes"] == 3 * (2 * (12 * 100_480_507 + 512 * rows)
                              + 8 * 512 * rows) == 14_883_246_744
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    # 7.7 ms on the MXU at six passes, 18.2 ms of HBM traffic: the bytes
    # bound it, at under a hundredth of a 2.5 s query
    assert c["flops"] * 6 / peaks["bf16_flops_per_s"] == pytest.approx(
        7.68e-3, rel=0.01)
    assert c["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(
        18.2e-3, rel=0.01)
