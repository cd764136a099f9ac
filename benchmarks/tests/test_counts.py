"""The count functions against the arithmetic PERF.md gives."""

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


def test_spmm_counts_at_the_cell_s_size():
    c = counts("spmm", nnzb=384, block_size=512, rows=100352, width=512,
               itemsize=4, precision="highest")
    assert c["flops"] == 2 * 384 * 512 ** 3 == 103_079_215_104
    # tiles 403 MB + D 205 MB + product 205 MB
    assert c["bytes"] == 402_653_184 + 2 * 205_520_896
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    assert c["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(0.99e-3,
                                                                   rel=0.01)
    # float32 at HIGHEST is six bf16 passes: 3.14 ms, so the MXU bounds it
    assert peaks["mxu_passes"] == {"default": 1, "high": 3, "highest": 6}
    assert c["flops"] * peaks["mxu_passes"][c["precision"]] \
        / peaks["bf16_flops_per_s"] == pytest.approx(3.14e-3, rel=0.01)
    # bfloat16 operands: one pass (0.52 ms) and half the bytes (0.50 ms)
    b = counts("spmm", nnzb=384, block_size=512, rows=100352, width=512,
               itemsize=2, precision="default")
    assert b["flops"] * peaks["mxu_passes"][b["precision"]] \
        / peaks["bf16_flops_per_s"] == pytest.approx(0.52e-3, rel=0.01)
    assert b["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(0.50e-3,
                                                                   rel=0.01)


def test_pagerank_counts_at_the_cell_s_size():
    c = counts("pagerank_spmv", nodes=1_000_000, edges=10_000_000, rounds=30)
    assert c["bytes"] == 30 * 92_000_000
    assert c["flops"] == 600_000_000 and c["precision"] == "highest"


def test_every_device_in_the_peaks_table_names_its_source():
    for kind, row in harness.load_json(
            os.path.join(BENCH, "peaks.json")).items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 \
            and row["hbm_bytes_per_s"] > 0, kind
