"""The regression's counts against hand numbers, and the three readers of
cell ``linreg_10m_1c`` on a reduced trace and a ring written by hand
(``synthetic_ring.py``'s way: every answer known before the reader
runs)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as harness  # noqa: E402
import synthetic_ring  # noqa: E402

PEAKS = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
N, K = 2_555_904, 1000


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def reduced(device_s=(0.21, 0.23)):
    """Two queries of 210 and 230 ms of device time; the longest
    operations as seconds over the window: the Gram's dot first."""
    return {
        "window_s": 0.45, "busy_s": sum(device_s), "chips_traced": 1,
        "n_device_ops": 300,
        "queries": [{"template": "theta", "span_s": s + 0.001,
                     "device_s": s} for s in device_s],
        "device_ops": [
            ["%fusion.115 f32[1000,1000]", 0.36],
            ["%fusion.116 f32[1000]", 0.034],
            ["%custom-call.43 (f32[1000,128]", 0.02]],
        "idle_gaps": []}


def run_of(red, said=None):
    return types.SimpleNamespace(
        reduced=red, peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        shapes={"theta": {"n": N, "k": K, "itemsize": 4,
                          "precision": "highest"}},
        say=(said.append if said is not None else lambda line: None))


def test_linreg_counts_at_the_cell_s_size():
    c = harness.load_module(os.path.join(BENCH, "counts", "linreg.py")) \
        .counts(n=N, k=K, itemsize=4, precision="highest")
    # the symmetric Gram, the right-hand side, the solve
    assert c["flops"] == (N * K * (K + 1) + 2 * N * K
                          + K ** 3 // 3 + 2 * K * K)
    assert c["flops"] == 2_563_907_045_333
    # X once, y once, the Gram written and read, theta
    assert c["bytes"] == 4 * (N * K + N + 2 * K * K + K) \
        == 10_241_843_616
    # at six passes 78.1 ms of MXU against 12.5 ms of HBM: MXU bound,
    # and half of what a full-square dot (2 N k^2) is charged
    t_flops = c["flops"] * 6 / PEAKS["bf16_flops_per_s"]
    assert t_flops == pytest.approx(0.078089, rel=1e-4)
    assert c["bytes"] / PEAKS["hbm_bytes_per_s"] \
        == pytest.approx(0.012505, rel=1e-4)
    assert c["flops"] / (2 * N * K * K) == pytest.approx(0.5016, rel=1e-3)


def test_linreg_gram_roofline_is_the_symmetric_count_over_device_time():
    said = []
    v = reader("linreg_gram_roofline").read(run_of(reduced(), said))
    assert v == pytest.approx(100 * 0.078089 / 0.22, rel=1e-4)
    assert "bound=mxu" in said[0] and "mxu_passes=6" in said[0]
    # no clamp: a full-square dot at the peak would read about 50, and a
    # time too short to be true reads over 100
    assert reader("linreg_gram_roofline").read(
        run_of(reduced(device_s=(0.07, 0.07)))) > 100


def test_linreg_rest_ms_is_busy_less_the_longest_operation():
    said = []
    v = reader("linreg_rest_ms").read(run_of(reduced(), said))
    # 220 ms busy a query, 180 ms of it the dot
    assert v == pytest.approx(40.0)
    assert "%fusion.115" in said[0]
    empty = reduced()
    empty["device_ops"] = []
    assert reader("linreg_rest_ms").read(run_of(empty)) is None


def test_linreg_planned_hbm_pct_is_the_chain_s_reader_on_this_cell():
    ring = synthetic_ring.sql_ring()
    for r in ring:
        if r["name"] == "matrel.dispatch":
            r["attrs"].update(mesh="1x1", hbm_plan_bytes=11_785_613_632)
    said = []
    run = synthetic_ring.run_of(2, said)
    run.here, run.load_module = BENCH, harness.load_module
    v = reader("linreg_planned_hbm_pct").read(run, records=ring,
                                             bytes_limit=16_909_334_528)
    assert v == pytest.approx(100 * 11_785_613_632 / 16_909_334_528)
    # a parent commit reckons no one-device plan: nothing, no raise
    bare = synthetic_ring.run_of(2, said)
    bare.here, bare.load_module = BENCH, harness.load_module
    assert reader("linreg_planned_hbm_pct").read(
        bare, records=synthetic_ring.sql_ring(),
        bytes_limit=16_909_334_528) is None
    assert "no matrel.dispatch span" in said[-1]


@pytest.mark.parametrize("name", ["linreg_gram_roofline", "linreg_rest_ms",
                                  "linreg_planned_hbm_pct"])
def test_readers_without_a_trace_give_nothing(name):
    assert reader(name).read(types.SimpleNamespace(
        reduced=None, peaks=PEAKS, shapes={}, here=BENCH,
        load_module=harness.load_module, say=lambda line: None)) is None
