"""The four readers of cell ``linreg_10m_2x2`` on a reduced trace of four
chips and a ring written by hand (``synthetic_ring.py``'s way: every
answer known before the reader runs), and the count they share with the
one-chip cell at the whole table's rows."""

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
N, K = 10_223_616, 1000
READERS = ["linreg_whole_gram_roofline", "linreg_whole_collective_ms",
           "linreg_whole_planned_hbm_pct", "linreg_whole_launch_ms"]


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def reduced(device_s=(0.121, 0.123), chips=4, all_reduce=0.0006):
    """Two queries of 121 and 123 ms of device time (the mean over the
    chips' planes); the longest operations as seconds over the window:
    the block columns, the LU's calls, and the all-reduce last."""
    ops = [["%fusion.190 f32[1000,233]", 0.0876],
           ["%fusion.189 f32[768,256]", 0.0737],
           ["%custom-call.43 (f32[1000,128]", 0.0022]]
    if all_reduce:
        ops.append(["%all-reduce.7 (f32[1000,233]", all_reduce])
    return {"window_s": 0.25, "busy_s": sum(device_s),
            "chips_traced": chips, "n_device_ops": 1200,
            "queries": [{"template": "theta", "span_s": s + 0.001,
                         "device_s": s} for s in device_s],
            "device_ops": ops, "idle_gaps": []}


def run_of(red, said=None):
    return types.SimpleNamespace(
        reduced=red, peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        shapes={"theta": {"n": N, "k": K, "itemsize": 4,
                          "precision": "highest"}},
        say=(said.append if said is not None else lambda line: None))


def test_the_whole_table_s_count_is_four_quarters_and_one_solve():
    counts = harness.load_module(os.path.join(BENCH, "counts", "linreg.py")) \
        .counts
    whole = counts(n=N, k=K, itemsize=4, precision="highest")
    quarter = counts(n=N // 4, k=K, itemsize=4, precision="highest")
    solve = K ** 3 // 3 + 2 * K * K
    assert whole["flops"] - solve == 4 * (quarter["flops"] - solve)
    assert whole["flops"] == 10_254_622_181_333
    assert whole["bytes"] == 4 * (N * K + N + 2 * K * K + K)


def test_gram_roofline_is_reckoned_over_the_chips_the_trace_shows():
    said = []
    v = reader("linreg_whole_gram_roofline").read(run_of(reduced(), said))
    # 10.25e12 operations at six passes over four chips: 78.08 ms, the
    # one-chip cell's least time to three digits, over 122 ms
    least = 10_254_622_181_333 * 6 / (4 * PEAKS["bf16_flops_per_s"])
    assert least == pytest.approx(0.07808, rel=1e-3)
    assert v == pytest.approx(100 * least / 0.122, rel=1e-9)
    assert "chips=4" in said[0] and "bound=mxu" in said[0] \
        and "mxu_passes=6" in said[0]
    # two chips traced for the same work: half the roofline; no clamp
    assert reader("linreg_whole_gram_roofline").read(
        run_of(reduced(chips=2))) == pytest.approx(2 * v, rel=1e-9)
    assert reader("linreg_whole_gram_roofline").read(
        run_of(reduced(device_s=(0.07, 0.07)))) > 100
    assert reader("linreg_whole_gram_roofline").read(
        run_of(reduced(chips=0))) is None


def test_collective_ms_is_the_all_reduce_a_query_or_nothing_seen():
    said = []
    v = reader("linreg_whole_collective_ms").read(run_of(reduced(), said))
    assert v == pytest.approx(0.6 / 2)
    assert "all-reduce.7" in said[0].replace("%", "")
    said = []
    assert reader("linreg_whole_collective_ms").read(
        run_of(reduced(all_reduce=0), said)) == 0
    assert said[0].endswith("none")


def test_planned_hbm_pct_and_launch_ms_read_this_cell_s_spans():
    ring = synthetic_ring.sql_ring()
    launches = iter((0.61, 0.75, 9.0))
    for r in list(ring):
        if r["name"] == "matrel.dispatch":
            r["attrs"].update(mesh="2x2", hbm_plan_bytes=10_245_840_616)
            ms = next(launches)
            ring.append(synthetic_ring.rec(
                "matrel.dispatch.launch",
                (r["start_ns"] - synthetic_ring.T0) / synthetic_ring.MS,
                ms, 1000 + r["span_id"], r["span_id"], r["qid"]))
    said = []
    run = synthetic_ring.run_of(2, said)
    run.here, run.load_module = BENCH, harness.load_module
    v = reader("linreg_whole_planned_hbm_pct").read(
        run, records=sorted(ring, key=lambda r: r["start_ns"]),
        bytes_limit=16_909_336_064)
    assert v == pytest.approx(100 * 10_245_840_616 / 16_909_336_064)
    assert v == pytest.approx(60.59, abs=0.01)
    # a parent commit's spans carry no reckoning: nothing, no raise
    bare = synthetic_ring.run_of(2, said)
    bare.here, bare.load_module = BENCH, harness.load_module
    assert reader("linreg_whole_planned_hbm_pct").read(
        bare, records=synthetic_ring.sql_ring(),
        bytes_limit=16_909_336_064) is None
    # the launch reader takes the program's ring itself: its shared
    # reader's median over the window's two launches
    from benchmarks import program_spans
    found = program_spans.window(
        run, sorted(ring, key=lambda r: r["start_ns"]))
    lengths = [program_spans.ms(r) for r in found[0]
               if r["name"] == "matrel.dispatch.launch"]
    assert lengths == pytest.approx([0.61, 0.75], abs=1e-5)


@pytest.mark.parametrize("name", READERS)
def test_whole_readers_without_a_trace_give_nothing(name):
    assert reader(name).read(types.SimpleNamespace(
        reduced=None, peaks=PEAKS, shapes={}, here=BENCH,
        load_module=harness.load_module, say=lambda line: None)) is None
