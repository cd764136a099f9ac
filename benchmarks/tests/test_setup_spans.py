"""The six set-up readers and their helper on a synthetic cold ring
whose answers are known by hand, and one CPU rehearsal that lists them;
run by hand, like its neighbours:

    python -m pytest benchmarks/tests/test_setup_spans.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import setup_spans  # noqa: E402
from benchmarks import run as harness  # noqa: E402
import synthetic_ring as ring  # noqa: E402

METRICS = setup_spans.SECONDS + (setup_spans.MISSES,)


def cold(name, start_ms, dur_ms, span_id, parent_id=None, tid=1, **attrs):
    """A cold record as ``obs.trace.cold_spans()`` gives it: a bare
    name, on the clock of ``synthetic_ring``'s profile ring (whose
    first query root starts at 0.2 ms: set-up lies before 0)."""
    r = ring.rec(name, start_ms, dur_ms, span_id, parent_id, None, **attrs)
    r["tid"] = tid
    return r


def setup_ring():
    """A set-up of 1,000 ms before the window, by hand:

    coo.from_edges 30
    compile 600 = plan.optimize 200 (spmm.plan.build 150 (coo.slab.fill
      100 (jit.trace 5, jit.lower 10, jit.backend 60 with a miss))) +
      plan.verify 10 + plan.trace 300 (jit.trace 250 ⊃ jit.trace 20;
      spmm.plan 30 ⊃ spmm.plan.upload 25) + its own 90
    then the first dispatch: jit.trace 40 (⊃ spmm.plan 4), jit.lower
      120, jit.backend 80 with a hit; a generator's jit.backend 50 on
      another thread while the compile runs; and, after the window's
      first root, a compile of 500 that is the check's."""
    t = -1000
    return [
        cold("coo.from_edges", t, 30, 1, entries=7, bytes=140),
        cold("compile", t + 100, 600, 2, executors=["xla"]),
        cold("plan.optimize", t + 100, 200, 3, 2),
        cold("spmm.plan.build", t + 120, 150, 4, 3, orientation="forward",
             fill_s=0.01),
        cold("coo.slab.fill", t + 130, 100, 5, 4, entries=5, bytes=64),
        cold("jit.trace", t + 135, 5, 6, 5, fun_name="_slab_add"),
        cold("jit.lower", t + 140, 10, 7, 5, fun_name="jit(_slab_add)"),
        cold("jit.backend", t + 150, 60, 8, 5, fun_name="jit(_slab_add)"),
        cold("jit.cache", t + 209, 0, 9, 8, hit=False,
             fun_name="jit(_slab_add)"),
        cold("plan.verify", t + 300, 10, 10, 2),
        cold("plan.trace", t + 310, 300, 11, 2),
        cold("jit.trace", t + 315, 250, 12, 11, fun_name="fn"),
        cold("jit.trace", t + 320, 20, 13, 11, fun_name="_where"),
        cold("spmm.plan", t + 400, 30, 14, 11, hit=False, k=8),
        cold("spmm.plan.upload", t + 402, 25, 15, 14),
        # the first dispatch retraces: no cold span is open, no parent
        cold("jit.trace", t + 710, 40, 16, fun_name="matrel_plan_matmul"),
        cold("spmm.plan", t + 720, 4, 17, hit=False, k=8),
        cold("jit.lower", t + 750, 120, 18,
             fun_name="jit(matrel_plan_matmul)"),
        cold("jit.backend", t + 870, 80, 19,
             fun_name="jit(matrel_plan_matmul)"),
        cold("jit.cache", t + 940, 0, 20, 19, hit=True, retrieval_s=0.07,
             saved_s=3.0, fun_name="jit(matrel_plan_matmul)"),
        cold("jit.backend", t + 200, 50, 21, tid=2, fun_name="jit(generate)"),
        # after the window's first query root: not set-up's
        cold("compile", 5, 500, 22, executors=["xla"]),
        cold("jit.backend", 100, 300, 23, 22, fun_name="jit(reference)"),
        cold("jit.cache", 399, 0, 24, 23, hit=False,
             fun_name="jit(reference)"),
    ]


def run_of(said=None, first_calls=None):
    run = ring.run_of(2, said)
    run.first_calls = first_calls or {"q": (0.9, 0.1)}
    return run


def read_all(records=None, spans=None, said=None):
    records = setup_ring() if records is None else records
    spans = sorted(ring.sql_ring(), key=lambda r: r["start_ns"]) \
        if spans is None else spans
    out = {}
    for m in METRICS:
        reader = harness.load_module(
            os.path.join(BENCH, "metrics", m + ".py"))
        out[m] = reader.read(run_of(said), records, spans)
    return out


def test_every_new_name_has_a_reader_and_an_entry():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in bench["per_layer"][-6:]] \
        == [m["name"] for m in mine] and len(mine) == 6
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "optimizer, planner, compile", "setup_s", "host_clock", "lower")
        assert m["workloads"] == cells[:10]
        assert m["unit"] == ("count" if m["name"] == setup_spans.MISSES
                             else "s")


def test_the_six_by_hand():
    got = read_all()
    # the two nested traces count once: 250 − 20 − 30 (the product's
    # lowering inside it) + 20, then the slab's 5 and the dispatch's
    # 40 − 4
    assert got["setup_jit_trace_s"] == pytest.approx(0.261)
    assert got["setup_jit_lower_s"] == pytest.approx(0.130)
    # the slab's compile is a compile, the generator's on its own thread
    assert got["setup_backend_compile_s"] == pytest.approx(0.190)
    assert got["setup_cache_misses"] == 1
    # from_edges 30 + optimize 50 + build 50 + verify 10 + trace's own
    # 50 + compile's own 90
    assert got["setup_plan_build_s"] == pytest.approx(0.280)
    # the fill's own 25, the upload 25, spmm.plan's own 5 and 4
    assert got["setup_upload_s"] == pytest.approx(0.059)


def test_self_times_add_up_to_the_outermost_records():
    got = read_all()
    outermost = 30 + 600 + 40 + 120 + 80 + 50      # ms, set-up's
    assert sum(got[m] for m in setup_spans.SECONDS) \
        == pytest.approx(outermost * 1e-3)
    selfs = setup_spans.self_seconds(setup_ring())
    assert sum(selfs.values()) == pytest.approx((outermost + 500) * 1e-3)
    assert all(s >= -1e-12 for s in selfs.values())


def test_a_compile_inside_the_slabs_fill_is_counted_once():
    selfs = setup_spans.self_seconds(setup_ring())
    assert selfs[8] == pytest.approx(0.060)         # the jit.backend
    assert selfs[5] == pytest.approx(0.025)         # the fill less its jits
    assert selfs[4] == pytest.approx(0.050)         # the build less the fill
    assert selfs[3] == pytest.approx(0.050)         # optimize less the build
    without = [r for r in setup_ring() if r["span_id"] not in (6, 7, 8, 9)]
    got, less = read_all(), read_all(without)
    assert less["setup_upload_s"] - got["setup_upload_s"] \
        == pytest.approx(0.075)
    assert got["setup_backend_compile_s"] \
        - less["setup_backend_compile_s"] == pytest.approx(0.060)


def test_records_after_the_windows_first_root_are_left_out():
    said = []
    got = read_all(said=said)
    assert got["setup_cache_misses"] == 1       # not the reference's
    assert not any("jit(reference)" in line for line in said)
    late = [dict(r, start_ns=r["start_ns"] + 2000 * ring.MS,
                 end_ns=r["end_ns"] + 2000 * ring.MS)
            for r in setup_ring()]
    said = []
    assert read_all(late, said=said) == dict.fromkeys(METRICS)
    assert said == ["setup spans: no cold record before the window's "
                    "first query root"] * 6


def test_one_line_a_run_and_five_contributors_a_metric():
    said = []
    run = run_of(said, {"a": (0.5, 0.1), "b": (0.2, 0.3)})
    spans = sorted(ring.sql_ring(), key=lambda r: r["start_ns"])
    for m in METRICS:
        setup_spans.read(run, m, setup_ring(), spans)
    inside = [line for line in said if line.startswith("setup inside")]
    assert inside == [
        "setup inside sum_s=0.920 covers_s=1.000 (first cold record to "
        "the window's first query root) first_call_s=0.400 records=21 "
        "cache_hits=1 cache_misses=1"]
    assert len(said) == 7
    by_metric = {line.split(" largest: ")[0]: line.split(" largest: ")[1]
                 for line in said if " largest: " in line}
    assert by_metric["setup_jit_trace_s"].startswith(
        "fn 0.200 s; matrel_plan_matmul 0.036 s; _where 0.020 s; "
        "_slab_add 0.005 s")
    assert by_metric["setup_cache_misses"] == "jit(_slab_add) 1"
    assert by_metric["setup_plan_build_s"].startswith(
        'compile#2 {"executors": ["xla"]} 0.090 s; ')
    assert by_metric["setup_upload_s"].split("; ")[0] \
        == "coo.slab.fill#5 {\"entries\": 5, \"bytes\": 64} 0.025 s"


def test_no_ring_gives_none(monkeypatch):
    said = []
    monkeypatch.setattr(setup_spans, "ring", lambda: None)
    run = run_of(said)
    for m in METRICS:
        assert setup_spans.read(run, m) is None
    assert said == ["setup spans: the program records none "
                    "(no obs.trace.cold_spans)"]
    # and a run without a reduced trace, as program_spans says it
    said = []
    run = ring.run_of(0, said)
    assert setup_spans.read(run, METRICS[0], setup_ring(), []) is None
    assert said == ["program spans: no reduced trace to count the "
                    "queries by"]


def test_a_rehearsal_lists_the_six():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "relational_small_1c", "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse", "0.1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert set(METRICS) <= set(last["metric_names"])
    assert sum(ln.startswith("setup inside sum_s=") for ln in lines) == 1
    for m in METRICS:
        assert sum(ln.startswith(m + " largest: ") for ln in lines) == 1
