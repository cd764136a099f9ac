"""The benchmark's deployment ``ldbc_graphalytics_g500_22`` (PR 33) in
tier-1, in ``test_bench_readers.py``'s manner: its three per-layer
readers on synthetic records and on a real ring, its generator and plain
reference at a small scale, and ``compare`` turning an answer that
another executor gave into not correct."""

import json
import os
import types

import jax
import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.workloads import pagerank as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "pagerank_g500_22_1c"
MS = 1_000_000


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def _rec(name, start_ms, dur_ms, span_id, parent_id, qid, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "end_ns": int((start_ms + dur_ms) * MS), "span_id": span_id,
            "parent_id": parent_id, "qid": qid, "tid": 1, "attrs": attrs}


def _ring(plan_attrs=True):
    """Two PageRank queries of 3,000 ms: 100 and 120 ms of comparison
    (the second in two spans), plans that say 133 slots for 128 edges."""
    said = {"layout": "chunks", "edges": 128, "slots": 133} \
        if plan_attrs else {}
    return [
        _rec("matrel.pagerank.fingerprint", 1, 100, 2, 1, 1, how="compare"),
        _rec("matrel.pagerank.plan", 102, 1, 3, 1, 1, hit=True, **said),
        _rec("matrel.pagerank", 0, 3000, 1, None, 1, impl="compact"),
        _rec("matrel.pagerank.fingerprint", 3101, 50, 5, 4, 2, how="compare"),
        _rec("matrel.pagerank.fingerprint", 3152, 70, 6, 4, 2, how="compare"),
        _rec("matrel.pagerank.plan", 3223, 1, 7, 4, 2, hit=True, **said),
        _rec("matrel.pagerank", 3100, 3000, 4, None, 2, impl="compact"),
    ]


def _run(n_queries=2, window_ms=6200, said=None):
    from benchmarks import run as harness
    return types.SimpleNamespace(
        reduced={"queries": [{}] * n_queries, "window_s": window_ms * 1e-3}
        if n_queries else None,
        say=(said.append if said is not None else lambda line: None),
        here=BENCH, load_module=harness.load_module)


# -- the readers ----------------------------------------------------------------


def test_slot_padding_reads_the_plan_spans():
    reader = _load("metrics", "g500_slot_padding_pct.py")
    assert reader.read(_run(), _ring()) == pytest.approx(
        100.0 * (133 / 128 - 1))
    # a program whose plan spans say neither (a parent commit): nothing,
    # a line saying why, no raise
    said = []
    assert reader.read(_run(said=said), _ring(plan_attrs=False)) is None
    assert "carries slots and edges" in said[0]
    assert reader.read(_run(0), _ring()) is None


def test_fingerprint_is_summed_a_query():
    reader = _load("metrics", "g500_fingerprint_ms.py")
    assert reader.read(_run(), _ring()) == pytest.approx(110.0)   # 100, 120
    assert reader.read(_run(0), _ring()) is None


def test_roofline_is_the_counts_least_time_over_the_device_time():
    reader = _load("metrics", "g500_spmv_roofline.py")
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    shapes = {"nodes": 2_396_366, "edges": 128_309_282, "rounds": 10}
    said = []
    run = _run(said=said)
    run.peaks, run.shapes = peaks, {"pagerank_g500": shapes}
    run.reduced = {"n_device_ops": 7, "chips_traced": 1, "window_s": 7.0,
                   "queries": [{"template": "pagerank_g500", "device_s": 3.0},
                               {"template": "pagerank_g500", "device_s": 3.2}]}
    least = 10 * (8 * shapes["edges"] + 12 * shapes["nodes"]) / 819e9
    assert reader.read(run) == pytest.approx(100.0 * least / 3.1)
    assert "bound=hbm" in said[0]
    run.reduced = None                # an untraced or CPU run: nothing
    assert reader.read(run) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "ldbc_graphalytics_g500_22"
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "g500_fingerprint_ms", "g500_slot_padding_pct", "g500_spmv_roofline"]
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] == "query_p50_ms"
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == spec["reduced"] == []
    assert config["source"] == spec["source"]
    assert spec["queries"]["pagerank_g500"]["vertex_limit"] <= 1e-4


# -- the deployment at a small scale ----------------------------------------------


@pytest.fixture(scope="module")
def dep():
    """The deployment as a rehearsal builds it (Kronecker scale 10,
    Pallas interpreted), every plan past the small-plan threshold as the
    real graph is."""
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    assert [m["query"] for m in traffic["mix"]] == ["pagerank_g500"]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
        patch.setattr(pr, "_PLAN_CACHE", [])
        was = config_lib._default_config
        d = harness.build_deployment(config, spec, 2147483999,
                                     ["pagerank_g500"], 2.0 ** -12)
        try:
            yield d
        finally:
            config_lib._default_config = was


def test_deployment_answers_within_its_limits_through_chunks(dep):
    from benchmarks import run as harness
    assert dep.kron_scale == 10 and dep.src.dtype == np.int32
    assert dep.src.size == 2 * dep.undirected
    ans = dep.run("pagerank_g500", harness.no_span)
    ans = dep.run("pagerank_g500", harness.no_span)
    notes = dep.notes("pagerank_g500")
    assert notes["layout"] == "chunks" and notes["impl"] == "compact"
    assert notes["overflow_edges"] == 0 and notes["hit"] is True
    assert notes["vertices"] == dep.nodes and "build_s" in notes
    want = dep.reference("pagerank_g500")
    np.testing.assert_allclose(
        want, pr.pagerank_reference_edges(dep.src, dep.dst, dep.nodes,
                                          dep.rounds, dep.alpha), rtol=1e-13)
    got = dict((label, (value, limit)) for label, value, limit in
               dep.compare("pagerank_g500", ans, want))
    assert all(value <= limit for value, limit in got.values()), got
    assert got["pagerank_g500.plan_builds"] == (1, 1)
    assert set(dep.shapes("pagerank_g500")) == {"nodes", "edges", "rounds"}
    # the control (the reference in bfloat16) breaks both error limits
    ctl = dict((label, value) for label, value, _ in dep.compare(
        "pagerank_g500", dep.control("pagerank_g500"), want))
    assert ctl["pagerank_g500.max_rel_err"] > 3e-6
    assert ctl["pagerank_g500.max_vertex_rel_err"] > 5e-6


def test_an_answer_another_executor_gave_is_not_correct(dep, monkeypatch):
    """A silent fall to the segment-sum path: the ranks are right and
    the run is not correct."""
    from benchmarks import run as harness
    dep.run("pagerank_g500", harness.no_span)
    want = dep.reference("pagerank_g500")
    monkeypatch.setattr(dep, "interpret", False)    # impl="auto" off the
    ans = dep.run("pagerank_g500", harness.no_span)         # TPU: segment
    got = dict((label, (value, limit)) for label, value, limit in
               dep.compare("pagerank_g500", ans, want))
    assert got["pagerank_g500.max_rel_err"][0] < 5e-6
    assert got["pagerank_g500.not_compact_calls"] == (1, 0)
    assert not all(value <= limit for value, limit in got.values())


def test_a_first_call_another_executor_answers_stops_set_up(monkeypatch):
    """A program that cannot lay the graph out (a parent commit) exits
    in set-up instead of serving the window through the slow path."""
    from benchmarks import run as harness
    _, _, config, spec, _ = harness.load_cell(CELL)
    was = config_lib._default_config
    try:
        d = harness.build_deployment(config, spec, 5, ["pagerank_g500"],
                                     2.0 ** -12)
        d.interpret = False                     # "auto" off the TPU
        with pytest.raises(RuntimeError, match="not by the compact-table"):
            d.run("pagerank_g500", harness.no_span)
        # where the program announces the fall (on the TPU, a plan
        # refused), the first call ends at the announcement
        d.calls = 0
        monkeypatch.setattr(pr, "on_tpu", lambda: True)
        monkeypatch.setattr(pr, "_auto_max_slots", lambda: 1000)
        monkeypatch.setattr(pr, "_PLAN_CACHE", [])
        before = pr.path_counts()["segment"]
        with pytest.raises(RuntimeError, match="cannot serve this deployment"
                           ".*bytes: the .* layout takes"):
            d.run("pagerank_g500", harness.no_span)
        assert pr.path_counts()["segment"] == before     # never ran
        assert not pr.log.handlers
    finally:
        config_lib._default_config = was


# -- the readers on the program's own ring -----------------------------------------


def test_readers_read_the_programs_ring(dep, tmp_path):
    """The span attributes the readers ask for are the ones the program
    sets: a window of two calls under the CPU profiler."""
    from benchmarks import program_spans, run as harness
    from matrel_tpu.obs.trace import profile_spans
    dep.run("pagerank_g500", harness.no_span)               # warm
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            dep.run("pagerank_g500", harness.no_span)
    finally:
        jax.profiler.stop_trace()
    mine = sorted(profile_spans()[before:], key=lambda r: r["start_ns"])
    roots = [r for r in mine if r["name"] in program_spans.QUERY_ROOTS]
    run = _run(2, (roots[2]["start_ns"] - mine[0]["start_ns"]) * 1e-6)
    plan = next(r for r in mine if r["name"] == "matrel.pagerank.plan")
    assert set(plan["attrs"]) >= {"hit", "layout", "edges", "slots",
                                  "chunks", "chunk", "overflow_edges",
                                  "row_values", "panels", "plan_bytes",
                                  "hubs", "hub_slots", "hub_chunks",
                                  "hub_walk_rows"}
    # slots are both sets of chunks (PR 36), so the reader keeps meaning
    # slots over edges; at scale 10 each of two blocks ends two ragged
    # sets (37% where the cell's graph pads 7.5%)
    attrs = plan["attrs"]
    assert attrs["hubs"] > 0 and attrs["slots"] == (
        attrs["chunks"] + attrs["hub_chunks"]) * attrs["chunk"]
    # PR 42: the registers walk the rows they name, a step each at the
    # least
    assert attrs["hub_walk_rows"] >= 2 * 64 * attrs["hub_chunks"]
    pad = _load("metrics", "g500_slot_padding_pct.py").read(run, mine)
    assert pad == pytest.approx(
        100.0 * (attrs["slots"] / dep.src.size - 1)) and pad < 40
    assert _load("metrics", "g500_fingerprint_ms.py").read(run, mine) > 0
