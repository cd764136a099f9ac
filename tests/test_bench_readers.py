"""The benchmark's span readers (``benchmarks/metrics/``, PR 25) read
the program's own ring — ``obs.trace.profile_spans()`` — so a change
to the ring's records or the spans' names breaks them: tier-1 holds
each reader to a real ring, recorded under the CPU profiler. Their
arithmetic is checked on a synthetic ring by hand in
``benchmarks/tests/test_program_spans.py``."""

import gc
import os
import types

import jax
import numpy as np
import pytest

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.session import MatrelSession
from matrel_tpu.workloads import pagerank as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = 3


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """What a reader is handed after a traced window of three SQL
    queries and three PageRank calls, warm, each followed by a forced
    collection, and one more of each after the count the trace would
    give."""
    from benchmarks import program_spans, run as harness
    from matrel_tpu.obs.trace import profile_spans
    sess = MatrelSession()
    rng = np.random.default_rng(7)
    sess.register("A", BlockMatrix.from_numpy(
        rng.standard_normal((32, 32)).astype(np.float32), mesh=sess.mesh))
    src = rng.integers(0, 200, 1500).astype(np.int32)
    dst = rng.integers(0, 200, 1500).astype(np.int32)

    def query():
        sess.compute(sess.sql("rowsum(A * A)")).to_numpy()
        pr.pagerank_edges(src, dst, 200, rounds=2, impl="onehot")

    query()                                             # warm
    before = len(profile_spans())   # the ring is the process's: other
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("prof")),
                             profiler_options=opts)
    try:
        for _ in range(QUERIES + 1):
            query()
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    mine = sorted(profile_spans()[before:],     # tests traced before
                  key=lambda r: r["start_ns"])
    said = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(program_spans, "ring", lambda: mine)
        # two roots a query here (a compute and a pagerank), so the
        # count the trace gives is over both
        # and the window ends where the call after it starts
        roots = [r for r in mine if r["name"] in program_spans.QUERY_ROOTS]
        window_ns = roots[2 * QUERIES]["start_ns"] - mine[0]["start_ns"]
        yield types.SimpleNamespace(
            reduced={"queries": [{}] * (2 * QUERIES),
                     "window_s": window_ns * 1e-9}, say=said.append,
            said=said, load_module=harness.load_module)


@pytest.mark.parametrize("name, low, high", [
    ("plan_lookup_ms", 0.0, 50.0), ("dispatch_ms", 0.0, 500.0),
    ("compute_self_ms", 0.0, 50.0), ("fetch_ms", 0.0, 500.0),
    ("fingerprint_ms", 0.0, 50.0), ("compiles_in_window", 0, 0),
    ("fetch_wait_ms", 0.0, 500.0), ("fetch_copy_ms", 0.0, 500.0),
    ("dispatch_launch_ms", 0.0, 500.0), ("dispatch_self_ms", 0.0, 50.0),
    ("gc_ms", 0.0, 5000.0)])
def test_reader_reads_the_programs_ring(traced_run, name, low, high):
    reader = traced_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    value = reader.read(traced_run)
    assert value is not None, traced_run.said
    assert low <= value <= high
    if name != "compiles_in_window":
        assert value > 0
    # without a reduced trace to count the queries by: nothing, no raise
    assert reader.read(types.SimpleNamespace(
        reduced=None, say=lambda line: None)) is None


SPLIT = ("matrel.fetch.wait", "matrel.fetch.copy",
         "matrel.dispatch.launch", "matrel.gc")


@pytest.mark.parametrize("name, missing", [
    ("fetch_wait_ms", "no matrel.fetch.wait"),
    ("fetch_copy_ms", "no matrel.fetch.copy"),
    ("dispatch_launch_ms", "no matrel.dispatch.launch"),
    ("dispatch_self_ms", "no matrel.dispatch.launch"),
    ("gc_ms", "no obs.trace.GC_SPAN")])
def test_split_reader_on_a_program_without_its_span(traced_run, name,
                                                    missing, monkeypatch):
    """The parent commit under these readers: the ring as PR 25's
    program leaves it gives None and a line naming what is missing."""
    from benchmarks import program_spans
    from matrel_tpu.obs import trace
    older = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                            if k != "ready"})
             for r in program_spans.ring() if r["name"] not in SPLIT]
    monkeypatch.setattr(program_spans, "ring", lambda: older)
    monkeypatch.delattr(trace, "GC_SPAN")
    reader = traced_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    del traced_run.said[:]
    assert reader.read(traced_run) is None
    assert missing in traced_run.said[-1]


def test_the_split_makes_up_what_it_splits(traced_run):
    """Span by span: wait + copy lie inside fetch and leave it a few
    microseconds; launch + self are dispatch."""
    from benchmarks import program_spans
    records, _ = program_spans.window(traced_run)
    by_parent = {}
    for r in records:
        by_parent.setdefault(r["parent_id"], []).append(r)
    fetches = [r for r in records if r["name"] == "matrel.fetch"]
    assert fetches and all(type(r["attrs"]["ready"]) is bool
                           for r in fetches)
    for f in fetches:
        kids = sorted(by_parent[f["span_id"]], key=lambda r: r["start_ns"])
        assert [k["name"] for k in kids] == ["matrel.fetch.wait",
                                             "matrel.fetch.copy"]
        assert 0 <= program_spans.self_ms(f, records) < 5.0
    for d in (r for r in records if r["name"] == "matrel.dispatch"):
        [launch] = [k for k in by_parent[d["span_id"]]
                    if k["name"] == "matrel.dispatch.launch"]
        assert program_spans.self_ms(d, records) \
            <= program_spans.ms(d) - program_spans.ms(launch) + 1e-9
