"""The span readers, their helper and ``stages.py`` on a synthetic ring
and a synthetic trace whose answers are known by hand."""

import io
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import program_spans, stages  # noqa: E402
from benchmarks import run as harness  # noqa: E402
import synthetic_ring as ring  # noqa: E402
import test_trace_reduce  # noqa: E402

MS = 1e6


def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))


def ordered(records):
    return sorted(records, key=lambda r: r["start_ns"])


def test_window_is_the_first_n_query_roots_within_its_length():
    records = ordered(ring.sql_ring())
    found, roots = program_spans.window(ring.run_of(2), records)
    assert [r["qid"] for r in roots] == [2, 5]
    # the two roots take 12 of the window's 14 ms, so it reaches no
    # further than 2 ms past them: the call after the window is cut,
    # its sql too
    assert found == [r for r in records if r["qid"] <= 6]
    assert not any(r["name"] == "matrel.compile" for r in found)
    # a third query's compile takes it out of a window of 24 ms
    assert program_spans.window(ring.run_of(3), records) is None
    everything, roots = program_spans.window(
        ring.run_of(3, window_ms=31), records)
    assert everything == records and len(roots) == 3


def test_window_skips_what_an_earlier_session_left():
    """One query traced three seconds before the window (a warm-up
    trace): with it the first two roots do not fit the window."""
    earlier = [dict(r, start_ns=r["start_ns"] - 3000 * ring.MS,
                    end_ns=r["end_ns"] - 3000 * ring.MS,
                    qid=r["qid"] + 100)
               for r in ring.sql_ring() if r["qid"] <= 3]
    records = ordered(earlier + ring.sql_ring())
    found, roots = program_spans.window(ring.run_of(2), records)
    assert [r["qid"] for r in roots] == [2, 5]
    assert found == [r for r in records if r["qid"] <= 6]
    assert reader("compiles_in_window").read(ring.run_of(2), records) == 0


@pytest.mark.parametrize("n, window_ms, why", [
    (0, None, "no reduced trace"),
    (4, None, "3 query roots in the ring, no 4 in a row"),
    (2, 11.0, "3 query roots in the ring, no 2 in a row within the "
              "trace's 0.0110 s")])
def test_window_says_why_it_reads_nothing(n, window_ms, why):
    said = []
    assert program_spans.window(ring.run_of(n, said, window_ms),
                                ordered(ring.sql_ring())) is None
    assert why in said[0]


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    """A parent commit: the import finds no ``profile_spans``."""
    from matrel_tpu.obs import trace
    monkeypatch.delattr(trace, "profile_spans")
    said = []
    assert program_spans.ring() is None
    assert reader("fetch_ms").read(ring.run_of(2, said)) is None
    assert "records none" in said[0]


@pytest.mark.parametrize("name, span, want", [
    ("plan_lookup_ms", "matrel.plan", 0.3),
    ("dispatch_ms", "matrel.dispatch", 0.75),
    ("fetch_ms", "matrel.fetch", 1.0)])
def test_median_readers(name, span, want, monkeypatch):
    monkeypatch.setattr(program_spans, "ring",
                        lambda: ordered(ring.sql_ring()))
    assert reader(name).read(ring.run_of(2)) == pytest.approx(want)
    assert program_spans.median_ms(ring.run_of(2), "matrel.nothing") is None


def test_compute_self_time_is_what_the_children_leave():
    records = ordered(ring.sql_ring())
    assert reader("compute_self_ms").read(ring.run_of(2), records) \
        == pytest.approx((0.3 + 0.6) / 2)
    # the compiling query: 9.0 - 0.2 - 8.0 - 0.5
    assert program_spans.self_ms(
        next(r for r in records if r["qid"] == 8
             and r["name"] == "matrel.compute"), records) \
        == pytest.approx(0.3)


def test_fingerprint_is_summed_per_query():
    assert reader("fingerprint_ms").read(
        ring.run_of(2, window_ms=1300), ordered(ring.pagerank_ring())) \
        == pytest.approx((150 + 180) / 2)
    assert reader("fingerprint_ms").read(
        ring.run_of(2), ordered(ring.sql_ring())) is None


def test_compiles_in_window_counts_misses_only():
    read = reader("compiles_in_window").read
    assert read(ring.run_of(2), ordered(ring.sql_ring())) == 0
    assert read(ring.run_of(3, window_ms=31), ordered(ring.sql_ring())) == 1
    assert read(ring.run_of(2, window_ms=1300),
                ordered(ring.pagerank_ring())) == 1
    assert read(ring.run_of(1, window_ms=300),
                ordered(ring.pagerank_ring())) == 1


def with_program_spans(ahead=1.4 * MS):
    """test_trace_reduce's trace with the program's spans inside the
    benchmark's: query a is sql, compute (plan, dispatch), fetch; query b
    a compute that dispatches at once."""
    trace = test_trace_reduce.synthetic(ahead)
    trace["host"] += [
        ("matrel.sql", 0.1 * MS, 0.8 * MS),
        ("matrel.compute", 1.1 * MS, 7.8 * MS),
        ("matrel.plan", 1.2 * MS, 0.5 * MS),
        ("matrel.dispatch", 1.8 * MS, 7.0 * MS),
        ("matrel.fetch", 9.1 * MS, 0.8 * MS),
        ("matrel.compute", 12.1 * MS, 9.8 * MS),
        ("matrel.dispatch", 12.2 * MS, 9.6 * MS)]
    trace["host"].sort(key=lambda e: e[1])
    return trace


def test_stages_names_idle_gaps_by_the_program_span():
    """The device is busy 2-6, 7-8 and 17-22 of the window 0-22."""
    out = io.StringIO()
    got = stages.report(with_program_spans(), out)
    gaps = dict(map(tuple, got["reduced"]["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(0.012)
    assert gaps["matrel.sql"] == pytest.approx(0.0008)
    assert gaps["matrel.plan"] == pytest.approx(0.0005)
    # query a: 1.8-2, 6-7, 8-8.8; query b: 12.2-17
    assert gaps["matrel.dispatch"] == pytest.approx(0.0002 + 0.001 + 0.0008
                                                    + 0.0048)
    assert gaps["matrel.fetch"] == pytest.approx(0.0008)
    assert gaps["bench.fetch"] == pytest.approx(0.0002)
    assert got["containment"] == {
        "matrel.sql": {"bench.parse": 1},
        "matrel.compute": {"bench.compute": 2},
        "matrel.plan": {"bench.compute": 1},
        "matrel.dispatch": {"bench.compute": 2},
        "matrel.fetch": {"bench.fetch": 1}}
    assert "matrel.dispatch" in out.getvalue()
    # the reducer's own numbers are what they were without the spans
    plain = test_trace_reduce.tr.reduce(test_trace_reduce.synthetic(1.4 * MS))
    for key in ("window_s", "busy_s", "queries", "device_ops"):
        assert got["reduced"][key] == plain[key]


def test_stages_load_keeps_both_prefixes_and_restores_the_reducer(monkeypatch):
    seen = []
    monkeypatch.setattr(stages.trace_reduce, "load",
                        lambda path: seen.append(
                            stages.trace_reduce.SPAN_PREFIX) or {})
    stages.load("x.xplane.pb")
    assert seen == [("bench.", "matrel.")]
    assert stages.trace_reduce.SPAN_PREFIX == "bench."


def test_clock_residual_takes_the_session_start_out():
    host = with_program_spans()["host"]
    start = 1_790_000_000_000_000_000
    names = [e for e in host if e[0].startswith("matrel.")]
    recs = [{"name": n, "start_ns": start + int(s) + (700 if i == 3 else 0)}
            for i, (n, s, _) in enumerate(names)]
    # a later session's span of a name the trace has no more of
    recs.append({"name": "matrel.sql", "start_ns": start + 10 ** 12})
    worst, offset, matched = stages.clock_residual_ns(recs, host)
    assert (worst, offset, matched) == (700, start, len(names))
    assert stages.clock_residual_ns([], host) is None
