"""PR 35's readers (what is beneath ``dispatch`` and ``fetch``, the
collector's pauses) on ``synthetic_ring``'s SQL ring with the spans a
PR 35 program adds, by hand; beside ``test_program_spans.py``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import program_spans  # noqa: E402
from benchmarks import run as harness  # noqa: E402
import synthetic_ring as ring  # noqa: E402

NS = 2e-6       # the ring's whole nanoseconds cut a start and an end

def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))


def split_ring(collections=True):
    """The SQL ring (dispatch {0.5, 1.0, 0.5} at {0.5, 0.7, 20.5},
    fetch {0.8, 1.2, 0.8} at {1.3, 12.3, 29.3}) with: launch {0.3, 0.9,
    0.3} starting 0.1 into its dispatch; the first fetch ready (wait
    0.01, copy 0.75), the second not (wait 0.9, copy 0.25); a young
    collection of 0.06 inside the second dispatch, outside its launch,
    and a full one of 2.0 between the two queries, a root."""
    out, sid = list(ring.sql_ring()), 100
    by = {(r["name"], r["qid"]): r for r in out}
    for k, (launch, ready, wait, copy) in enumerate(
            [(0.3, True, 0.01, 0.75), (0.9, False, 0.9, 0.25),
             (0.3, True, 0.01, 0.75)]):
        d = by["matrel.dispatch", 3 * k + 2]
        f = by["matrel.fetch", 3 * k + 3]
        f["attrs"] = {"ready": ready, **f["attrs"]}
        t_d = (d["start_ns"] - ring.T0) / ring.MS
        t_f = (f["start_ns"] - ring.T0) / ring.MS
        out.append(ring.rec("matrel.dispatch.launch", t_d + 0.1, launch,
                            sid + 1, d["span_id"], d["qid"]))
        out.append(ring.rec("matrel.fetch.wait", t_f + 0.01, wait,
                            sid + 2, f["span_id"], f["qid"]))
        out.append(ring.rec("matrel.fetch.copy", t_f + 0.02 + wait, copy,
                            sid + 3, f["span_id"], f["qid"]))
        sid += 3
    if collections:
        d = by["matrel.dispatch", 5]
        out.append(ring.rec("matrel.gc", 10.7 + 0.02, 0.06, 200,
                            d["span_id"], 5, generation=0, collected=3))
        out.append(ring.rec("matrel.gc", 5.0, 2.0, 201, None, 50,
                            generation=2, collected=40))
    return sorted(out, key=lambda r: r["start_ns"])


def test_dispatch_self_is_what_launch_and_a_collection_leave():
    records = split_ring()
    # 0.5 - 0.3 and 1.0 - 0.9 - 0.06
    assert reader("dispatch_self_ms").read(ring.run_of(2), records) \
        == pytest.approx((0.2 + 0.04) / 2, abs=NS)
    # a program without the launch span: not the whole of dispatch
    said = []
    assert reader("dispatch_self_ms").read(
        ring.run_of(2, said), sorted(ring.sql_ring(),
                                     key=lambda r: r["start_ns"])) is None
    assert "no matrel.dispatch.launch" in said[-1]


def test_gc_is_the_windows_pauses_over_its_queries():
    said = []
    assert reader("gc_ms").read(ring.run_of(2, said), split_ring()) \
        == pytest.approx((0.06 + 2.0) / 2, abs=NS)
    assert said[-1] == ("gc generation=0 count=1 longest_ms=0.0600 "
                        "generation=2 count=1 longest_ms=2.0000")
    # a window without a collection is 0.0, not None
    said = []
    assert reader("gc_ms").read(ring.run_of(2, said),
                                split_ring(collections=False)) == 0.0
    assert said[-1] == "gc no collection in the window"


def test_gc_on_a_program_that_records_none(monkeypatch):
    from matrel_tpu.obs import trace
    monkeypatch.delattr(trace, "GC_SPAN")
    said = []
    assert reader("gc_ms").read(ring.run_of(2, said), split_ring()) is None
    assert "no obs.trace.GC_SPAN" in said[-1]


def test_fetch_split_and_the_ready_share(monkeypatch):
    records = split_ring()
    said = []
    assert reader("fetch_wait_ms").read(ring.run_of(2, said), records) \
        == pytest.approx((0.01 + 0.9) / 2, abs=NS)
    assert said[-1] == "fetch ready_at_entry=1 of 2 (50.0%)"
    monkeypatch.setattr(program_spans, "ring", lambda: records)
    assert reader("fetch_copy_ms").read(ring.run_of(2)) \
        == pytest.approx((0.75 + 0.25) / 2, abs=NS)
    assert reader("dispatch_launch_ms").read(ring.run_of(2)) \
        == pytest.approx((0.3 + 0.9) / 2, abs=NS)
    said = []
    assert reader("fetch_wait_ms").read(
        ring.run_of(2, said), sorted(ring.sql_ring(),
                                     key=lambda r: r["start_ns"])) is None
    assert "no matrel.fetch.wait" in said[-1]
