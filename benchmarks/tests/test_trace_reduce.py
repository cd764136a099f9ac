"""The trace reducer on a synthetic trace whose answers are known by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import trace_reduce as tr  # noqa: E402

MS = 1e6    # nanoseconds


def synthetic(device_clock_ahead=0.0, modules=True):
    """Two queries of 10 ms, 2 ms apart. The first holds a 4 ms ``while``
    with two 1 ms children and a 1 ms fusion; the second holds one 5 ms
    kernel that ends as the query does. The device clock may run ahead of
    the host's."""
    host = [
        ("bench.query:a", 0 * MS, 10 * MS),
        ("bench.parse", 0 * MS, 1 * MS),
        ("bench.compute", 1 * MS, 8 * MS),
        ("bench.fetch", 9 * MS, 1 * MS),
        ("bench.query:b", 12 * MS, 10 * MS),
        ("bench.compute", 12 * MS, 10 * MS),
    ]
    d = device_clock_ahead
    ops = [
        ("%while = (s32[]) while(...)", 2 * MS - d, 4 * MS),
        ("%body.1 = f32[8]{0} add(...)", 2 * MS - d, 1 * MS),
        ("%body.2 = f32[8]{0} add(...)", 4 * MS - d, 1 * MS),
        ("%fusion = f32[4]{0} fusion(...)", 7 * MS - d, 1 * MS),
        ("%kernel = f32[4]{0} custom-call(...)", 17 * MS - d, 5 * MS),
    ]
    mods = [("jit_a", 2 * MS - d, 6 * MS), ("jit_b", 17 * MS - d, 5 * MS)]
    return {"device": {"/device:TPU:0": {
        "ops": ops, "modules": mods if modules else []}}, "host": host}


def test_merge_and_covered():
    m = tr.merge([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert m == [[0, 3], [5, 7]]
    assert tr.covered(m, 1, 6) == 3


def test_short_name():
    assert tr.short_name("%reduce_sum.7 = f32[4096]{0:T(1024)S(1)} reduce("
                         "f32[4096,4096]{1,0} %a)") == "%reduce_sum.7 f32[4096]"
    assert tr.short_name("plain") == "plain"


def test_self_times_charge_a_parent_only_what_children_leave():
    ops = synthetic()["device"]["/device:TPU:0"]["ops"]
    t = tr.self_times(ops)
    assert t[ops[0][0]] == 2 * MS
    assert t[ops[1][0]] == t[ops[2][0]] == 1 * MS


@pytest.mark.parametrize("ahead", [0.0, 1.4 * MS, -0.7 * MS])
@pytest.mark.parametrize("modules", [True, False])
def test_reduce_window_busy_and_queries(ahead, modules):
    """The same answers whatever the device clock's offset: query b's
    kernel ends with its query, which pins the shift."""
    r = tr.reduce(synthetic(ahead, modules))
    assert r["window_s"] == pytest.approx(0.022)
    assert r["busy_s"] == pytest.approx(0.010)      # 4 + 1 + 5 ms
    assert r["chips_traced"] == 1 and r["n_device_ops"] == 5
    assert r["runs_paired_with_queries"] is modules
    a, b = r["queries"]
    assert a["template"] == "a" and b["template"] == "b"
    assert a["device_s"] == pytest.approx(0.005)
    assert b["device_s"] == pytest.approx(0.005)
    assert a["span_s"] - a["device_s"] == pytest.approx(0.005)
    assert r["spans"]["bench.parse"] == [pytest.approx(0.001)]
    assert dict(map(tuple, r["device_ops"]))["%kernel f32[4]"] \
        == pytest.approx(0.005)
    assert dict(map(tuple, r["device_ops"]))["%while (s32[])"] \
        == pytest.approx(0.002)


def test_idle_gaps_are_cut_at_span_edges_and_named_by_the_innermost():
    """The device is busy 2-6, 7-8 and 17-22 of the window 0-22."""
    gaps = dict(map(tuple, tr.reduce(synthetic(1.4 * MS))["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(0.012)
    assert gaps["bench.parse"] == pytest.approx(0.001)
    assert gaps["bench.compute"] == pytest.approx(0.001 + 0.001 + 0.001 + 0.005)
    assert gaps["bench.fetch"] == pytest.approx(0.001)
    assert gaps["between_queries"] == pytest.approx(0.002)


def test_two_chips_average():
    t = synthetic()
    t["device"]["/device:TPU:1"] = {
        "ops": [("%kernel = f32[4]{0} custom-call(...)", 17 * MS, 1 * MS)],
        "modules": []}
    r = tr.reduce(t)
    assert r["chips_traced"] == 2
    assert r["busy_s"] == pytest.approx((0.010 + 0.001) / 2)


def test_ops_named_finds_a_pallas_call_by_its_target():
    """The name the profiler gives a Pallas kernel on the chip (PR 24's
    trace of the SpMM cell), beside a custom call that is none."""
    t = synthetic()
    ops = t["device"]["/device:TPU:0"]["ops"]
    needle = 'custom_call_target="tpu_custom_call"'
    assert tr.ops_named(t, needle) == 0
    ops.append(('%custom-call = f32[8]{0} custom-call(), '
                'custom_call_target="AllocateBuffer"', 23 * MS, 0.0))
    assert tr.ops_named(t, needle) == 0
    ops.append(('%_run.1 = f32[100352,512]{1,0:T(8,128)} custom-call(s32[415]'
                '{0:T(512)} %constant.2), custom_call_target="tpu_custom_call"'
                ', operand_layout_constraints={s32[415]{0}}', 24 * MS, MS))
    assert tr.ops_named(t, needle) == 1


def test_no_query_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "host": []})
