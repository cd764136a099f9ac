"""LinearRegCG's counts against hand numbers, and the five readers of
cell ``linregcg_10m_1c`` on a reduced trace and a ring written by hand
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
from synthetic_ring import rec  # noqa: E402

PEAKS = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
N, K = 2_555_904, 1000
QUERY = "beta_cg"
READERS = ["linregcg_roofline", "linregcg_rounds", "linregcg_launches",
           "linregcg_planned_hbm_pct", "linregcg_compiles_in_window"]


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def ring(queries=2, rounds=4, compile_in=None, chains=True):
    """``queries`` traced queries of 3 + 6 x ``rounds`` statements: each
    a ``matrel.compute`` root over a dispatch and its launch; the chain's
    statement (the first of a round) 14 ms long with a dispatch that says
    10.3 GB and the chain's span, the others 1 ms and 30 MB."""
    out, sid, t0, u = [], 0, 0.0, 0
    for _ in range(queries):
        for s in range(3 + 6 * rounds):
            chain = s >= 3 and (s - 3) % 6 == 0
            long_ms = 14.0 if chain else 1.0
            root = sid + 1
            if compile_in == u:
                out.append(rec("matrel.compile", t0 + 0.1, 0.2, sid + 5,
                               root, u))
            out.append(rec("matrel.dispatch", t0 + 0.4, 0.3, sid + 2, root,
                           u, hbm_plan_bytes=10_300_000_000 if chain
                           else 30_000_000))
            if chain and chains:
                out.append(rec("matrel.mmchain.plan", t0 + 0.41, 0.01,
                               sid + 3, sid + 2, u, hit=True, one_read=True))
            out.append(rec("matrel.dispatch.launch", t0 + 0.45, 0.2,
                           sid + 4, sid + 2, u))
            out.append(rec("matrel.compute", t0, long_ms, root, None, u))
            sid += 5
            t0 += long_ms + 0.2
            u += 1
    return sorted(out, key=lambda r: r["start_ns"]), t0


def run_of(queries=2, rounds=4, window_ms=None, said=None):
    return types.SimpleNamespace(
        reduced={"queries": [{"template": QUERY}] * queries,
                 "window_s": (window_ms or 1e4) * 1e-3}
        if queries else None,
        shapes={QUERY: {"n": N, "k": K, "itemsize": 4, "rounds": rounds,
                        "precision": "highest"}},
        peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        say=(said.append if said is not None else lambda line: None))


def test_counts_against_hand_numbers():
    """4 rows, 3 columns, 2 rounds, by hand: three reads of the 12
    entries, y's 4, p and q of 3 a round and t(X) y's 3 (48 + 4 + 15
    numbers of 4 B); two chains of 4 x 12 operations and one product of
    2 x 12."""
    counts = harness.load_module(
        os.path.join(BENCH, "counts", "linregcg.py")).counts
    assert counts(n=4, k=3, itemsize=4, rounds=2, precision="highest") == {
        "flops": 2 * 48 + 24, "bytes": 4 * (36 + 4 + 15),
        "precision": "highest"}
    # the cell at 4 rounds: five reads of the 10.2 GB table
    full = counts(n=N, k=K, itemsize=4, rounds=4, precision="highest")
    assert full["flops"] == 18 * N * K == 46_006_272_000
    assert full["bytes"] == 4 * (5 * N * K + N + 9 * K) \
        == 51_128_339_616
    # bound by HBM (62.4 ms against 1.4 ms of MXU at six passes), and a
    # plan that reads X twice a chain can reach 5 / 9 of it
    assert full["bytes"] / PEAKS["hbm_bytes_per_s"] \
        == pytest.approx(0.062428, rel=1e-4)
    assert full["flops"] * 6 / PEAKS["bf16_flops_per_s"] \
        == pytest.approx(0.0014012, rel=1e-3)


def test_roofline_is_the_counts_least_time_over_the_device_time():
    said = []
    run = run_of(said=said)
    run.reduced = {"n_device_ops": 70, "chips_traced": 1, "window_s": 0.2,
                   "queries": [{"template": QUERY, "device_s": 0.066},
                               {"template": QUERY, "device_s": 0.070}]}
    v = reader("linregcg_roofline").read(run)
    assert v == pytest.approx(100.0 * 0.062428 / 0.068, rel=1e-4)
    assert "bound=hbm" in said[0]
    # no clamp: a device time too short to be true reads over 100, and
    # two reads a chain at the peak would read 5 / 9
    run.reduced["queries"] = [{"template": QUERY, "device_s": 0.05}]
    assert reader("linregcg_roofline").read(run) > 100
    run.reduced["queries"] = [{"template": QUERY,
                               "device_s": 9 * N * K * 4 / 819e9}]
    assert reader("linregcg_roofline").read(run) \
        == pytest.approx(100 * 5 / 9, rel=2e-3)
    run.reduced = None
    assert reader("linregcg_roofline").read(run) is None


def test_the_span_readers_take_every_statement_as_a_query_root():
    records, length = ring()
    run = run_of(window_ms=length)
    assert reader("linregcg_rounds").read(run, records) \
        == pytest.approx(4.0)
    assert reader("linregcg_launches").read(run, records) \
        == pytest.approx(27.0)
    assert reader("linregcg_compiles_in_window").read(run, records) == 0
    assert reader("linregcg_planned_hbm_pct").read(
        run, records, bytes_limit=20_600_000_000) == pytest.approx(50.0)
    # another round count, and a compile in the window
    records, length = ring(3, 7, compile_in=50)
    run = run_of(3, 7, window_ms=length)
    assert reader("linregcg_rounds").read(run, records) \
        == pytest.approx(7.0)
    assert reader("linregcg_launches").read(run, records) \
        == pytest.approx(45.0)
    assert reader("linregcg_compiles_in_window").read(run, records) == 1


def test_a_program_without_the_spans_gives_nothing():
    """A parent commit's ring holds no ``matrel.mmchain.plan``: the
    metric is left out, and says why; no reduced trace: every reader
    gives None and does not raise."""
    records, length = ring(chains=False)
    said = []
    assert reader("linregcg_rounds").read(
        run_of(window_ms=length, said=said), records) is None
    assert "no matrel.mmchain.plan" in said[0]
    for name in READERS[1:]:
        assert reader(name).read(run_of(0), records) is None
    assert reader("linregcg_launches").read(
        run_of(window_ms=length), [r for r in records
                                   if "launch" not in r["name"]]) is None
