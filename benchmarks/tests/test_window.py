"""The sliding-window cell's counts against hand numbers, and the eight
readers of cell ``linreg_window_10m_1c`` on a reduced trace and a ring
written by hand (``synthetic_ring.py``'s way: every answer known before
the reader runs)."""

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
N, K, C = 2_555_904, 1000, 8192
QUERY = "tick"
READERS = ["window_delta_ms", "window_upload_ms", "window_patch_roofline",
           "window_patched_pct", "window_rebases", "window_table_passes",
           "window_planned_hbm_pct", "window_compiles_in_window"]
PLANNED = 10_310_000_000


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def ring(ticks=3, rebase_in=None, kill_in=None, compile_in=None,
         table_read_in=None, deltas=True):
    """``ticks`` traced ticks of 10 ms: two ``matrel.delta`` entries (X:
    3.0 ms with a 2.0 ms upload, y: 0.5 ms with 0.1), then two
    ``matrel.compute`` roots (theta 1.5 ms over a dispatch, the cached
    view 0.2 ms). Tick ``rebase_in`` re-bases a view in its y delta (a
    100 ms span, the tick that much longer), ``kill_in`` kills a view
    that had a rule, ``compile_in`` patches with a patch it did not
    re-use, ``table_read_in``'s theta statement reads a table."""
    out, sid, t0 = [], 0, 0.0
    for u in range(ticks):
        rebased = u == rebase_in
        for name, long_ms, up_ms, patches in (("X", 3.0, 2.0, 2),
                                              ("y", 0.5, 0.1, 1)):
            if not deltas:
                continue
            root = sid + 1
            extra = 100.0 if rebased and name == "y" else 0.0
            out.append(rec("matrel.delta.upload", t0 + 0.05, up_ms, sid + 2,
                           root, u, bytes=C * K * 4))
            out.append(rec("matrel.delta.update", t0 + 0.1 + up_ms, 0.2,
                           sid + 3, root, u, rows=C, in_place=True,
                           hbm_plan_bytes=PLANNED))
            for j in range(patches):
                if rebased and name == "y":
                    out.append(rec("matrel.delta.rebase", t0 + 0.4 + up_ms,
                                   extra, sid + 4 + j, root, u, rule="rows",
                                   table_pass=True, err_bound=4e-6))
                else:
                    out.append(rec(
                        "matrel.delta.patch", t0 + 0.35 + up_ms + 0.1 * j,
                        0.05, sid + 4 + j, root, u, rule="rows",
                        reused=not (u == compile_in and name == "X"),
                        table_pass=False, err_bound=1e-7))
            killed = 2 if u == kill_in and name == "X" else 1
            out.append(rec("matrel.delta", t0, long_ms + extra, root, None,
                           u, delta_kind="rows", in_place=True,
                           patched=patches, killed=killed if name == "X"
                           else 0, no_rule=1 if name == "X" else 0,
                           rebased=int(rebased and name == "y"),
                           table_passes=int(rebased and name == "y")))
            sid += 8
            t0 += long_ms + extra
        for text, long_ms in (("theta", 1.5), ("xty", 0.2)):
            root = sid + 1
            reads = text == "theta" and u == table_read_in
            out.append(rec("matrel.rc.probe", t0 + 0.05, 0.1, sid + 2, root,
                           u, hit=text == "xty",
                           views_hit=1 if text == "xty" or reads else 2,
                           table_pass=reads))
            if text == "theta":
                out.append(rec("matrel.dispatch", t0 + 0.3, 0.3, sid + 3,
                               root, u, hbm_plan_bytes=12_000_000))
            out.append(rec("matrel.compute", t0, long_ms, root, None, u))
            sid += 4
            t0 += long_ms + 0.1
        t0 += 10.0 - 5.4
    return sorted(out, key=lambda r: r["start_ns"]), t0


def run_of(ticks=3, window_ms=None, said=None, rebases_a_tick=0.0):
    return types.SimpleNamespace(
        reduced={"queries": [{"template": QUERY}] * ticks,
                 "window_s": (window_ms or 1e4) * 1e-3} if ticks else None,
        shapes={QUERY: {"c": C, "k": K, "n": N, "itemsize": 4,
                        "precision": "highest",
                        "rebases_a_tick": rebases_a_tick}},
        peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        say=(said.append if said is not None else lambda line: None))


def test_counts_against_hand_numbers():
    """A batch of 2 rows of 3 columns, by hand: two Grams of 2 x 3 x 4
    operations and two right-hand sides of 2 x 2 x 3; the batch in and
    the old rows out with their responses (2 x 2 x 4 numbers), the 3 x 3
    and 3 x 1 views read and written (2 x 12)."""
    counts = harness.load_module(
        os.path.join(BENCH, "counts", "window.py")).counts
    assert counts(c=2, k=3, n=10, itemsize=4, precision="highest") == {
        "flops": 2 * 24 + 2 * 12, "bytes": 4 * (16 + 24),
        "precision": "highest"}
    # a re-base every tick adds the regression's Gram and right-hand
    # side over all 10 rows: 10 x 3 x 4 + 2 x 10 x 3, and a read of them
    every = counts(c=2, k=3, n=10, itemsize=4, precision="highest",
                   rebases_a_tick=1.0)
    assert every["flops"] == 72 + 120 + 60
    assert every["bytes"] == 160 + 4 * (30 + 10)
    # the cell: MXU bound (0.5 ms at six passes against 0.1 ms of HBM),
    # 156 times less than a refit a tick
    full = counts(c=C, k=K, n=N, itemsize=4, precision="highest")
    assert full["flops"] == 2 * C * K * (K + 1) + 4 * C * K \
        == 16_433_152_000
    assert full["bytes"] == 4 * (2 * C * 1001 + 2 * 1_001_000) \
        == 73_609_536
    assert full["flops"] * 6 / PEAKS["bf16_flops_per_s"] \
        == pytest.approx(0.5005e-3, rel=1e-3)
    assert full["bytes"] / PEAKS["hbm_bytes_per_s"] \
        == pytest.approx(0.0899e-3, rel=1e-3)
    assert N * K * (K + 1) / (2 * C * K * (K + 1)) == 156


def test_roofline_is_the_counts_least_time_over_the_ticks_device_time():
    said = []
    run = run_of(said=said)
    run.reduced = {"n_device_ops": 70, "chips_traced": 1, "window_s": 0.2,
                   "queries": [{"template": QUERY, "device_s": 0.0024},
                               {"template": QUERY, "device_s": 0.0026}]}
    v = reader("window_patch_roofline").read(run)
    assert v == pytest.approx(100.0 * 0.5005e-3 / 0.0025, rel=1e-3)
    assert "bound=mxu" in said[0]
    # no clamp: a device time too short to be true reads over 100
    run.reduced["queries"] = [{"template": QUERY, "device_s": 0.0004}]
    assert reader("window_patch_roofline").read(run) > 100
    # a window that re-based every hundredth tick is charged for it
    run.shapes[QUERY]["rebases_a_tick"] = 0.01
    run.reduced["queries"] = [{"template": QUERY, "device_s": 0.0025}]
    assert reader("window_patch_roofline").read(run) == pytest.approx(
        100.0 * (0.5005e-3 + 0.01 * 78.1e-3) / 0.0025, rel=2e-3)
    run.reduced = None
    assert reader("window_patch_roofline").read(run) is None


def test_the_span_readers_count_a_tick_as_two_deltas_and_two_statements():
    records, length = ring()
    run = run_of(window_ms=length)
    assert reader("window_delta_ms").read(run, records) \
        == pytest.approx(3.5)
    assert reader("window_upload_ms").read(run, records) \
        == pytest.approx(2.1)
    assert reader("window_patched_pct").read(run, records) == 100.0
    assert reader("window_rebases").read(run, records) == 0
    assert reader("window_table_passes").read(run, records) == 0
    assert reader("window_compiles_in_window").read(run, records) == 0
    assert reader("window_planned_hbm_pct").read(
        run, records, bytes_limit=2 * PLANNED) == pytest.approx(50.0)


def test_a_rebase_a_kill_a_compile_and_a_table_read_show():
    records, length = ring(5, rebase_in=1, kill_in=3, compile_in=2,
                           table_read_in=4)
    run = run_of(5, window_ms=length)
    # the median tick is a steady one; the re-based tick is one of five
    assert reader("window_delta_ms").read(run, records) \
        == pytest.approx(3.5)
    assert reader("window_patched_pct").read(run, records) \
        == pytest.approx(60.0)
    assert reader("window_rebases").read(run, records) == 1
    assert reader("window_table_passes").read(run, records) \
        == pytest.approx(1 / 5)
    assert reader("window_compiles_in_window").read(run, records) == 2


def test_a_program_without_the_spans_gives_nothing():
    """A parent commit's ring holds no ``matrel.delta``: every metric is
    left out, and says why; no reduced trace: every reader gives None
    and does not raise."""
    records, length = ring(deltas=False)
    for name in READERS:
        if name == "window_patch_roofline":
            continue
        said = []
        assert reader(name).read(
            run_of(window_ms=length, said=said), records) is None, name
        assert reader(name).read(run_of(0), records) is None
    assert "no matrel.delta in the window" in " ".join(said) \
        or "no matrel.delta.update" in " ".join(said) or not said
    said = []
    assert reader("window_delta_ms").read(
        run_of(window_ms=length, said=said), records) is None
    assert "no matrel.delta in the window" in said[0]
