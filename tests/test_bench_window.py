"""The benchmark's deployment ``fivm_linreg_window_10m`` (PR 56) in
tier-1, in ``test_bench_linregcg.py``'s manner: what ``BENCHMARK.json``
and the configuration's file say of the cell, its per-layer readers and
counts on synthetic records (the benchmark's own tests of them, run here
too), its generator against ``matrel_linreg_10m``'s, its plain reference
against a second plain implementation over several turnovers of the
ring, the deployment at a rehearsal's scale, the stale-view and
bfloat16 controls turning ``correct`` false, the durability check, the
probe that turns a program without the ``rows`` kind away before any
data is made, and the cell's rehearsal end to end."""

import json
import os
import sys

import numpy as np
import pytest

from matrel_tpu import config as config_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "tests"))

# the benchmark's own tests of the cell's readers: tier-1 runs them too
from test_window import (  # noqa: E402,F401
    test_a_program_without_the_spans_gives_nothing,
    test_a_rebase_a_kill_a_compile_and_a_table_read_show,
    test_counts_against_hand_numbers,
    test_roofline_is_the_counts_least_time_over_the_ticks_device_time,
    test_the_span_readers_count_a_tick_as_two_deltas_and_two_statements)

CELL, QUERY = "linreg_window_10m_1c", "tick"
NAME = "fivm_linreg_window_10m"
SCALE = 0.0064          # the traffic file's: 16 slots of 1,024 rows
SEED = 2147483999


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def test_benchmark_json_names_the_cell_and_its_metrics():
    from benchmarks import run as harness
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "register_delta rows" in cell["why"]
    # appended after the eleven cells and ten configurations PR 56 found
    assert bench["workloads"][11] is cell
    assert bench["configs"][10]["name"] == NAME
    assert len(bench["configs"][10]["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "window_delta_ms", "window_upload_ms", "window_patch_roofline",
        "window_patched_pct", "window_rebases", "window_table_passes",
        "window_planned_hbm_pct", "window_compiles_in_window"]
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 8] == mine
    assert first + 8 == len(bench["per_layer"])
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    assert [m["layer"] for m in mine] == [
        "view maintenance", "view maintenance", "kernels",
        "view maintenance", "view maintenance", "view maintenance",
        "optimizer, planner, compile", "optimizer, planner, compile"]
    assert {m["name"]: m["moves"] for m in mine if
            m["moves"] != "query_p50_ms"} == {
        "window_rebases": "query_p95_ms",
        "window_compiles_in_window": "query_p95_ms"}
    assert mine[2]["source"] == "device_trace" and mine[2]["unit"] == "%"
    # every metric without a list of cells is one the harness reads off
    # any cell's trace
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} == {
        "session_host_ms", "first_call_s", "kernel_ms", "device_idle_pct"}
    config = bench["configs"][10]
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == ["rows"] and len(spec["reduced"]) == 1
    assert spec["reduced"][0].startswith("rows: 10,223,616 -> 2,555,904")
    assert config["source"] == spec["source"] and len(spec["source"]) <= 200
    assert spec["architecture"] is None
    traffic = json.load(open(os.path.join(BENCH, "workloads",
                                          cell["traffic"] + ".json")))
    assert set(traffic) == harness.TRAFFIC_KEYS
    assert traffic == {"mix": [{"query": QUERY, "weight": 1}],
                       "warm_calls": 2, "check_every": 400, "check_max": 8,
                       "trace_seconds": 4, "trace_max_queries": 64,
                       "rehearse_scale": SCALE}
    assert round(2_555_904 * SCALE / 1024) >= 16
    # the shapes are the regression cell's, never changed
    theirs = json.load(open(os.path.join(BENCH, "configs",
                                         "matrel_linreg_10m.json")))
    for key in ("tables", "rows", "published_rows", "panel_rows",
                "chips_sharing_the_table", "reference_panel_rows",
                "noise_sigma", "dtype", "matmul_precision"):
        assert spec[key] == theirs[key], key
    w = spec["window"]
    assert (w["batch_rows"], w["slots"], w["pool_batches"]) == (8192, 312, 64)
    assert w["batch_rows"] * w["slots"] == spec["rows"]
    assert spec["result_cache_max_bytes"] > 0
    assert spec["views"] == ["t(X) * X", "t(X) * y"]
    q = spec["queries"][QUERY]
    assert q["sql"] == {"theta": theirs["queries"]["theta"]["sql"],
                        "xty": "t(X) * y"}
    assert 5e-7 < q["limit"] < 2e-5 and "bfloat16" in q["limit_readings"]
    assert len(spec["guarantees"]) == 5


@pytest.fixture(scope="module")
def dep():
    """The deployment as a rehearsal builds it (16 slots of 1,024 rows
    of 1000 columns, a pool of 7 batches)."""
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    assert [m["query"] for m in traffic["mix"]] == [QUERY]
    was = config_lib._default_config
    d = harness.build_deployment(config, spec, SEED, [QUERY], SCALE)
    try:
        yield d
    finally:
        config_lib._default_config = was


def _checks(dep, answer):
    return dict((label, (value, limit)) for label, value, limit in
                dep.compare(QUERY, answer, dep.reference(QUERY)))


def test_the_generator_is_the_regression_cells(dep):
    """Its own copy of ``matrel_linreg_10m``'s: the same seed gives the
    same tables, to the bit (read before the first tick)."""
    from benchmarks import run as harness
    assert dep.ticks == 0
    _, _, config, spec, _ = harness.load_cell("linreg_10m_1c")
    theirs = harness.build_deployment(config, spec, SEED, ["theta"], SCALE)
    assert theirs.n == dep.n == 16_384 and dep.k == 1000
    assert (dep.slots, dep.batch, dep.pool) == (16, 1024, 7)
    for name in ("X", "y"):
        np.testing.assert_array_equal(np.asarray(dep.arrays[name]),
                                      np.asarray(theirs.arrays[name]))
    # and the batches are the table's distribution
    assert all(b.shape == (1024, 1000) and b.dtype == np.float32
               and -1.0 <= b.min() and b.max() < 1.0 for b in dep.pool_x)
    assert abs(float(np.mean(dep.pool_x[0]))) < 5e-3
    assert dep.slots_differing() == 0


def test_the_ring_says_what_every_slot_holds(dep):
    # tick t replaces slot t mod 16 by batch t mod 7
    assert [dep.batch_in(s, 0) for s in (0, 15)] == [None, None]
    assert [dep.batch_in(s, 3) for s in range(5)] == [0, 1, 2, None, None]
    assert [dep.batch_in(s, 16) for s in (0, 7, 8, 15)] == [0, 0, 1, 1]
    assert dep.batch_in(0, 17) == 16 % 7 and dep.batch_in(1, 17) == 1
    assert dep.batch_in(5, 40) == 37 % 7
    # a slot's next batch is never the one it holds, here and at size
    assert all(dep.batch_in(t % 16, t + 1) != dep.batch_in(t % 16, t)
               for t in range(16, 200))
    assert 312 % 64 and 16 % 7


def test_deployment_ticks_and_the_reference_follows_it(dep):
    """Three turnovers of the ring through the timed path; the answer
    of a tick against the reference at that tick, and the reference
    against float64 numpy over the tables read back from the device."""
    from benchmarks import run as harness
    limit = dep.spec["queries"][QUERY]["limit"]
    for t in range(1, 3 * dep.slots + 1):
        ans = dep.run(QUERY, harness.no_span)
        assert ans[2] == dep.ticks == t
        if t in (1, 2, dep.slots - 1, dep.slots, 2 * dep.slots + 5,
                 3 * dep.slots):
            x = np.asarray(dep.arrays["X"], np.float64)
            y = np.asarray(dep.arrays["y"], np.float64)
            theta, xty = dep.reference(QUERY)(t)
            assert np.max(np.abs(xty - x.T @ y)) \
                / np.max(np.abs(xty)) < 1e-6
            exact = np.linalg.solve(x.T @ x, x.T @ y)
            assert np.max(np.abs(theta - exact)) \
                / np.max(np.abs(exact)) < 1e-6
            got = _checks(dep, ans)
            assert all(value <= lim for value, lim in got.values()), got
            assert got[f"{QUERY}.theta_max_rel_err"][1] == limit
    notes = dep.notes(QUERY)
    assert notes["said"]["X"]["in_place"] is True
    assert notes["said"]["X"]["patched"] == 2
    assert notes["said"]["y"]["patched"] == 1
    assert notes["said"]["theta"]["views_hit"] == 2
    assert notes["said"]["theta"]["table_pass"] is False
    assert notes["table_passes"] == notes["reads_over_the_table"] == 0
    assert notes["compiles_after_first"] == notes["unpatched"] == 0
    assert 0 < notes["rebases"] < 0.2 * dep.ticks    # a ring of 16 slots
    assert set(dep.shapes(QUERY)) == {"c", "k", "n", "itemsize",
                                      "precision", "rebases_a_tick"}
    assert dep.slots_differing() == 0
    # an answer scaled as the harness's own test of a broken path does
    got = _checks(dep, ans * 1.001)
    assert got[f"{QUERY}.theta_max_rel_err"][0] > limit
    assert got[f"{QUERY}.xty_max_rel_err"][0] > limit
    # an answer of another tick is not this tick's
    late = type(ans)((ans[0], ans[1], ans[2] - 1))
    assert _checks(dep, late)[f"{QUERY}.xty_max_rel_err"][0] > limit


def test_a_lost_write_shows_as_a_slot_that_differs(dep):
    """Durability: one element of one slot changed behind the session's
    back, in a slot a batch was written to."""
    import jax.numpy as jnp
    table = dep._tables_of["y"]
    kept = table.data
    row = 3 * dep.batch + 7
    table.data = kept.at[row, 0].set(kept[row, 0] + jnp.float32(1e-3))
    dep._checked = None
    try:
        assert dep.slots_differing() == 1
    finally:
        table.data = kept
        dep._checked = None
    assert dep.slots_differing() == 0


def test_the_controls_are_not_correct(dep):
    limit = dep.spec["queries"][QUERY]["limit"]
    got = _checks(dep, dep.control(QUERY))
    assert got[f"{QUERY}.theta_max_rel_err"][0] > limit
    knobs = dep.program_controls(QUERY)
    assert [k for k, _ in knobs] == ["views_of_the_tick_before",
                                    "matmul_precision=default"]
    stale = _checks(dep, knobs[0][1])
    # one batch moves t(X) * y by far more than the limit; on a ring of
    # 16 slots theta moves with it
    assert stale[f"{QUERY}.xty_max_rel_err"][0] > 100 * limit
    # on the CPU the lower precision computes what highest does: the
    # chip's readings are in the configuration file
    low = _checks(dep, knobs[1][1])
    assert low[f"{QUERY}.xty_max_rel_err"][0] <= limit
    # the deployment's own session was made again, and ticks on
    from benchmarks import run as harness
    before = dict(dep.counts)
    ans = dep.run(QUERY, harness.no_span)
    ans = dep.run(QUERY, harness.no_span)
    got = _checks(dep, ans)
    assert got[f"{QUERY}.xty_max_rel_err"][0] <= limit
    assert dep.counts["compiles_after_first"] \
        == before["compiles_after_first"]


def test_a_program_without_the_kind_is_turned_away_at_once(monkeypatch):
    """A parent commit: ``kind="rows"`` is no kind, and the Deployment
    raises before any table is made. So does a program that takes the
    kind and kills the views."""
    from benchmarks import run as harness
    from matrel_tpu.ir import delta as delta_lib
    mod = _load("configs", NAME + ".py")
    _, _, config, spec, _ = harness.load_cell(CELL)
    made = []
    monkeypatch.setattr(mod, "device_key", lambda seed: made.append(seed))
    ok, said = mod.can_serve(interpret=True)
    assert ok and said["X"]["patched"] == 2 and said["theta"] == {
        "views_hit": 2, "table_pass": False}
    with monkeypatch.context() as m:
        m.setattr(delta_lib, "derive_rows_patch", lambda expr, t: None)
        ok, said = mod.can_serve(interpret=True)
        assert not ok and said["X"]["killed"] == 2
    real = delta_lib.as_delta

    def parents(payload, old, kind="auto", config=None):
        if kind == "rows":
            raise ValueError("unknown delta kind 'rows' (expected "
                             "'auto'/'coo'/'lowrank'/'dense')")
        return real(payload, old, kind, config)

    monkeypatch.setattr(delta_lib, "as_delta", parents)
    ok, said = mod.can_serve(interpret=True)
    assert not ok and "unknown delta kind 'rows'" in said
    with pytest.raises(RuntimeError, match=NAME + ": this program cannot "
                       "serve the deployment"):
        mod.Deployment(spec, 5, [QUERY], scale=SCALE, interpret=True)
    assert not made


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_cell_rehearses_end_to_end(capsys, trace):
    """``run.py --rehearse`` on the cell (16 slots of 1,024 rows):
    set-up, warm-up, a window, the check against the reference of the
    kept tick, one result line with no metric value; traced, every
    per-layer reader of the cell is called."""
    from benchmarks import run as harness
    was = config_lib._default_config
    try:
        rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                           "--seconds", "1", "--rehearse", str(SCALE),
                           "--trace", trace])
    finally:
        config_lib._default_config = was
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and "metrics" not in result
    for label in ("table.slots_differing", f"{QUERY}.compiles_after_first"
                  "_tick", f"{QUERY}.table_passes_outside_a_rebase",
                  f"{QUERY}.ticks_with_a_view_not_patched"):
        assert any(line.startswith(f"check {label} value=0 ")
                   for line in out), label
    assert any(line.startswith("reference tick=") for line in out)
    if trace == "1":
        assert {"window_delta_ms", "window_upload_ms", "window_patched_pct",
                "window_rebases", "window_table_passes",
                "window_compiles_in_window", "session_host_ms",
                "first_call_s"} <= set(result["metric_names"])
