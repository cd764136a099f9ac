"""The benchmark's deployment ``systemml_linregcg_10m`` (PR 54) in
tier-1, in ``test_bench_wcc.py``'s manner: what ``BENCHMARK.json`` and
the configuration's file say of the cell, its per-layer readers and
counts on synthetic records (the benchmark's own tests of them, run here
too), its generator against ``matrel_linreg_10m``'s, its plain reference
against a second plain implementation, the deployment at a rehearsal's
scale through the kernel, the bfloat16 control and an un-fused chain
turning ``correct`` false, the probe that turns a program without the
node away before any data is made, and the cell's rehearsal end to
end."""

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
from test_linregcg import (  # noqa: E402,F401
    test_a_program_without_the_spans_gives_nothing,
    test_counts_against_hand_numbers,
    test_roofline_is_the_counts_least_time_over_the_device_time,
    test_the_span_readers_take_every_statement_as_a_query_root)

CELL, QUERY = "linregcg_10m_1c", "beta_cg"
NAME = "systemml_linregcg_10m"
SCALE = 0.0064          # the traffic file's: 16 panels of 1,024 rows


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def test_benchmark_json_names_the_cell_and_its_metrics():
    from benchmarks import run as harness
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # appended after the ten cells and nine configurations PR 54 found
    assert bench["workloads"][10] is cell
    assert bench["configs"][9]["name"] == NAME
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "linregcg_roofline", "linregcg_rounds", "linregcg_launches",
        "linregcg_planned_hbm_pct", "linregcg_compiles_in_window"]
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 5] == mine
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] == ("query_p95_ms" if "compiles" in m["name"]
                              else "query_p50_ms")
    assert mine[0]["source"] == "device_trace" \
        and mine[0]["layer"] == "kernels"
    assert mine[2]["layer"] == "session and executor dispatch"
    config = bench["configs"][9]
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == ["rows"] and len(spec["reduced"]) == 1
    assert spec["reduced"][0].startswith("rows: 10,223,616 -> 2,555,904")
    assert config["source"] == spec["source"] and len(spec["source"]) <= 200
    assert spec["architecture"] is None
    # the traffic file holds the keys the generator reads and no other,
    # with the regression cell's values
    traffic = json.load(open(os.path.join(BENCH, "workloads",
                                          cell["traffic"] + ".json")))
    other = json.load(open(os.path.join(BENCH, "workloads",
                                        "linreg_10m_1c.json")))
    assert set(traffic) == harness.TRAFFIC_KEYS
    assert traffic["mix"] == [{"query": QUERY, "weight": 1}]
    for key in ("warm_calls", "check_every", "check_max", "trace_seconds",
                "trace_max_queries"):
        assert traffic[key] == other[key]
    assert 2_555_904 * traffic["rehearse_scale"] / 1024 >= 15.5
    # the shapes are the regression cell's, never changed; the script's
    # parameters and the loop's lines are the issue's
    theirs = json.load(open(os.path.join(BENCH, "configs",
                                         "matrel_linreg_10m.json")))
    for key in ("tables", "rows", "published_rows", "panel_rows",
                "chips_sharing_the_table", "reference_panel_rows",
                "noise_sigma", "dtype", "matmul_precision"):
        assert spec[key] == theirs[key], key
    assert spec["cg"] == {"reg": 1e-6, "tol": 1e-6, "maxi": 0, "icpt": 0}
    q = spec["queries"][QUERY]
    assert q["sql"]["q"] == "t(X) * (X * p) + p * lam"
    assert q["sql"]["a"] == "rr / (t(p) * q)"
    assert q["sql"]["p"] == "p * (rr2 / rr) - r"
    assert q["device_op"] == "matrel_mmchain"
    assert 1e-6 < q["limit"] < 2e-5 and "bfloat16" in q["limit_readings"]
    # no value in any statement's text
    assert not any(ch.isdigit() for text in q["sql"].values()
                   for ch in text.replace("rr2", "").replace("p0", ""))


@pytest.fixture(scope="module")
def dep():
    """The deployment as a rehearsal builds it (16,384 x 1000, Pallas
    interpreted)."""
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    assert [m["query"] for m in traffic["mix"]] == [QUERY]
    was = config_lib._default_config
    d = harness.build_deployment(config, spec, 2147483999, [QUERY], SCALE)
    try:
        yield d
    finally:
        config_lib._default_config = was


def _checks(dep, answer, want):
    return dict((label, (value, limit)) for label, value, limit in
                dep.compare(QUERY, answer, want))


def test_the_generator_is_the_regression_cells(dep):
    """Its own copy of ``matrel_linreg_10m``'s: the same seed gives the
    same tables, to the bit."""
    from benchmarks import run as harness
    _, _, config, spec, _ = harness.load_cell("linreg_10m_1c")
    theirs = harness.build_deployment(config, spec, 2147483999, ["theta"],
                                      SCALE)
    assert theirs.n == dep.n == 16_384 and dep.k == 1000
    for name in ("X", "y"):
        np.testing.assert_array_equal(np.asarray(dep.arrays[name]),
                                      np.asarray(theirs.arrays[name]))


def test_the_reference_is_a_second_plain_implementations(dep):
    """LinearRegCG.dml's loop over the whole table in float64 numpy: the
    same rounds and, to the panels' float32 products, the same beta; and
    CG's answer is the normal equations' to its stopping rule."""
    x = np.asarray(dep.arrays["X"], np.float64)
    y = np.asarray(dep.arrays["y"], np.float64)
    r = -(x.T @ y)
    p, rr, beta, rounds = -r, float(np.sum(r * r)), 0.0, 0
    target = rr * dep.tol ** 2
    while rounds < dep.k and rr > target:
        q = x.T @ (x @ p) + dep.reg * p
        a = rr / float(np.sum(p * q))
        beta, r = beta + a * p, r + a * q
        rr_new = float(np.sum(r * r))
        p, rr = -r + (rr_new / rr) * p, rr_new
        rounds += 1
    want, want_rounds = dep.reference(QUERY)
    assert want_rounds == rounds and 3 <= rounds <= 40
    assert want.dtype == np.float64 and want.shape == (dep.k, 1)
    assert np.max(np.abs(want - beta)) / np.max(np.abs(beta)) < 2e-6
    exact = np.linalg.solve(x.T @ x + dep.reg * np.eye(dep.k), x.T @ y)
    assert np.max(np.abs(want - exact)) / np.max(np.abs(exact)) < 1e-4


def test_deployment_answers_every_chain_in_one_read(dep):
    from benchmarks import run as harness
    dep.run(QUERY, harness.no_span)
    ans = dep.run(QUERY, harness.no_span)
    notes = dep.notes(QUERY)
    assert notes["chain"]["one_read"] is True
    assert notes["chain"]["rows"] == 16_384 and notes["chain"]["cols"] == 1000
    assert notes["chain"]["tile_rows"] == 2048
    # the table, the vectors, the kernel's lanes: no second table
    table = 16_384 * 1000 * 4
    assert table < notes["chain"]["hbm_plan_bytes"] < 1.1 * table
    want = dep.reference(QUERY)
    got = _checks(dep, ans, want)
    assert all(value <= limit for value, limit in got.values()), got
    assert got[f"{QUERY}.rounds_off"] == (0, 0)
    assert got[f"{QUERY}.chains_not_fused"] == (0, 0)
    assert got[f"{QUERY}.compiles_after_first_query"] == (0, 0)
    rounds = dep.shapes(QUERY)["rounds"]
    assert ans[1] == want[1] == rounds > 2
    assert notes["statements"] == 3 + 6 * rounds
    assert set(dep.shapes(QUERY)) == {"n", "k", "itemsize", "rounds",
                                      "precision"}
    # an answer scaled as the harness's own test of a broken path does
    value, limit = _checks(dep, ans * 1.001, want)[f"{QUERY}.max_rel_err"]
    assert value > limit


def test_the_bfloat16_control_is_not_correct(dep):
    want = dep.reference(QUERY)
    got = _checks(dep, dep.control(QUERY), want)
    value, limit = got[f"{QUERY}.max_rel_err"]
    assert value > limit


def test_the_programs_lower_precisions_un_fuse_by_name(dep):
    """``program_controls``: the same tables in sessions of a lower
    ``matmul_precision``, whose chains the planner writes back as two
    products (on the CPU their numbers do not differ: the chip's do)."""
    knobs = dep.program_controls(QUERY)
    assert [k for k, _ in knobs] == ["matmul_precision=high",
                                    "matmul_precision=default"]
    for precision, sess in dep._lower.items():
        sess.compute(sess.sql("t(X) * (X * p)"))
        (rec,) = sess.last_plan()["mmchain"]
        assert rec["why_not"] == "matmul_precision"
    for _, (beta, rounds) in knobs:
        assert beta.shape == (dep.k, 1) and rounds == dep.rounds


def test_a_chain_answered_as_two_products_is_not_correct(dep):
    """A silent fall to two reads of X: beta is right and the run is
    not correct."""
    import dataclasses
    from benchmarks import run as harness
    want = dep.reference(QUERY)
    was, before = dep.session, dep.chains_not_fused
    try:
        dep.session = dep._session(dataclasses.replace(
            dep._config, pallas_interpret=False))
        ans = dep.run(QUERY, harness.no_span)
    finally:
        dep.session = was
    got = _checks(dep, ans, want)
    value, limit = got[f"{QUERY}.max_rel_err"]
    assert value <= limit
    assert got[f"{QUERY}.chains_not_fused"][0] == ans[1] > 0
    assert not all(value <= limit for value, limit in got.values())
    assert dep.facts["why_not"] == "pallas_off"
    dep.chains_not_fused = before


def test_a_program_without_the_node_is_turned_away_at_once(monkeypatch):
    """A parent commit: the rule is not there, ``last_plan()`` names no
    chain, and the Deployment raises before any table is made."""
    from benchmarks import run as harness
    from matrel_tpu.ir import rules
    from matrel_tpu.session import MatrelSession
    mod = _load("configs", NAME + ".py")
    _, _, config, spec, _ = harness.load_cell(CELL)
    made = []
    monkeypatch.setattr(mod, "device_key", lambda seed: made.append(seed))
    ok, said = mod.can_serve(interpret=True)
    assert ok and said["mmchain"][0]["rows"] == 256
    monkeypatch.setattr(rules, "_RULES", [
        r for r in rules._RULES if r is not rules.mmchain_product])
    ok, said = mod.can_serve(interpret=True)
    assert not ok and not said["mmchain"]
    with pytest.raises(RuntimeError, match=NAME + ": this program cannot "
                       "serve the deployment"):
        mod.Deployment(spec, 5, [QUERY], scale=SCALE, interpret=True)
    assert not made
    monkeypatch.delattr(MatrelSession, "last_plan")
    assert mod.can_serve(interpret=True) == (
        False, "no MatrelSession.last_plan")


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_cell_rehearses_end_to_end(capsys, trace):
    """``run.py --rehearse`` on the cell (16,384 rows): set-up, warm-up,
    a window, the check against the reference, one result line with no
    metric value; traced, every per-layer reader of the cell is
    called."""
    from benchmarks import run as harness
    was = config_lib._default_config
    try:
        rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "1", "--rehearse", str(SCALE),
                           "--trace", trace])
    finally:
        config_lib._default_config = was
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and "metrics" not in result
    assert any(line.startswith(f"check {QUERY}.chains_not_fused value=0")
               for line in out)
    assert any(line.startswith(f"check {QUERY}.rounds_off value=0")
               for line in out)
    if trace == "1":
        assert {"linregcg_rounds", "linregcg_launches",
                "linregcg_compiles_in_window"} <= set(
            result["metric_names"])
