"""The benchmark's deployment ``ldbc_graphalytics_wcc_g500_22`` (PR 50)
in tier-1, in ``test_bench_pnmf.py``'s manner: its per-layer readers on
synthetic records, its counts against hand numbers, its plain reference
against a second plain implementation (union-find) at a small size, the
deployment at a rehearsal's scale through the reduction kernel, the
bfloat16 control and a densified round turning ``correct`` false, the
probe that turns a program without the semiring product away before any
data is made, and the cell's rehearsal end to end."""

import json
import os
import types

import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.core import coo as coo_lib
from matrel_tpu.ops import spmv as spmv_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, QUERY = "wcc_g500_22_1c", "wcc_g500"
NAME = "ldbc_graphalytics_wcc_g500_22"
MS = 1_000_000


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def _rec(name, start_ms, dur_ms, span_id, parent_id, qid, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "end_ns": int((start_ms + dur_ms) * MS), "span_id": span_id,
            "parent_id": parent_id, "qid": qid, "tid": 1, "attrs": attrs}


def _ring(queries=2, rounds=4, compile_in=None, products=True):
    """``queries`` traced queries of ``rounds`` rounds: a round is a
    ``matrel.compute`` root of 100 ms over a dispatch that says 7 GB and
    its semiring product's span, then a root of 10 ms (the count)."""
    out, sid, t0 = [], 0, 0.0
    for u in range(queries * rounds):
        root = sid + 1
        if compile_in == u:
            out.append(_rec("matrel.compile", t0 + 2, 5, sid + 4, root, u))
        out.append(_rec("matrel.dispatch", t0 + 10, 1, sid + 2, root, u,
                        hbm_plan_bytes=7_000_000_000))
        if products:
            out.append(_rec("matrel.semiring.plan", t0 + 10.1, 0.01, sid + 3,
                            sid + 2, u, hit=True, how="kernel"))
        out.append(_rec("matrel.compute", t0, 100, root, None, u))
        out.append(_rec("matrel.dispatch", t0 + 101, 1, sid + 6, sid + 5, u,
                        hbm_plan_bytes=30_000_000))
        out.append(_rec("matrel.compute", t0 + 100.5, 10, sid + 5, None, u))
        sid += 6
        t0 += 115.0
    return sorted(out, key=lambda r: r["start_ns"])


def _run(queries=2, rounds=4, said=None):
    from benchmarks import run as harness
    return types.SimpleNamespace(
        reduced={"queries": [{"template": QUERY}] * queries,
                 "window_s": 115.0 * queries * rounds * 1e-3}
        if queries else None,
        shapes={QUERY: {"nodes": 10, "edges": 20, "rounds": rounds}},
        say=(said.append if said is not None else lambda line: None),
        here=BENCH, load_module=harness.load_module)


# -- the readers and the counts ---------------------------------------------------


def test_the_span_readers_take_every_compute_as_a_query_root():
    rounds = _load("metrics", "wcc_rounds.py")
    assert rounds.read(_run(), _ring()) == pytest.approx(4.0)
    assert rounds.read(_run(3, 7), _ring(3, 7)) == pytest.approx(7.0)
    said = []
    # a program whose ring holds no such span (a parent commit)
    assert rounds.read(_run(said=said), _ring(products=False)) is None
    assert "no matrel.semiring.plan" in said[0]
    assert rounds.read(_run(0), _ring()) is None
    compiles = _load("metrics", "wcc_compiles_in_window.py")
    assert compiles.read(_run(), _ring()) == 0
    assert compiles.read(_run(), _ring(compile_in=5)) == 1
    assert compiles.read(_run(0), _ring()) is None
    hbm = _load("metrics", "wcc_planned_hbm_pct.py")
    assert hbm.read(_run(), _ring(), bytes_limit=14_000_000_000) \
        == pytest.approx(50.0)
    assert hbm.read(_run(0), _ring(), bytes_limit=1) is None


def test_counts_against_hand_numbers():
    """5 vertices, 12 directed edges, 3 rounds, by hand: a round reads
    12 edges of 8 B and reads and writes 5 labels of 4 B (96 + 40 B) and
    compares once an edge."""
    counts = _load("counts", "wcc.py").counts
    assert counts(nodes=5, edges=12, rounds=3) == {
        "flops": 36, "bytes": 3 * (96 + 40), "precision": "highest"}
    # the cell at 8 rounds: 1.03 G compares and 8.37 GB a query
    full = counts(nodes=2_396_366, edges=128_309_282, rounds=8)
    assert full["flops"] == 1_026_474_256
    assert full["bytes"] == 8_365_161_472


def test_roofline_is_the_counts_least_time_over_the_device_time():
    reader = _load("metrics", "wcc_roofline.py")
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    said = []
    run = _run(said=said)
    run.peaks = peaks
    run.shapes = {QUERY: {"nodes": 2_396_366, "edges": 128_309_282,
                          "rounds": 8}}
    run.reduced = {"n_device_ops": 7, "chips_traced": 1, "window_s": 6.0,
                   "queries": [{"template": QUERY, "device_s": 2.9},
                               {"template": QUERY, "device_s": 3.1}]}
    assert reader.read(run) == pytest.approx(
        100.0 * (8_365_161_472 / 819e9) / 3.0)
    assert "bound=hbm" in said[0]
    run.reduced = None
    assert reader.read(run) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    from benchmarks import run as harness
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    # appended after the nine cells and eight configurations PR 50 found
    assert bench["workloads"][9] is cell
    assert bench["configs"][8]["name"] == NAME
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "wcc_compiles_in_window", "wcc_planned_hbm_pct", "wcc_roofline",
        "wcc_rounds"]
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] == ("query_p95_ms" if "compiles" in m["name"]
                              else "query_p50_ms")
    config = next(c for c in bench["configs"] if c["name"] == NAME)
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == spec["reduced"] == []
    assert config["source"] == spec["source"]
    # the traffic file holds the keys the generator reads and no other
    traffic = json.load(open(os.path.join(BENCH, "workloads",
                                          cell["traffic"] + ".json")))
    assert set(traffic) == harness.TRAFFIC_KEYS
    assert traffic["mix"] == [{"query": QUERY, "weight": 1}]
    # the round is the issue's text, and the graph cell 6's
    assert spec["queries"][QUERY]["round_sql"] == \
        'elemmax(L, rowmax(joincols(A, t(L), "mul")))'
    other = json.load(open(os.path.join(
        BENCH, "configs", "ldbc_graphalytics_g500_22.json")))
    assert spec["graph"] == other["graph"]


# -- the plain reference ---------------------------------------------------------


def _union_find_labels(lo, hi, n):
    """A second plain implementation: union by the larger root, so a
    component's root is its largest vertex id."""
    parent = np.arange(n)

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for a, b in zip(lo.tolist(), hi.tolist()):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[min(ra, rb)] = max(ra, rb)
    return np.array([find(v) + 1.0 for v in range(n)])


def _rounds_by_sets(lo, hi, n):
    """The synchronous propagation on python dicts, round by round."""
    nbrs = {v: [] for v in range(n)}
    for a, b in zip(lo.tolist(), hi.tolist()):
        nbrs[a].append(b)
        nbrs[b].append(a)
    L = {v: v + 1 for v in range(n)}
    for rounds in range(1, 10_000):
        new = {v: max([L[v]] + [L[u] for u in nbrs[v]]) for v in L}
        if new == L:
            return rounds
        L = new


@pytest.fixture(scope="module")
def mod():
    return _load("configs", NAME + ".py")


def test_the_reference_is_a_second_plain_implementations(mod):
    rng = np.random.default_rng(50)
    n = 900
    group = rng.integers(0, 9, n)
    a, b = rng.integers(0, n, 1_500), rng.integers(0, n, 1_500)
    same = (group[a] == group[b]) & (a != b)
    keys = np.unique(np.minimum(a, b)[same] * n + np.maximum(a, b)[same])
    lo, hi = keys // n, keys % n
    want, count = mod.component_labels(lo, hi, n)
    np.testing.assert_array_equal(want, _union_find_labels(lo, hi, n))
    assert count == np.unique(want).size > 9
    labels, rounds = mod.propagate(lo, hi, n)
    np.testing.assert_array_equal(labels, want)
    assert rounds == _rounds_by_sets(lo, hi, n) > 2
    # the generator is cell 6's own, to the edge
    other = _load("configs", "ldbc_graphalytics_g500_22.py")
    mine = mod.kronecker_graph(10, 16, [0.57, 0.19, 0.19], 1)
    theirs = other.kronecker_graph(10, 16, [0.57, 0.19, 0.19], 1)
    for got, exp in zip(mine, theirs):
        np.testing.assert_array_equal(got, exp)


# -- the deployment at a rehearsal's scale ---------------------------------------


@pytest.fixture(scope="module")
def dep():
    """The deployment as a rehearsal builds it (Kronecker scale 10,
    Pallas interpreted), its plan in chunks as the real graph's is."""
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    assert [m["query"] for m in traffic["mix"]] == [QUERY]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(coo_lib, "_plan_layout", lambda: "chunks")
        patch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
        was = config_lib._default_config
        d = harness.build_deployment(config, spec, 2147483999, [QUERY],
                                     2.0 ** -12)
        try:
            yield d
        finally:
            config_lib._default_config = was


def _checks(dep, answer, want):
    return dict((label, (value, limit)) for label, value, limit in
                dep.compare(QUERY, answer, want))


def test_deployment_answers_every_label_through_the_kernel(dep):
    from benchmarks import run as harness
    assert dep.kron_scale == 10 and dep.edges == 2 * dep.lo.size
    dep.run(QUERY, harness.no_span)
    ans = dep.run(QUERY, harness.no_span)
    notes = dep.notes(QUERY)
    assert notes["plan"]["how"] == "kernel"
    assert notes["plan"]["layout"] == "chunks" and notes["plan_builds"] == 1
    want = dep.reference(QUERY)
    got = _checks(dep, ans, want)
    assert all(value <= limit for value, limit in got.values()), got
    assert got[f"{QUERY}.label_mismatches"] == (0, 0)
    assert got[f"{QUERY}.rounds_not_by_kernel"][0] == 0
    assert got[f"{QUERY}.compiles_after_first_query"] == (0, 0)
    assert ans[1] == want[2] == dep.shapes(QUERY)["rounds"] > 2
    assert set(dep.shapes(QUERY)) == {"nodes", "edges", "rounds"}
    assert dep.program_controls(QUERY) == []
    # an answer scaled as the harness's own test of a broken path does
    assert _checks(dep, ans * 1.001, want)[
        f"{QUERY}.label_mismatches"][0] == dep.nodes


def test_the_bfloat16_control_is_not_correct(dep):
    want = dep.reference(QUERY)
    got = _checks(dep, dep.control(QUERY), want)
    value, limit = got[f"{QUERY}.label_mismatches"]
    assert value > limit == 0


def test_a_round_a_densified_join_gave_is_not_correct(dep):
    """A silent fall to the materialised join: the labels are right and
    the run is not correct."""
    from benchmarks import run as harness
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.session import MatrelSession
    want = dep.reference(QUERY)
    was = dep.session
    try:
        dep.session = MatrelSession(
            mesh=dep._mesh, config=MatrelConfig(rewrite_rules=False))
        dep.session.register("A", was.table("A"))
        ans = dep.run(QUERY, harness.no_span)
    finally:
        dep.session = was
    got = _checks(dep, ans, want)
    assert got[f"{QUERY}.label_mismatches"] == (0, 0)
    assert got[f"{QUERY}.densified_products"][0] > 0
    assert got[f"{QUERY}.rounds_not_by_kernel"][0] > 0
    assert not all(value <= limit for value, limit in got.values())


def test_a_program_without_the_semiring_product_is_turned_away_at_once(
        mod, monkeypatch):
    """A parent commit: the rule is not there, the toy round densifies,
    and the Deployment raises before any graph is made."""
    from benchmarks import run as harness
    from matrel_tpu.ir import rules
    from matrel_tpu.session import MatrelSession
    _, _, config, spec, _ = harness.load_cell(CELL)
    was = config_lib._default_config
    made = []
    monkeypatch.setattr(mod, "kronecker_graph",
                        lambda *a: made.append(a))
    try:
        assert mod.can_serve(interpret=True)[0]
        monkeypatch.setattr(rules, "_RULES", [
            r for r in rules._RULES if r is not rules.semiring_product])
        monkeypatch.setattr(rules, "_RULES_AHEAD_OF_CHAIN_DP", [
            r for r in rules._RULES_AHEAD_OF_CHAIN_DP
            if r is not rules.semiring_product])
        ok, said = mod.can_serve(interpret=True)
        assert not ok and said["densified_products"]
        with pytest.raises(RuntimeError, match=NAME + ": this program "
                           "cannot serve the deployment"):
            mod.Deployment(spec, 5, [QUERY], scale=0.02, interpret=True)
        assert not made
        monkeypatch.delattr(MatrelSession, "last_plan")
        assert mod.can_serve(interpret=True) == (
            False, "no MatrelSession.last_plan")
    finally:
        config_lib._default_config = was


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_cell_rehearses_end_to_end(capsys, trace):
    """``run.py --rehearse`` on the cell (Kronecker scale 12): set-up,
    warm-up, a window, the check against the reference, one result line
    with no metric value; traced, every per-layer reader of the cell is
    called."""
    from benchmarks import run as harness
    was = config_lib._default_config
    try:
        rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "1", "--rehearse", "0.001",
                           "--trace", trace])
    finally:
        config_lib._default_config = was
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and "metrics" not in result
    assert any(line.startswith(f"check {QUERY}.label_mismatches value=0")
               for line in out)
    if trace == "1":
        assert {"wcc_rounds", "wcc_compiles_in_window"} <= set(
            result["metric_names"])
