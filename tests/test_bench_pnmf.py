"""The benchmark's deployment ``systemml_pnmf_netflix`` (PR 46) in tier-1,
in ``test_bench_gnmf.py``'s manner: its per-layer readers on synthetic
records, its counts against a hand count, its plain reference against the
float64 fit at a small scale, the cell's rehearsal end to end (traced and
untraced), ``compare`` turning an answer that a densified leaf or a late
compile gave into not correct, and the probe that turns a program without
the sampled product away before any data is made."""

import json
import os
import types

import numpy as np
import pytest

from matrel_tpu import config as config_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, QUERY = "pnmf_netflix_r128_1c", "pnmf_fit"
MS = 1_000_000


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def _rec(name, start_ms, dur_ms, span_id, parent_id, qid, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "end_ns": int((start_ms + dur_ms) * MS), "span_id": span_id,
            "parent_id": parent_id, "qid": qid, "tid": 1, "attrs": attrs}


def _ring(fits=2, iterations=3, compile_in=None, plan_attrs=True):
    """``fits`` traced fits of ``2 * iterations`` updates of 100 ms, each
    a ``matrel.compute`` root over a dispatch that says 7 GB and, of its
    sampled product, 90 (transposed) or 94 (forward) of 100 entries on
    the slab."""
    out, sid = [], 0
    for u in range(fits * 2 * iterations):
        t0, root = 110.0 * u, sid + 1
        forward = u % 2
        if compile_in == u:
            out.append(_rec("matrel.compile", t0 + 2, 5, sid + 4, root, u))
        out.append(_rec("matrel.dispatch", t0 + 10, 1, sid + 2, root, u,
                        hbm_plan_bytes=7_000_000_000 + forward))
        said = dict(entries=100, dense_entries=94 if forward else 90,
                    orientation="forward" if forward else "transposed") \
            if plan_attrs else {}
        out.append(_rec("matrel.sampled.plan", t0 + 10.1, 0.01, sid + 3,
                        sid + 2, u, hit=True, **said))
        out.append(_rec("matrel.compute", t0, 100, root, None, u))
        sid += 4
    return sorted(out, key=lambda r: r["start_ns"])


def _run(fits=2, iterations=3, said=None):
    from benchmarks import run as harness
    window_ms = 110.0 * fits * 2 * iterations
    return types.SimpleNamespace(
        reduced={"queries": [{"template": QUERY}] * fits,
                 "window_s": window_ms * 1e-3} if fits else None,
        shapes={QUERY: {"iterations": iterations}},
        say=(said.append if said is not None else lambda line: None),
        here=BENCH, load_module=harness.load_module)


# -- the readers and the counts ---------------------------------------------------


def test_the_span_readers_take_every_update_as_a_query_root():
    mxu = _load("metrics", "pnmf_mxu_entries_pct.py")
    assert mxu.read(_run(), _ring()) == pytest.approx(92.0)
    said = []
    # a program whose spans carry no such record (a parent commit)
    assert mxu.read(_run(said=said), _ring(plan_attrs=False)) is None
    assert "carries entries" in said[0]
    assert mxu.read(_run(0), _ring()) is None
    compiles = _load("metrics", "pnmf_compiles_in_window.py")
    assert compiles.read(_run(), _ring()) == 0
    assert compiles.read(_run(), _ring(compile_in=11)) == 1
    assert compiles.read(_run(0), _ring()) is None
    hbm = _load("metrics", "pnmf_planned_hbm_pct.py")
    assert hbm.read(_run(), _ring(), bytes_limit=14_000_000_002) \
        == pytest.approx(50.0)
    assert hbm.read(_run(0), _ring(), bytes_limit=1) is None


def test_counts_against_a_hand_count():
    """3 users x 2 movies, 4 entries, rank 2, one iteration, by hand:
    two updates of a sampled quotient and its product (2 * 4 * 2 each:
    64 operations); an update reads 4 entries of 12 B and both factors
    (8 B a row: 40 B) and writes one (16 B or 24 B); the element-wise
    passes read three operands and write one of each factor and the
    sums read the other (4 * 8 * 5 = 160 B)."""
    counts = _load("counts", "pnmf.py").counts
    got = counts(users=3, movies=2, entries=4, rank=2, iterations=1,
                 plans={"forward": {}})
    assert got == {"flops": 64, "precision": "highest",
                   "bytes": 2 * (48 + 40) + 40 + 160}
    twice = counts(users=3, movies=2, entries=4, rank=2, iterations=2)
    assert twice["flops"] == 128 and twice["bytes"] == 2 * got["bytes"]
    # the cell: 309 GFLOP and 12.6 GB a query
    full = counts(users=480_189, movies=17_770, entries=100_480_507,
                  rank=128, iterations=3)
    assert full["flops"] == 308_676_117_504
    assert full["bytes"] == 12_588_651_672


def test_roofline_is_the_counts_least_time_over_the_device_time():
    reader = _load("metrics", "pnmf_roofline.py")
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    shapes = {"users": 480_189, "movies": 17_770, "entries": 100_480_507,
              "rank": 128, "iterations": 3, "plans": {}}
    said = []
    run = _run(said=said)
    run.peaks, run.shapes = peaks, {QUERY: shapes}
    run.reduced = {"n_device_ops": 7, "chips_traced": 1, "window_s": 6.0,
                   "queries": [{"template": QUERY, "device_s": 0.9},
                               {"template": QUERY, "device_s": 1.1}]}
    assert reader.read(run) == pytest.approx(
        100.0 * (12_588_651_672 / 819e9) / 1.0)
    assert "bound=hbm" in said[0]
    run.reduced = None
    assert reader.read(run) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "systemml_pnmf_netflix"
    # the ninth cell, where PR 46 appended it (later cells follow it)
    assert bench["workloads"][8] is cell
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "pnmf_roofline", "pnmf_mxu_entries_pct", "pnmf_planned_hbm_pct",
        "pnmf_compiles_in_window"]
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 4] == mine
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    config = bench["configs"][7]
    assert config["name"] == cell["config"]
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == spec["reduced"] == []
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    gnmf = json.load(open(os.path.join(BENCH, "configs",
                                       "matfast_gnmf_netflix.json")))
    # the matrix the benchmark already holds: the same shape, marginals
    # and structure seed, so the same plans
    assert spec["matrix"] == gnmf["matrix"]
    assert spec["marginals"] == gnmf["marginals"]
    assert (spec["rank"], spec["iterations"]) == (128, 3)
    traffic = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    assert traffic["mix"] == [{"query": QUERY, "weight": 1}]


def test_the_generator_is_the_gnmf_cells_own(rng):
    """This file's copy of the generator draws the cells the GNMF
    configuration's draws: the two cells hold one matrix."""
    mine = _load("configs", "systemml_pnmf_netflix.py")
    theirs = _load("configs", "matfast_gnmf_netflix.py")
    spec = json.load(open(os.path.join(BENCH, "configs",
                                       "systemml_pnmf_netflix.json")))
    a = mine.ratings_structure(4_000, 150, 7_000, spec["marginals"], 1)
    b = theirs.ratings_structure(4_000, 150, 7_000, spec["marginals"], 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


# -- the deployment at the rehearsal's scale ------------------------------------------


@pytest.fixture(scope="module")
def dep():
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    was = config_lib._default_config
    d = harness.build_deployment(config, spec, 2147483999, [QUERY],
                                 float(traffic["rehearse_scale"]))
    try:
        yield d
    finally:
        config_lib._default_config = was


def _float64_fit(dep, iterations=3):
    Vd = np.zeros((dep.users, dep.movies))
    Vd[dep.rows, dep.cols] = dep.vals
    W = np.asarray(dep.W0.data, np.float64)[:dep.users, :128]
    H = np.asarray(dep.H0.data, np.float64)[:128, :dep.movies]
    assert W.min() > 0 and W.max() <= 1 and H.min() > 0
    for _ in range(iterations):
        H = H * (W.T @ (Vd / (W @ H))) / W.sum(0)[:, None]
        W = W * ((Vd / (W @ H)) @ H.T) / H.sum(1)[None, :]
    return H, W


def test_deployment_answers_within_its_limits(dep):
    from benchmarks import run as harness
    assert (dep.users, dep.movies, dep.entries) == (9604, 355, 40192)
    assert dep.V.nnz == dep.entries and dep.rank == 128
    ans = dep.run(QUERY, harness.no_span)
    ans = dep.run(QUERY, harness.no_span)
    h, W = ans
    assert h.shape == (128, dep.movies) and isinstance(h, np.ndarray)
    assert W.shape == (dep.users, 128)          # W stays on the device
    notes = dep.notes(QUERY)
    assert set(notes["plans"]) == {"forward", "transposed"}
    for rec in notes["plans"].values():
        assert rec["op"] == "div" and rec["entries"] == dep.entries
        assert rec["shared_gather"] is True
    want = dep.reference(QUERY)
    got = dict((label, (value, limit)) for label, value, limit in
               dep.compare(QUERY, ans, want))
    assert all(value <= limit for value, limit in got.values()), got
    assert got[f"{QUERY}.compiles_after_first_fit"] == (0, 0)
    assert got[f"{QUERY}.densified_products"] == (0, 0)
    assert got[f"{QUERY}.plan_builds"] == (2, 2)    # the probe's not counted
    assert set(dep.shapes(QUERY)) == {
        "users", "movies", "entries", "rank", "iterations", "plans"}
    # the plain reference is the float64 fit
    H64, W64 = _float64_fit(dep)
    np.testing.assert_allclose(want[0], H64, rtol=2e-6)
    np.testing.assert_allclose(want[1], W64, rtol=2e-6)
    # the control (the dense sides in bfloat16) breaks the limits
    ctl = dict((label, value) for label, value, _ in dep.compare(
        QUERY, dep.control(QUERY), want))
    q = dep.spec["queries"][QUERY]
    assert ctl[f"{QUERY}.W.max_rel_err"] > q["limit_w"]
    assert ctl[f"{QUERY}.H.max_rel_err"] > q["limit_h"]
    assert ctl[f"{QUERY}.W.max_entry_rel_err"] > q["entry_limit"]
    assert ctl[f"{QUERY}.H.max_entry_rel_err"] > q["entry_limit"]


def test_the_programs_own_lower_passes_run_the_same_fit(dep):
    """``program_controls`` is the fit through the sampled product's own
    body at ``passes`` 2 and 1: the same answer to the precision of the
    parts it leaves out (at this scale nearly every entry lies on the
    slab, whose share is float32 whatever ``passes`` says, so the
    readings here bound the wiring, not a limit)."""
    want = dep.reference(QUERY)
    for knob, got in dep.program_controls(QUERY):
        low = dict((label, value) for label, value, _ in
                   dep.compare(QUERY, got, want))
        assert low[f"{QUERY}.W.max_rel_err"] < 1e-2, knob
        assert low[f"{QUERY}.negative_or_not_finite"] == 0


def test_an_answer_a_densified_leaf_gave_is_not_correct(dep):
    """A leaf that was densified, an entry left to the scalar tail or an
    update that compiled after the first fit: the factors are right and
    the run is not correct. And a timed path broken underneath (the
    harness's own test: an answer times 1.001) is out of its limits."""
    from benchmarks import run as harness
    ans = dep.run(QUERY, harness.no_span)
    want = dep.reference(QUERY)
    broken = dict((label, (value, limit)) for label, value, limit in
                  dep.compare(QUERY, ans * 1.001, want))
    assert broken[f"{QUERY}.H.max_rel_err"][0] > broken[
        f"{QUERY}.H.max_rel_err"][1]
    assert broken[f"{QUERY}.W.max_entry_rel_err"][0] > broken[
        f"{QUERY}.W.max_entry_rel_err"][1]
    dep._note({"hit": False, "executors": ["xla"], "sampled": [
        {"orientation": "forward", "overflow_edges": 7}],
        "densified_products": [{"shape": [1, 1]}]})
    try:
        got = dict((label, (value, limit)) for label, value, limit in
                   dep.compare(QUERY, ans, want))
        assert got[f"{QUERY}.H.max_rel_err"][0] < got[
            f"{QUERY}.H.max_rel_err"][1]
        assert got[f"{QUERY}.densified_products"] == (1, 0)
        assert got[f"{QUERY}.overflow_edges"] == (7, 0)
        assert got[f"{QUERY}.compiles_after_first_fit"] == (1, 0)
    finally:
        dep.densified = dep.overflow_edges = dep.misses_after_first = 0
        dep.facts.pop("forward", None)


def test_a_program_without_the_sampled_product_is_turned_away_at_once(
        monkeypatch):
    """A tree whose rule batch does not write the sampled node (a
    parent commit) densifies the toy update and is refused before the
    ratings are drawn; one without ``last_plan`` likewise."""
    from benchmarks import run as harness
    from matrel_tpu.ir import rules
    from matrel_tpu.session import MatrelSession
    _, _, config, spec, traffic = harness.load_cell(CELL)
    mod = _load("configs", "systemml_pnmf_netflix.py")
    was = config_lib._default_config
    monkeypatch.setattr(mod, "ratings_structure", lambda *a, **k: pytest.fail(
        "the ratings were drawn"))
    try:
        for batch in ("_RULES", "_RULES_AHEAD_OF_CHAIN_DP"):
            monkeypatch.setattr(rules, batch, [
                r for r in getattr(rules, batch)
                if r is not rules.sampled_product])
        ok, said = mod.can_serve(interpret=True)
        assert not ok and said["sampled"] == []
        assert len(said["densified_products"]) == 1
        with pytest.raises(RuntimeError, match="systemml_pnmf_netflix: this "
                           "program cannot serve the deployment"):
            mod.Deployment(spec, 5, [QUERY], scale=0.02, interpret=True)
        monkeypatch.undo()
        assert mod.can_serve(interpret=True)[0]
        monkeypatch.delattr(MatrelSession, "last_plan")
        assert mod.can_serve(interpret=True) == (
            False, "no MatrelSession.last_plan")
    finally:
        config_lib._default_config = was


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_cell_rehearses_end_to_end(capsys, trace):
    """``run.py --rehearse`` on the cell: set-up, warm-up, a window, the
    check against the reference, one result line with no metric value;
    traced, every per-layer reader of the cell is called."""
    from benchmarks import run as harness
    was = config_lib._default_config
    try:
        rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "1", "--rehearse", "0.02",
                           "--trace", trace])
    finally:
        config_lib._default_config = was
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and "metrics" not in result
    assert any(line.startswith(f"check {QUERY}.plan_builds") for line in out)
