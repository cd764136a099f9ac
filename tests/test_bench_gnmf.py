"""The benchmark's deployment ``matfast_gnmf_netflix`` (PR 37) in tier-1,
in ``test_bench_g500.py``'s manner: its per-layer readers on synthetic
records, its generator's marginals and its plain reference at a small
scale, the cell's rehearsal end to end, and ``compare`` turning an answer
that a densified leaf or a late compile gave into not correct."""

import json
import os
import types

import numpy as np
import pytest

from matrel_tpu import config as config_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "gnmf_netflix_r128_1c"
MS = 1_000_000
SAID = {"layout": "chunks", "entries": 100, "k": 128, "source_panels": 1,
        "table": "hbm", "overflow_edges": 0, "panels": 1}


def _load(*parts):
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, *parts))


def _rec(name, start_ms, dur_ms, span_id, parent_id, qid, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "end_ns": int((start_ms + dur_ms) * MS), "span_id": span_id,
            "parent_id": parent_id, "qid": qid, "tid": 1, "attrs": attrs}


def _ring(fits=2, iterations=3, compile_in=None, plan_attrs=True):
    """``fits`` traced fits of ``2 * iterations`` updates of 400 ms, each
    a ``matrel.compute`` root over a template lookup of 0.05 ms and a
    dispatch that says 6 GB and, of its plan, 101 (transposed) or 103
    (forward) slots for 100 entries."""
    out, sid = [], 0
    for u in range(fits * 2 * iterations):
        t0, root = 410.0 * u, sid + 1
        forward = u % 2
        out.append(_rec("matrel.plan", t0 + 1, 0.05, sid + 2, root, u,
                        via="template", hit=True))
        if compile_in == u:
            out.append(_rec("matrel.compile", t0 + 2, 5, sid + 5, root, u))
        out.append(_rec("matrel.dispatch", t0 + 10, 1, sid + 3, root, u,
                        hbm_plan_bytes=6_000_000_000 + forward))
        said = dict(SAID, slots=103 if forward else 101,
                    orientation="forward" if forward else "transposed") \
            if plan_attrs else {}
        out.append(_rec("matrel.spmm.plan", t0 + 10.1, 0.01, sid + 4,
                        sid + 3, u, hit=True, **said))
        out.append(_rec("matrel.compute", t0, 400, root, None, u))
        sid += 5
    return sorted(out, key=lambda r: r["start_ns"])


def _run(fits=2, iterations=3, said=None):
    from benchmarks import run as harness
    window_ms = 410.0 * fits * 2 * iterations
    return types.SimpleNamespace(
        reduced={"queries": [{"template": "gnmf_fit"}] * fits,
                 "window_s": window_ms * 1e-3} if fits else None,
        shapes={"gnmf_fit": {"iterations": iterations}},
        say=(said.append if said is not None else lambda line: None),
        here=BENCH, load_module=harness.load_module)


# -- the readers ----------------------------------------------------------------


def test_the_span_readers_take_every_update_as_a_query_root():
    """A fit is six ``matrel.compute`` roots: the accepted readers'
    window is all of them, not the first two."""
    pad = _load("metrics", "gnmf_slot_padding_pct.py")
    assert pad.read(_run(), _ring()) == pytest.approx(
        100.0 * ((101 + 103) / 200 - 1))
    said = []
    assert pad.read(_run(said=said), _ring(plan_attrs=False)) is None
    assert "carries slots and entries" in said[0]
    assert pad.read(_run(0), _ring()) is None
    compiles = _load("metrics", "gnmf_compiles_in_window.py")
    assert compiles.read(_run(), _ring()) == 0
    # a compile in the LAST update of the window is inside it
    assert compiles.read(_run(), _ring(compile_in=11)) == 1
    assert compiles.read(_run(0), _ring()) is None
    hbm = _load("metrics", "gnmf_planned_hbm_pct.py")
    assert hbm.read(_run(), _ring(), bytes_limit=12_000_000_002) \
        == pytest.approx(50.0)
    assert hbm.read(_run(0), _ring(), bytes_limit=1) is None


def test_roofline_is_the_counts_least_time_over_the_device_time():
    reader = _load("metrics", "gnmf_roofline.py")
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    shapes = {"users": 480_189, "movies": 17_770, "entries": 100_480_507,
              "rank": 128, "iterations": 3, "plans": {}}
    said = []
    run = _run(said=said)
    run.peaks, run.shapes = peaks, {"gnmf_fit": shapes}
    run.reduced = {"n_device_ops": 7, "chips_traced": 1, "window_s": 6.0,
                   "queries": [{"template": "gnmf_fit", "device_s": 2.4},
                               {"template": "gnmf_fit", "device_s": 2.6}]}
    assert reader.read(run) == pytest.approx(
        100.0 * (14_883_246_744 / 819e9) / 2.5)
    assert "bound=hbm" in said[0]
    run.reduced = None
    assert reader.read(run) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "matfast_gnmf_netflix"
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "gnmf_compiles_in_window", "gnmf_plan_lookup_ms",
        "gnmf_planned_hbm_pct", "gnmf_roofline", "gnmf_slot_padding_pct"]
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    spec = json.load(open(os.path.join(ROOT, config["file"])))
    assert config["reduced"] == spec["reduced"] == []
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    assert (spec["matrix"]["users"], spec["matrix"]["movies"],
            spec["matrix"]["entries"], spec["rank"]) == (
        480_189, 17_770, 100_480_507, 128)
    traffic = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    assert traffic["mix"] == [{"query": "gnmf_fit", "weight": 1}]


# -- the generator ----------------------------------------------------------------


@pytest.fixture(scope="module")
def structure():
    """A twelfth of the matrix each way: 40,016 users x 1,481 movies and
    697,781 ratings, the degrees' shape kept."""
    mod = _load("configs", "matfast_gnmf_netflix.py")
    spec = json.load(open(os.path.join(BENCH, "configs",
                                       "matfast_gnmf_netflix.json")))
    users, movies, entries = 40_016, 1_481, 697_781
    rows, cols, parts = mod.ratings_structure(
        users, movies, entries, spec["marginals"], 1)
    return mod, spec, users, movies, entries, rows, cols


def test_the_generator_gives_the_exact_count_of_distinct_cells(structure):
    _, _, users, movies, entries, rows, cols = structure
    assert rows.size == cols.size == entries
    assert rows.min() == 0 and rows.max() == users - 1
    assert cols.min() == 0 and cols.max() == movies - 1
    keys = rows * movies + cols
    assert np.all(np.diff(keys) > 0)            # sorted, no cell twice
    assert np.bincount(rows, minlength=users).min() >= 1
    assert np.bincount(cols, minlength=movies).min() >= 1


def test_the_generators_marginals_keep_the_data_sets_shape(structure):
    """Medians, means and largest degrees in the data set's proportions
    (a twelfth of each at a twelfth of the size): user median 96 of a
    mean of 209, movie median 561 of a mean of 5,654."""
    mod, spec, users, movies, entries, rows, cols = structure
    user = mod._degree_facts(np.bincount(rows, minlength=users))
    movie = mod._degree_facts(np.bincount(cols, minlength=movies))
    mu, mm = spec["marginals"]["user"], spec["marginals"]["movie"]
    assert user["mean"] == pytest.approx(entries / users, abs=1e-3)
    # (a mean of 17 at this size: whole numbers and the draw's own
    # spread move a median of 8 or 9 by a tenth)
    assert user["median"] / user["mean"] == pytest.approx(
        mu["median"] / mu["mean"], rel=0.2)
    assert movie["median"] / movie["mean"] == pytest.approx(
        mm["median"] / mm["mean"], rel=0.12)
    assert 0.5 < movie["largest"] / (mm["largest"] * entries / movies
                                     / mm["mean"]) < 1.1
    assert user["largest"] < movies and movie["largest"] < users
    # the same structure from the same seed, whatever --seed
    again = mod.ratings_structure(users, movies, entries,
                                  spec["marginals"], 1)
    assert np.array_equal(again[0], rows) and np.array_equal(again[1], cols)


# -- the deployment at the rehearsal's scale ------------------------------------------


@pytest.fixture(scope="module")
def dep():
    from benchmarks import run as harness
    _, _, config, spec, traffic = harness.load_cell(CELL)
    was = config_lib._default_config
    d = harness.build_deployment(config, spec, 2147483999, ["gnmf_fit"],
                                 float(traffic["rehearse_scale"]))
    try:
        yield d
    finally:
        config_lib._default_config = was


def test_deployment_answers_within_its_limits(dep):
    from benchmarks import run as harness
    assert (dep.users, dep.movies, dep.entries) == (9604, 355, 40192)
    assert dep.V.nnz == dep.entries and dep.rank == 128
    assert sorted(np.unique(dep.V.vals)) == [1, 2, 3, 4, 5]
    ans = dep.run("gnmf_fit", harness.no_span)
    ans = dep.run("gnmf_fit", harness.no_span)
    h, W = ans
    assert h.shape == (128, dep.movies) and isinstance(h, np.ndarray)
    assert W.shape == (dep.users, 128)          # W stays on the device
    notes = dep.notes("gnmf_fit")
    assert set(notes["plans"]) == {"forward", "transposed"}
    assert notes["plan_builds"] <= 2 or dep.calls > 2
    want = dep.reference("gnmf_fit")
    got = dict((label, (value, limit)) for label, value, limit in
               dep.compare("gnmf_fit", ans, want))
    assert all(value <= limit for value, limit in got.values()), got
    assert got["gnmf_fit.compiles_after_first_fit"] == (0, 0)
    assert got["gnmf_fit.densified_products"] == (0, 0)
    assert set(dep.shapes("gnmf_fit")) == {
        "users", "movies", "entries", "rank", "iterations", "plans"}
    # the plain reference is the float64 fit
    Vd = np.zeros((dep.users, dep.movies))
    Vd[dep.rows, dep.cols] = dep.vals
    W64 = np.asarray(dep.W0.data, np.float64)[:dep.users, :128]
    H64 = np.asarray(dep.H0.data, np.float64)[:128, :dep.movies]
    assert W64.min() > 0 and W64.max() <= 1 and H64.min() > 0
    for _ in range(3):
        H64 = H64 * (W64.T @ Vd) / (W64.T @ W64 @ H64)
        W64 = W64 * (Vd @ H64.T) / (W64 @ H64 @ H64.T)
    np.testing.assert_allclose(want[0], H64, rtol=2e-6)
    np.testing.assert_allclose(want[1], W64, rtol=2e-6)
    # the control (the dense sides in bfloat16) breaks the limits
    ctl = dict((label, value) for label, value, _ in dep.compare(
        "gnmf_fit", dep.control("gnmf_fit"), want))
    q = dep.spec["queries"]["gnmf_fit"]
    assert ctl["gnmf_fit.W.max_rel_err"] > q["limit"]
    assert ctl["gnmf_fit.W.max_entry_rel_err"] > q["entry_limit"]
    # and so do the program's own lower passes
    for knob, got in dep.program_controls("gnmf_fit"):
        low = dict((label, value) for label, value, _ in
                   dep.compare("gnmf_fit", got, want))
        assert low["gnmf_fit.W.max_entry_rel_err"] > q["entry_limit"], knob


def test_an_answer_a_densified_leaf_gave_is_not_correct(dep):
    """A product that fell to the dense leaf, an entry left to the scalar
    tail or an update that compiled after the first fit: the factors are
    right and the run is not correct."""
    from benchmarks import run as harness
    ans = dep.run("gnmf_fit", harness.no_span)
    want = dep.reference("gnmf_fit")
    dep._note({"hit": False, "executors": ["xla"], "spmm": [
        {"orientation": "forward", "overflow_edges": 7}],
        "densified_products": [{"shape": [1, 1]}]})
    try:
        got = dict((label, (value, limit)) for label, value, limit in
                   dep.compare("gnmf_fit", ans, want))
        assert got["gnmf_fit.H.max_rel_err"][0] < got[
            "gnmf_fit.H.max_rel_err"][1]
        assert got["gnmf_fit.densified_products"] == (1, 0)
        assert got["gnmf_fit.overflow_edges"] == (7, 0)
        assert got["gnmf_fit.compiles_after_first_fit"] == (1, 0)
    finally:
        dep.densified = dep.overflow_edges = dep.misses_after_first = 0
        dep.facts.pop("forward", None)


def test_a_program_without_the_compact_product_stops_in_set_up(monkeypatch):
    """A tree that says nothing of its plans, or answers through another
    executor (a parent commit), exits in its first call."""
    from benchmarks import run as harness
    from matrel_tpu.session import MatrelSession
    _, _, config, spec, traffic = harness.load_cell(CELL)
    was = config_lib._default_config
    try:
        d = harness.build_deployment(config, spec, 5, ["gnmf_fit"],
                                     float(traffic["rehearse_scale"]))
        monkeypatch.setattr(MatrelSession, "last_plan",
                            lambda self: {"executors": ["xla"], "spmm": []})
        with pytest.raises(RuntimeError, match="not answered by the "
                           "compact-table Pallas product"):
            d.run("gnmf_fit", harness.no_span)
        # a program that says nothing of its plans is turned away
        # before anything is made (the parent commit compiled a 51 GB
        # gather for ten minutes in its first call, my chip run, PR 37)
        monkeypatch.delattr(MatrelSession, "last_plan")
        with pytest.raises(RuntimeError, match="has no "
                           "MatrelSession.last_plan"):
            harness.build_deployment(config, spec, 5, ["gnmf_fit"],
                                     float(traffic["rehearse_scale"]))
    finally:
        config_lib._default_config = was


def test_the_cell_rehearses_end_to_end(capsys):
    """``run.py --rehearse`` on the cell: set-up, warm-up, a window, the
    check against the reference, one result line with no metric value."""
    from benchmarks import run as harness
    was = config_lib._default_config
    try:
        rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "1", "--rehearse", "0.02"])
    finally:
        config_lib._default_config = was
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and "metrics" not in result
    assert any(line.startswith("check gnmf_fit.plan_builds") for line in out)
