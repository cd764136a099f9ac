"""The benchmark's deployment ``matrel_linreg_10m_whole`` (PR 39) in
tier-1, in ``test_bench_gnmf.py``'s manner: what ``BENCHMARK.json`` and
the configuration's file say of the cell, its four per-layer readers on
synthetic records (the benchmark's own tests of them, run here too), the
configuration's generator and plain reference at a small scale, the
cell's rehearsal end to end, and ``compare`` turning rows that do not lie
as stated, or a late compile, into not correct."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "tests"))

# the benchmark's own tests of the cell's readers: tier-1 runs them too
from test_linreg_whole import (  # noqa: E402,F401
    test_collective_ms_is_the_all_reduce_a_query_or_nothing_seen,
    test_gram_roofline_is_reckoned_over_the_chips_the_trace_shows,
    test_planned_hbm_pct_and_launch_ms_read_this_cell_s_spans,
    test_the_whole_table_s_count_is_four_quarters_and_one_solve,
    test_whole_readers_without_a_trace_give_nothing)

CELL, CONFIG = "linreg_10m_2x2", "matrel_linreg_10m_whole"
METRICS = {"linreg_whole_gram_roofline": ("kernels", "device_trace"),
           "linreg_whole_collective_ms": ("strategies and collectives",
                                          "device_trace"),
           "linreg_whole_planned_hbm_pct": ("optimizer, planner, compile",
                                            "host_clock"),
           "linreg_whole_launch_ms": ("session and executor dispatch",
                                      "host_clock")}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config_module():
    from benchmarks import run as harness
    return harness.load_module(os.path.join(BENCH, "configs",
                                            CONFIG + ".py"))


def test_benchmark_json_names_the_cell_whole_on_four_chips():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, CELL, 4)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    spec = _json(entry["file"])
    assert entry["reduced"] == [] and spec["reduced"] == []
    assert entry["source"] == spec["source"] and len(entry["source"]) <= 200
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert spec["rows"] == spec["published_rows"] == 10_223_616
    assert spec["tables"] == {"X": [10_223_616, 1000], "y": [10_223_616, 1]}
    assert (spec["dtype"], spec["matmul_precision"]) == ("float32", "highest")
    assert spec["exact"] == {"devices": 4, "rows_a_device": 2_555_904,
                             "gram_tiles": [10, 16], "gram_rides": 1}
    # at most half of the cells ask for four chips
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 and 2 * len(four) <= len(bench["workloads"])
    for name, (layer, source) in METRICS.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert (m["layer"], m["source"], m["moves"], m["workloads"]) \
            == (layer, source, "query_p50_ms", [CELL])
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    traffic = _json("benchmarks", "workloads", CELL + ".json")
    assert traffic["mix"] == [{"query": "theta", "weight": 1}]


def test_the_generator_cuts_by_rows_and_draws_each_device_its_own(
        mesh_square):
    """Every device holds whole rows of its own draw: no two devices'
    panels are equal, the same seed gives the same tables, another seed
    others, and y is X theta* plus noise of the stated sigma."""
    from benchmarks.reference import device_key
    cfg = _config_module()
    n, k, panel = 4 * 2 * 256, 12, 256
    x, y = cfg.generate(mesh_square, n, k, panel, 0.1, device_key(7))
    assert {(s.data.shape, len(s.data.sharding.device_set))
            for s in x.addressable_shards} == {((n // 4, k), 1)}
    assert len(x.sharding.device_set) == 4
    panels = np.asarray(x).reshape(n // panel, panel, k)
    assert len({p.tobytes() for p in panels}) == n // panel
    assert np.abs(np.asarray(x)).max() < 1.0
    again = cfg.generate(mesh_square, n, k, panel, 0.1, device_key(7))
    other = cfg.generate(mesh_square, n, k, panel, 0.1,
                         device_key(3_000_000_007))
    assert np.array_equal(np.asarray(again[0]), np.asarray(x))
    assert not np.array_equal(np.asarray(other[0]), np.asarray(x))
    theta = cfg.PanelSums(k, 64).solve(x, y)
    resid = np.asarray(y, np.float64) - np.asarray(x, np.float64) @ theta
    assert 0.08 < resid.std() < 0.12
    with pytest.raises(ValueError, match="whole number of panels"):
        cfg.generate(mesh_square, n + 4, k, panel, 0.1, device_key(7))


def test_the_reference_is_float64_normal_equations_and_the_control_is_not(
        mesh_square):
    """``PanelSums.solve`` against numpy's float64 normal equations on
    the float32 values the tables hold, a ragged last panel a shard
    among the sums; with X rounded to bfloat16 it is another answer."""
    from benchmarks.reference import device_key, rel_err
    cfg = _config_module()
    n, k = 4 * 300, 20
    x, y = cfg.generate(mesh_square, n, k, 300, 0.1, device_key(11))
    x64, y64 = np.asarray(x, np.float64), np.asarray(y, np.float64)
    want = np.linalg.solve(x64.T @ x64, x64.T @ y64)
    sums = cfg.PanelSums(k, 128)       # 128 + 128 + 44 rows a shard
    assert rel_err(sums.solve(x, y), want) < 1e-6
    assert rel_err(sums.solve(x, y, rnd=cfg._bf16), want) > 1e-4


@pytest.fixture(scope="module")
def rehearsed():
    """The cell's rehearsal, untraced, in this process (the harness's
    last line and the deployment it built)."""
    from benchmarks import run as harness
    kept = {}
    real = harness.build_deployment

    def build(*args, **kw):
        kept["dep"] = real(*args, **kw)
        return kept["dep"]

    harness.build_deployment = build
    import contextlib
    import io
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", CELL, "--seed", "2147483999",
                               "--seconds", "1", "--trace", "0",
                               "--rehearse", str(_json(
                                   "benchmarks", "workloads",
                                   CELL + ".json")["rehearse_scale"])])
    finally:
        harness.build_deployment = real
    return rc, out.getvalue(), kept["dep"]


def test_the_cell_rehearses_end_to_end_and_is_correct(rehearsed):
    rc, said, dep = rehearsed
    assert rc == 0
    last = json.loads(said.strip().splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metric_names"]) == {"query_p50_ms", "query_p95_ms",
                                         "queries_per_s", "setup_s"}
    for label in ("max_rel_err", "devices_short", "rows_a_device_off",
                  "compiles_after_warm"):
        assert f"check theta.{label} " in said
    assert "OUT OF LIMIT" not in said
    # k is never cut; the rows lie on every device of the mesh
    assert dep.k == 1000 and dep.n % dep.mesh.size == 0


def test_compare_says_rows_that_do_not_lie_as_stated_and_late_compiles(
        rehearsed):
    """``compare`` on the rehearsed deployment: the sound answer breaks
    nothing; a compile heard during a query after the first, a table
    that lies on fewer devices than the mesh has, and the panelled
    lowering's stamps missing at the deployment's size each break an
    exact number."""
    _, _, dep = rehearsed
    want = dep.reference("theta")
    answer = dep.run("theta", lambda name: __import__("contextlib")
                     .nullcontext())

    def broken(dep):
        return {label for label, value, limit in
                dep.compare("theta", answer, want) if not value <= limit}

    assert broken(dep) == set()
    dep._late_compiles += 1
    assert broken(dep) == {"theta.compiles_after_warm"}
    dep._late_compiles -= 1
    whole, dep.whole = dep.whole, True      # as at the deployment's size
    try:
        assert {"theta.gram_tiles_off", "theta.gram_rides_off",
                "theta.devices_short", "theta.rows_a_device_off"} \
            <= broken(dep)
    finally:
        dep.whole = whole
    assert broken(dep) == set()
