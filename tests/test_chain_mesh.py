"""The 65k matrix chain on the 2x2 mesh (PR 27), at tier-1 sizes: the
session's ``A * B * C`` in bfloat16 on a real 2x2 mesh of the CPU's
virtual devices against a plain float64 reference, under every strategy
the planner may still choose and with budgets that make the panelled rmm
take several panels; the panelled product bit for bit the one-panel
product; the planner's memory reckoning at the benchmark cell's real
shapes, allocating nothing; what the plan and its spans say of it."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import expr as E
from matrel_tpu.parallel import planner, strategies
from matrel_tpu.session import MatrelSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 1024
V5E_BYTES_LIMIT = 16_909_334_528     # memory_stats()["bytes_limit"], PR 22
GIB = 1 << 30


@pytest.fixture(scope="module")
def tables(mesh_square):
    """Three seeded bfloat16 tables, uniform [-1, 1), sharded P(x, y),
    and the plain float64 chain of the values they really hold."""
    x, y = mesh_square.axis_names
    sharded = jax.sharding.NamedSharding(mesh_square, P(x, y))
    key = jax.random.PRNGKey(27)
    arrays = {name: jax.device_put(jax.random.uniform(
        jax.random.fold_in(key, i), (N, N), jnp.bfloat16, -1.0, 1.0),
        sharded) for i, name in enumerate("ABC")}
    host = {k: np.asarray(v.astype(jnp.float32), np.float64)
            for k, v in arrays.items()}
    return arrays, (host["A"] @ host["B"]) @ host["C"]


def chain(mesh, arrays, config):
    """(answer as float64, plan.meta, the answer's array) of
    ``A * B * C`` through session.sql + session.compute."""
    sess = MatrelSession(mesh=mesh, config=config)
    for name, arr in arrays.items():
        sess.register(name, BlockMatrix.from_array(
            arr, (N, N), mesh, P(*mesh.axis_names)))
    expr = sess.sql("A * B * C")
    out = sess.compute(expr)
    return (np.asarray(out.data.astype(jnp.float32), np.float64),
            sess.compile(expr).meta, out.data)


def rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("strategy", ["auto", "rmm", "cpmm", "summa",
                                      "xla", "bmm_left", "bmm_right"])
def test_chain_under_every_strategy(mesh_square, tables, strategy):
    arrays, want = tables
    got, meta, data = chain(mesh_square, arrays,
                            MatrelConfig(strategy_override=strategy))
    assert data.dtype == jnp.bfloat16
    assert len(data.sharding.device_set) == 4
    if strategy != "auto":
        assert meta["executors"] == [strategy]
    # two bfloat16 roundings (T and R) of zero-mean sums: 3e-3 at this
    # size; a float8 operand reads 5e-2 (benchmarks/tests/control.py)
    assert rel_err(got, want) < 1e-2
    assert meta["mesh"] == "2x2"
    assert [p["chosen"] for p in meta["products"]] \
        == meta["executors"] * 2


@pytest.mark.parametrize("tiles, panels, fits", [
    (10, (1, 4), True), (8, (2, 4), True), (6, (4, 4), False)])
def test_small_budget_panels_and_is_bit_for_bit_one_panel(
        mesh_square, tables, tiles, panels, fits):
    """Three tables of one 512 KiB tile a device each; the answer and T
    beside them make five, and one product's whole inputs, their
    chunk's slice and its two float32 tiles eight more. A budget of ten
    tiles leaves that no room: the planner chooses rmm itself, with
    four column panels; at eight it also halves the row panel; and
    nothing changes in the answer — every stored element is still one
    float32 accumulation over the whole contraction, rounded once. At
    six tiles not even the narrowest panels fit: the plan that needs
    least is handed over, and says that it was refused."""
    arrays, want = tables
    one, meta1, _ = chain(mesh_square, arrays,
                          MatrelConfig(strategy_override="rmm"))
    assert [p["panels"] for p in meta1["products"]] == [[1, 1], [1, 1]]
    budget = int(tiles * N * N * 2 / 4)
    got, meta, _ = chain(mesh_square, arrays,
                         MatrelConfig(hbm_budget_bytes=budget))
    assert meta["executors"] == ["rmm"]
    assert [tuple(p["panels"]) for p in meta["products"]] == [panels] * 2
    for p in meta["products"]:
        assert {"cpmm", "summa"} <= set(p["refused_hbm"])
        assert ("rmm" in p["refused_hbm"]) == (not fits)
    assert (meta["hbm_plan_bytes"] <= budget) == fits
    assert np.array_equal(got, one)
    assert rel_err(got, want) < 1e-2


@pytest.mark.parametrize("grid, panels", [
    ((2, 2), (1, 2)), ((2, 2), (2, 1)), ((2, 2), (2, 4)), ((2, 2), (4, 2)),
    # column panel 0 is multiplied ahead of the loop wherever there is a
    # loop and a slice to move (PR 30): the loop then runs once after
    # each of two prologues; three moved slices a row panel (gy = 4),
    # with no gather beside them (1x4) and with one (2x4)
    ((2, 2), (2, 2)), ((1, 4), (1, 2)), ((2, 4), (2, 4))])
def test_panelled_rmm_is_the_one_panel_product(tables, grid, panels):
    """Below the session: run_matmul's rmm at forced panel counts."""
    mesh = mesh_lib.make_mesh(grid, devices=jax.devices()[:grid[0] * grid[1]])
    x, y = mesh.axis_names
    a, b = (jax.device_put(tables[0][name],
                           jax.sharding.NamedSharding(mesh, P(x, y)))
            for name in "AB")

    def product(p):
        return jax.jit(lambda u, v: strategies.run_matmul(
            "rmm", u, v, mesh, MatrelConfig(), panels=p,
            out_dtype=jnp.bfloat16))(a, b)

    one, many = product((1, 1)), product(panels)
    assert many.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(one.astype(jnp.float32)),
                          np.asarray(many.astype(jnp.float32)))


@pytest.mark.parametrize("n, mid", [(64, 8), (60, 6)])
@pytest.mark.parametrize("grid", [(2, 4), (4, 2), (2, 2)])
def test_a_float32_chain_is_numpys_whether_or_not_the_mesh_divides_it(
        grid, n, mid, rng):
    """What the streaming chain's tests held, on the path that stays:
    ``A * B * C`` in float32 through session.sql + session.compute on
    eight devices (both ways round) and on four, against numpy; 64 and
    8 are multiples of every grid side, 60 and 6 of neither 4 nor 8
    (the session pads the tables; nothing is refused), and the skewed
    shapes leave the chain DP one cheap order to find."""
    mesh = mesh_lib.make_mesh(grid, devices=jax.devices()[:grid[0] * grid[1]])
    host = {"A": rng.standard_normal((n, mid)).astype(np.float32),
            "B": rng.standard_normal((mid, n)).astype(np.float32),
            "C": rng.standard_normal((n, mid)).astype(np.float32)}
    sess = MatrelSession(mesh=mesh)
    for name, arr in host.items():
        sess.register(name, sess.from_numpy(arr))
    expr = sess.sql("A * B * C")
    out = sess.compute(expr)
    assert out.shape == (n, mid)
    np.testing.assert_allclose(out.to_numpy(),
                               host["A"] @ host["B"] @ host["C"],
                               rtol=1e-4, atol=1e-4)
    inner = sess.compile(expr).optimized.children[1]
    assert inner.kind == "matmul" and inner.shape == (mid, mid)


def test_rmm_alone_derives_its_panels_from_the_budget(mesh_square, tables):
    """A caller that holds no plan (autotune, a tiered pass): the
    strategy reckons the product taken alone."""
    arrays, _ = tables
    cfg = MatrelConfig(hbm_budget_bytes=4_000_000)
    out = jax.jit(lambda u, v: strategies.matmul_rmm(
        u, v, mesh_square, cfg))(arrays["A"], arrays["B"])
    ref = jax.jit(lambda u, v: strategies.matmul_rmm(
        u, v, mesh_square, MatrelConfig(), panels=(1, 1)))(
            arrays["A"], arrays["B"])
    assert strategies.rmm_panels(
        N, N, N, 2, 2, 2, 4_000_000 - 3 * N * N * 2 / 4) != (1, 1)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# -- the planner's arithmetic at the cell's real shapes -----------------------


def phantom(shape, spec, dtype="bfloat16"):
    """A leaf that is a shape and a spec: nothing is allocated."""
    return E.leaf(types.SimpleNamespace(shape=shape, nnz=None, spec=spec,
                                        dtype=np.dtype(dtype)))


@pytest.fixture()
def cell_plan(mesh_square):
    """``A * B * C`` at 65536^2 bfloat16 on the 2x2 mesh, annotated under
    the default config held to the v5e's own bytes_limit."""
    x, y = mesh_square.axis_names
    a, b, c = (phantom((65536, 65536), P(x, y)) for _ in range(3))
    expr = E.matmul(E.matmul(a, b), c)

    def annotate(**kw):
        return planner.annotate_strategies(expr, mesh_square,
                                           MatrelConfig(**kw))
    return annotate


def test_cell_plan_fits_the_chip(cell_plan):
    report = planner.hbm_report(cell_plan())
    assert [p["chosen"] for p in report] == ["rmm", "rmm"]
    for p in report:
        # what the tree before PR 27 passed or exempted, and what the
        # chip refused for each (PERF.md section 6): all gone
        assert p["refused_hbm"] == ["cpmm", "summa"]
        assert p["panels"] == [1, 8]
        # 6 GiB of tables a chip, the answer's 2 GiB from the start,
        # the intermediate's 2 GiB, and the transient
        assert 6 * GIB + 2 * GIB + 2 * GIB < p["hbm_plan_bytes"] \
            <= MatrelConfig().hbm_budget_bytes < V5E_BYTES_LIMIT


def test_cell_plan_without_a_gate_is_one_panel(cell_plan):
    report = planner.hbm_report(cell_plan(hbm_budget_bytes=0))
    assert all(p["panels"] == [1, 1] and not p["refused_hbm"]
               for p in report)


def test_cell_estimates_beside_what_the_chip_said(mesh_square):
    """PR 27's Stage 1 on four v5e chips, 6 GiB of tables resident and
    2 GiB of answer: the whole gathers of the old rmm and of xla asked
    for 8 GiB of 7.75 free; cpmm's float32 partial alone is 8 GiB;
    summa's carry is a 4 GiB float32 tile beside 4 GiB of skewed tiles.
    The transient terms say so, and nothing fits whole."""
    args = (65536, 65536, 65536, 2, 2, 2)
    assert planner.strategy_transient_bytes("xla", *args) == 8 * GIB
    assert planner.strategy_transient_bytes("cpmm", *args) \
        == 4 * GIB + 8 * GIB
    assert planner.strategy_transient_bytes("summa", *args) \
        == 8 * GIB + 4 * GIB
    # rmm whole: the other device's slice of A, all of B's column
    # panel, the chunk's slice of it, two float32 tiles
    assert planner.strategy_transient_bytes("rmm", *args) \
        == 2 * GIB + 4 * GIB + 2 * GIB + 8 * GIB
    assert planner.strategy_hbm_bytes("xla", *args) > 0
    alive = 6 * GIB + 2 * GIB
    for s in ("rmm", "xla", "cpmm", "summa", "bmm_left", "bmm_right"):
        assert planner.plan_hbm_bytes(s, *args, alive) > V5E_BYTES_LIMIT
    # eight column panels: 2 GiB of A's slices, three panels of 0.5 GiB,
    # a chunk's slice of one, two float32 panels of 0.5 GiB, and panel
    # 0 rounded (0.25 GiB), multiplied ahead of the loop (PR 30)
    room = MatrelConfig().hbm_budget_bytes - alive - 2 * GIB
    assert strategies.rmm_panels(*args, room) == (1, 8)
    assert strategies.rmm_moves_under_dot(65536, 2, (1, 8)) == 1
    assert strategies.rmm_transient_bytes(*args, (1, 8)) == 5.0 * GIB
    assert planner.plan_hbm_bytes("rmm", *args, alive, (1, 8)) \
        == 15.0 * GIB
    # no loop, or no move along the mesh row: nothing is ahead of it
    assert strategies.rmm_moves_under_dot(65536, 2, (1, 1)) == 0
    assert strategies.rmm_moves_under_dot(65536, 2, (4, 1)) == 0
    assert strategies.rmm_moves_under_dot(65536, 1, (1, 8)) == 0
    assert strategies.rmm_moves_under_dot(65536, 4, (2, 8)) == 3
    # a 4x1 mesh moves no slice: three gathered panels of 1 GiB and
    # one float32 panel, as before
    assert strategies.rmm_transient_bytes(65536, 65536, 65536, 4, 1, 2,
                                          (1, 8)) == 3.5 * GIB


def test_budget_is_held_to_what_the_device_reports(monkeypatch, mesh_square):
    cfg = MatrelConfig()
    assert cfg.hbm_budget_bytes < V5E_BYTES_LIMIT
    # the CPU reports no limit: the config alone holds
    assert mesh_lib.device_bytes_limit(mesh_square) is None
    assert mesh_lib.hbm_limit_bytes(mesh_square, cfg) == cfg.hbm_budget_bytes
    monkeypatch.setattr(mesh_lib, "device_bytes_limit", lambda mesh: 8 * GIB)
    assert mesh_lib.hbm_limit_bytes(mesh_square, cfg) == 8 * GIB
    assert mesh_lib.hbm_limit_bytes(
        mesh_square, MatrelConfig(hbm_budget_bytes=32 * GIB)) == 8 * GIB
    assert mesh_lib.hbm_limit_bytes(
        mesh_square, MatrelConfig(hbm_budget_bytes=GIB)) == GIB
    assert mesh_lib.hbm_limit_bytes(
        mesh_square, MatrelConfig(hbm_budget_bytes=0)) == 0


def test_one_device_plans_are_reckoned_too(tables):
    """PR 31: no strategy to choose on one device, but the same
    reckoning: three tables, the answer, and at the second product the
    intermediate beside them; no transient."""
    arrays, _ = tables
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    sess = MatrelSession(mesh=mesh)
    for name, arr in arrays.items():
        sess.register(name, BlockMatrix.from_array(
            jax.device_put(arr, jax.devices()[0]), (N, N), mesh, P()))
    meta = sess.compile(sess.sql("A * B * C")).meta
    assert meta["mesh"] == "1x1"
    table = N * N * 2
    assert [(p["chosen"], p["panels"], p["refused_hbm"], p["hbm_plan_bytes"])
            for p in meta["products"]] == [
        ("xla", [1, 1], [], 5 * table), ("xla", [1, 1], [], 5 * table)]
    assert meta["hbm_plan_bytes"] == 5 * table


# -- what the spans say ------------------------------------------------------


def test_spans_carry_the_reckoning(mesh_square, tables, tmp_path):
    """``matrel.dispatch`` carries ``mesh`` and ``hbm_plan_bytes``; a
    compile inside a profiler session leaves one ``plan.strategy`` span
    a product; the benchmark's ``planned_hbm_pct`` reads the ring."""
    from benchmarks import program_spans, run as harness
    from matrel_tpu.obs.trace import profile_spans
    arrays, _ = tables
    sess = MatrelSession(mesh=mesh_square,
                         config=MatrelConfig(hbm_budget_bytes=5 * N * N))
    for name, arr in arrays.items():
        sess.register(name, BlockMatrix.from_array(
            arr, (N, N), mesh_square, P(*mesh_square.axis_names)))
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            sess.compute(sess.sql("A * B * C")).data.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    mine = sorted(profile_spans()[before:], key=lambda r: r["start_ns"])
    meta = sess.compile(sess.sql("A * B * C")).meta
    dispatches = [r for r in mine if r["name"] == "matrel.dispatch"]
    assert len(dispatches) == 3
    for r in dispatches:
        assert r["attrs"]["mesh"] == "2x2"
        assert r["attrs"]["hbm_plan_bytes"] == meta["hbm_plan_bytes"]
    products = [r["attrs"] for r in mine
                if r["name"] == "matrel.plan.strategy"]
    assert [(p["chosen"], list(p["panels"]), p["moves_under_dot"])
            for p in products] \
        == [(p["chosen"], p["panels"], p["moves_under_dot"])
            for p in meta["products"]]
    # gy - 1 moves a row panel run under panel 0's dot where the plan
    # is panelled; the plan that fits whole has no loop to stand before
    assert all(p["panels"][1] > 1 and p["moves_under_dot"] == 1
               for p in products)
    whole = chain(mesh_square, arrays, MatrelConfig(strategy_override="rmm"))
    assert [(p["panels"], p["moves_under_dot"])
            for p in whole[1]["products"]] == [([1, 1], 0)] * 2
    compiles = [r for r in mine if r["name"] == "matrel.compile"]
    assert len(compiles) == 1
    reader = harness.load_module(os.path.join(
        ROOT, "benchmarks", "metrics", "planned_hbm_pct.py"))
    said = []
    run = types.SimpleNamespace(
        reduced={"queries": [{}] * 3,
                 "window_s": (mine[-1]["end_ns"] - mine[0]["start_ns"])
                 * 1e-9}, say=said.append)
    assert reader.read(run, records=mine, bytes_limit=4_000_000) \
        == pytest.approx(100.0 * meta["hbm_plan_bytes"] / 4_000_000), said
    # the CPU reports no bytes_limit: nothing, and no raise
    assert reader.read(run, records=mine) is None
    assert program_spans.window(run, mine) is not None
