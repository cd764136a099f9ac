"""Normal-equations regression as a query (PR 31), at tier-1 sizes on
one CPU device: every way of writing (XᵀX)⁻¹Xᵀy through ``session.sql``
reaches one plan whose solve is taken against Xᵀy (k x 1) and never
against Xᵀ (k x N); the one-device memory reckoning stamps that plan and
refuses the N-wide one by name before anything is traced; the spans say
both; and the chip's share of the table is a share: the row quarters'
Grams and right-hand sides add up to the whole table's. k = 100 is a
multiple of neither 8 nor 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import chain as chain_lib
from matrel_tpu.ir import expr as E
from matrel_tpu.ir import rules, stats
from matrel_tpu.parallel import planner, strategies
from matrel_tpu.session import MatrelSession

N, K = 4096, 100
SPELLINGS = ["inv(t(X) * X) * t(X) * y",
             "inv(t(X) * X) * (t(X) * y)",
             "solve(t(X) * X, t(X)) * y",
             "t(y) * X * inv(t(X) * X)"]


@pytest.fixture(scope="module")
def one_device():
    return mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def data():
    """X uniform [-1, 1), y = X theta* + noise, and the float64 normal
    equations' answer on the float32 values the tables hold."""
    rng = np.random.default_rng(31)
    x = rng.uniform(-1.0, 1.0, (N, K)).astype(np.float32)
    y = (x @ rng.standard_normal((K, 1)).astype(np.float32)
         + 0.1 * rng.standard_normal((N, 1)).astype(np.float32))
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    return x, y, np.linalg.solve(x64.T @ x64, x64.T @ y64)


def session_of(mesh, x, y, **config):
    sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
    for name, arr in (("X", x), ("y", y)):
        sess.register(name, BlockMatrix.from_array(
            jnp.asarray(arr), arr.shape, mesh, P(None, None)))
    return sess


def nodes(e, parent=None, seen=None):
    """(node, parent) over the plan, each node once."""
    seen = set() if seen is None else seen
    if e.uid in seen:
        return
    seen.add(e.uid)
    yield e, parent
    for c in e.children:
        yield from nodes(c, e, seen)


def shape_of_plan(e):
    """The plan as nested tuples of kinds and shapes (leaves by name)."""
    if not e.children:
        return ("leaf", e.shape)
    return (e.kind, e.shape) + tuple(shape_of_plan(c) for c in e.children)


def rel_err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def lowered_text(plan):
    """The plan's program as it is handed to the compiler."""
    return plan.jitted.lower(
        *[leaf.attrs["matrix"].data for leaf in plan.leaf_order]).as_text()


# -- the optimizer: one plan, however the formula was typed -------------------


@pytest.mark.parametrize("sql", SPELLINGS)
def test_every_spelling_solves_against_k_by_1(one_device, data, sql):
    x, y, want = data
    sess = session_of(one_device, x, y)
    expr = sess.sql(sql)
    plan = sess.compile(expr)
    solves = [n for n, _ in nodes(plan.optimized) if n.kind == "solve"]
    assert [n.shape for n in solves] == [(K, 1)]
    assert not [n for n, _ in nodes(plan.optimized) if n.kind == "inverse"]
    # N appears in a leaf, and in a leaf's transpose that a product
    # contracts over: in no value the plan computes
    for n, parent in nodes(plan.optimized):
        if N in n.shape and n.children:
            assert n.kind == "transpose" and not n.children[0].children \
                and parent.kind == "matmul", (n.kind, n.shape)
    # the rule counts name the rewrite
    assert plan.meta["rule_hits"]["chain_solve"] == 1
    got = sess.compute(expr).to_numpy()
    got = got.T if got.shape == (1, K) else got
    assert got.shape == (K, 1) and rel_err(got, want) < 2e-5


def test_the_spellings_share_one_plan(one_device, data):
    x, y, _ = data
    sess = session_of(one_device, x, y)
    plans = [shape_of_plan(sess.compile(sess.sql(q)).optimized)
             for q in SPELLINGS]
    assert plans[0] == plans[1] == plans[2]
    assert plans[3] == ("transpose", (1, K), plans[0])   # the answer 1 x k
    assert plans[0] == (
        "solve", (K, 1),
        ("matmul", (K, K), ("transpose", (K, N), ("leaf", (N, K))),
         ("leaf", (N, K))),
        ("matmul", (K, 1), ("transpose", (K, N), ("leaf", (N, K))),
         ("leaf", (N, 1))))


def test_association_is_by_cost_not_by_pattern(one_device):
    """Nothing here is a Gram: a general square A and wide or narrow
    neighbours. The inverse is bracketed with the side that makes the
    solve narrow, left or right, and two-factor products fuse as R7
    always did."""
    def leaf(n, m):
        return E.leaf(BlockMatrix.from_array(
            jnp.zeros((n, m), jnp.float32), (n, m), one_device,
            P(None, None)))

    a, wide, thin, row = leaf(64, 64), leaf(64, 2048), leaf(2048, 1), \
        leaf(1, 2048)
    # A⁻¹ · W · t, typed left to right: solve against W·t (64 x 1)
    counts = {}
    e = rules.optimize(E.matmul(E.matmul(E.inverse(a), wide), thin),
                       counts=counts)
    assert e.kind == "solve" and e.shape == (64, 1)
    assert e.children[1].kind == "matmul" and counts["chain_solve"] == 1
    # r · Wᵀ · A⁻¹ with the wide product typed first: (r·Wᵀ) · A⁻¹ =
    # solve(Aᵀ, (r·Wᵀ)ᵀ)ᵀ, the solve 64 x 1 again
    e = rules.optimize(E.matmul(row, E.matmul(E.transpose(wide),
                                              E.inverse(a))))
    assert e.kind == "transpose" and e.children[0].kind == "solve"
    assert e.children[0].shape == (64, 1)
    # a wide side that cannot be made narrow is solved against as it is
    e = rules.optimize(E.matmul(E.inverse(a), wide))
    assert e.kind == "solve" and e.shape == (64, 2048)
    # the cheaper association costs less by the DP's own reckoning
    good = E.solve(a, E.matmul(wide, thin))
    bad = E.matmul(E.solve(a, wide), thin)
    assert chain_lib.chain_cost(good) < chain_lib.chain_cost(bad)
    assert stats.solve_cost(64, 1) < stats.solve_cost(64, 2048)


def test_the_rewrite_off_keeps_what_was_typed(one_device, data):
    x, y, want = data
    sess = session_of(one_device, x, y, chain_opt=False)
    plan = sess.compile(sess.sql("solve(t(X) * X, t(X)) * y"))
    assert [n.shape for n, _ in nodes(plan.optimized)
            if n.kind == "solve"] == [(K, N)]
    assert "chain_solve" not in plan.meta["rule_hits"]
    got = sess.compute(sess.sql("solve(t(X) * X, t(X)) * y")).to_numpy()
    assert rel_err(got, want) < 2e-5


# -- the planner: the plan's peak on ONE device -------------------------------


def test_the_right_plan_is_stamped(one_device, data):
    x, y, _ = data
    sess = session_of(one_device, x, y)
    meta = sess.compile(sess.sql(SPELLINGS[0])).meta
    tables = x.nbytes + y.nbytes
    gram, rhs, theta = K * K * 4, K * 4, K * 4
    assert [(p["node"], p["shape"], p["chosen"], p["refused_hbm"])
            for p in meta["products"]] == [
        ("matmul", [K, K], "xla", []), ("matmul", [K, 1], "xla", []),
        ("solve", [K, 1], "solve", [])]
    # residents and the answer from the start; the Gram; Xᵀy beside it;
    # at the solve both, and its factorisation's copies
    assert [p["hbm_plan_bytes"] for p in meta["products"]] == [
        tables + theta + gram, tables + theta + gram + rhs,
        tables + theta + gram + rhs
        + int(planner.solve_transient_bytes(K, 1))]
    assert meta["hbm_plan_bytes"] == meta["products"][-1]["hbm_plan_bytes"]
    assert meta["mesh"] == "1x1"


def test_an_n_wide_plan_is_refused_by_name(one_device, data):
    x, y, _ = data
    budget = 2 * x.nbytes     # the table and one more of its size: not two
    wide = session_of(one_device, x, y, chain_opt=False,
                      hbm_budget_bytes=budget)
    with pytest.raises(planner.PlanMemoryError) as refused:
        wide.compile(wide.sql("solve(t(X) * X, t(X)) * y"))
    said = str(refused.value)
    # the first array that does not fit: Xᵀ as the solve's right side
    assert f"transpose {K}x{N}" in said and f"{x.nbytes:,} bytes" in said
    assert f"{budget:,} bytes" in said and "before tracing" in said
    # the same text under the same budget with the rewrite on: it fits
    sess = session_of(one_device, x, y, hbm_budget_bytes=budget)
    meta = sess.compile(sess.sql("solve(t(X) * X, t(X)) * y")).meta
    assert meta["hbm_plan_bytes"] <= budget
    # and with the reckoning off the wide plan is handed over
    off = session_of(one_device, x, y, chain_opt=False, hbm_budget_bytes=0)
    assert "products" in off.compile(
        off.sql("solve(t(X) * X, t(X)) * y")).meta


def test_spans_say_rules_and_reckoning(one_device, data, tmp_path):
    """Under a profiler session ``matrel.plan.optimize`` carries the
    plan's rule hits, one ``matrel.plan.strategy`` span stands for every
    reckoned node with ``hbm_plan_bytes``, and ``matrel.dispatch``
    carries the plan's."""
    from matrel_tpu.obs.trace import profile_spans
    x, y, _ = data
    sess = session_of(one_device, x, y)
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            sess.compute(sess.sql(SPELLINGS[0])).to_numpy()
    finally:
        jax.profiler.stop_trace()
    mine = profile_spans()[before:]
    meta = sess.compile(sess.sql(SPELLINGS[0])).meta
    (optimize,) = [r for r in mine if r["name"] == "matrel.plan.optimize"]
    assert optimize["attrs"]["chain_solve"] == 1
    assert optimize["attrs"]["chain_dp"] == 1
    strategy = [r["attrs"] for r in mine
                if r["name"] == "matrel.plan.strategy"]
    assert [(p["node"], p["hbm_plan_bytes"]) for p in strategy] \
        == [(p["node"], p["hbm_plan_bytes"]) for p in meta["products"]]
    dispatches = [r for r in mine if r["name"] == "matrel.dispatch"]
    assert len(dispatches) == 2 and all(
        r["attrs"]["hbm_plan_bytes"] == meta["hbm_plan_bytes"]
        and r["attrs"]["mesh"] == "1x1" for r in dispatches)
    assert len([r for r in mine if r["name"] == "matrel.compile"]) == 1
    # a table of 4,096 rows is no long contraction: nothing says a
    # triangle, in the records or the spans
    assert not any(stamp in p for p in meta["products"] + strategy
                   for stamp in ("gram_tiles", "gram_rides", "rides_gram"))


def test_records_and_spans_say_the_triangle(one_device, tmp_path):
    """On a table of LONG_CONTRACTION rows or more the Gram's record in
    ``plan.meta["products"]`` and its ``matrel.plan.strategy`` span
    carry ``gram_tiles``, the block products a panel multiplies of those
    the square holds, and (PR 34) ``gram_rides``, the one column of
    ``t(X) * y`` its loop carries; ``t(X) * y`` says ``rides_gram``, the
    solve carries none of them. The served query's theta is the float64
    normal equations' to the 2e-5 of the short table's test."""
    from matrel_tpu.obs.trace import profile_spans
    rng = np.random.default_rng(32)
    n, k = strategies.LONG_CONTRACTION + 40, strategies.GRAM_BLOCK + 4
    x = rng.uniform(-1, 1, (n, k)).astype(np.float32)
    y = (x @ rng.standard_normal((k, 1)).astype(np.float32)
         + 0.1 * rng.standard_normal((n, 1)).astype(np.float32))
    sess = session_of(one_device, x, y)
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = sess.compute(sess.sql(SPELLINGS[0])).to_numpy()
    finally:
        jax.profiler.stop_trace()
    meta = sess.compile(sess.sql(SPELLINGS[0])).meta
    spans = [r["attrs"] for r in profile_spans()[before:]
             if r["name"] == "matrel.plan.strategy"]
    for said in (meta["products"], spans):
        assert [(p["node"], p["shape"], p.get("gram_tiles"),
                 p.get("gram_rides"), p.get("rides_gram"))
                for p in said] == [
            ("matmul", [k, k], [3, 4], 1, None),
            ("matmul", [k, 1], None, None, True),
            ("solve", [k, 1], None, None, None)]
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    assert rel_err(got, np.linalg.solve(x64.T @ x64, x64.T @ y64)) < 2e-5
    assert strategies.gram_tiles(k) == (3, 4)
    assert strategies.gram_tiles(1000) == (10, 16)
    assert strategies.gram_tiles(strategies.GRAM_BLOCK) == (1, 1)


# -- the executor: the pair is one loop, whichever is reached first -----------


@pytest.mark.parametrize("first", ["gram", "rider"])
def test_the_pair_is_one_loop_whichever_is_reached_first(one_device, first):
    """A plan that reads ``t(X) * y`` and ``t(X) * X`` in either order
    (``(t(X) * y) .* rowsum(t(X) * X)`` and the operands swapped, planned
    and lowered without the rule batch, which would turn the row sum
    into a matvec): one loop over the table's panels, the block columns'
    dots and no other, and the value of the formula."""
    from matrel_tpu import executor
    rng = np.random.default_rng(34)
    n, k = strategies.LONG_CONTRACTION + 40, strategies.GRAM_BLOCK + 4
    x = rng.uniform(-1, 1, (n, k)).astype(np.float32)
    y = rng.uniform(-1, 1, (n, 1)).astype(np.float32)
    lx, ly = (E.leaf(BlockMatrix.from_array(
        jnp.asarray(arr), arr.shape, one_device, P(None, None)))
        for arr in (x, y))
    gram = E.matmul(E.transpose(lx), lx).row_sum()
    rider = E.matmul(E.transpose(lx), ly)
    plan = planner.annotate_strategies(
        gram.elem_multiply(rider) if first == "gram"
        else rider.elem_multiply(gram), one_device, MatrelConfig())
    said = [(p.get("gram_rides"), p.get("rides_gram"))
            for p in planner.hbm_report(plan)]
    assert said == ([(1, None), (None, True)] if first == "gram"
                    else [(None, True), (1, None)])
    fn = jax.jit(executor.Lowerer(one_device, MatrelConfig()).lower(
        plan, E.leaves(plan)))
    tables = [leaf.attrs["matrix"].data for leaf in E.leaves(plan)]
    text = fn.lower(*tables).as_text()
    assert text.count("stablehlo.while") == 1
    assert text.count("dot_general") == 2 * len(strategies.gram_blocks(k))
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    want = (x64.T @ y64) * (x64.T @ x64).sum(axis=1, keepdims=True)
    assert rel_err(np.asarray(fn(*tables))[:k, :1], want) < 1e-5


# -- the executor: a long float32 contraction is accumulated in panels --------


@pytest.mark.parametrize("ca, cb", [(0, 0), (1, 0), (0, 1), (1, 1)])
def test_dot_in_panels_is_the_product(ca, cb):
    """Two whole panels and a tail, every way the operands may lie."""
    rng = np.random.default_rng(ca * 2 + cb)
    length = 2 * strategies.ACC_PANEL_ROWS + 77
    a = rng.uniform(-1, 1, (length, 5) if ca == 0 else (5, length))
    b = rng.uniform(-1, 1, (length, 3) if cb == 0 else (3, length))
    want = (a.T if ca == 0 else a) @ (b if cb == 0 else b.T)
    got = jax.jit(lambda u, v: strategies.dot_in_panels(u, ca, v, cb))(
        jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    assert got.dtype == jnp.float32 and rel_err(np.asarray(got), want) < 1e-6


@pytest.mark.parametrize("k", [6, 128, 130, 257, 1000])
@pytest.mark.parametrize("ca", [0, 1])
def test_gram_in_panels_is_the_gram_and_symmetric(ca, k):
    """Two whole panels and a tail, ``t(a) * a`` (ca = 0) and ``a *
    t(a)`` (ca = 1), k from one ragged block to four (the cell's 1000 =
    3 x 256 + 232): the float64 Gram, symmetric to the last bit because
    the lower triangle is a copy of the upper. On the upper triangle it
    is ``dot_in_panels``' full square to 1e-6 of the largest entry and
    not to the bit: a narrower dot adds the same 8,192 products of an
    entry in another order (on a v5e 7% of the entries agree to the last
    bit, the furthest apart by 2.9e-7; PR 32), and the full square is
    itself not symmetric there."""
    rng = np.random.default_rng(100 * ca + k)
    length = 2 * strategies.ACC_PANEL_ROWS + 77
    a = rng.uniform(-1, 1, (length, k) if ca == 0 else (k, length)) \
        .astype(np.float32)
    a64 = a.astype(np.float64)
    want = a64.T @ a64 if ca == 0 else a64 @ a64.T
    dev = jnp.asarray(a)
    got = np.asarray(jax.jit(
        lambda u: strategies.gram_in_panels(u, ca))(dev))
    assert got.dtype == np.float32 and got.shape == (k, k)
    assert rel_err(got, want) < 1e-6
    assert np.array_equal(got, got.T)
    square = np.asarray(jax.jit(
        lambda u: strategies.dot_in_panels(u, ca, u, ca))(dev))
    upper = np.triu_indices(k)
    assert rel_err(got[upper], square[upper].astype(np.float64)) < 1e-6
    assert strategies.gram_tiles(k) == {
        6: (1, 1), 128: (1, 1), 130: (1, 1), 257: (3, 4), 1000: (10, 16)}[k]


@pytest.mark.parametrize("sql", ["t(X) * X", "X * t(X)"])
def test_a_long_gram_through_the_session_is_the_triangle(one_device, sql):
    """Both orientations through ``session.sql`` and ``compute`` with a
    contraction of LONG_CONTRACTION + 40 (16 panels and a tail of 40)
    and two block columns: the float64 Gram, symmetric to the last bit,
    stamped ``gram_tiles`` [3, 4]."""
    rng = np.random.default_rng(len(sql))
    n, k = strategies.LONG_CONTRACTION + 40, strategies.GRAM_BLOCK + 4
    x = rng.uniform(-1, 1, (n, k)).astype(np.float32)
    x64 = x.astype(np.float64)
    table = x if sql == "t(X) * X" else np.ascontiguousarray(x.T)
    sess = MatrelSession(mesh=one_device)
    sess.register("X", BlockMatrix.from_array(
        jnp.asarray(table), table.shape, one_device, P(None, None)))
    (record,) = sess.compile(sess.sql(sql)).meta["products"]
    assert record["gram_tiles"] == [3, 4] and record["shape"] == [k, k]
    got = sess.compute(sess.sql(sql)).to_numpy()
    assert rel_err(got, x64.T @ x64) < 1e-6
    assert np.array_equal(got, got.T)


def test_only_long_float32_contractions_are_panelled(one_device,
                                                     monkeypatch):
    """From LONG_CONTRACTION rows on, ``t(X) * X`` and ``t(X) * y`` of
    float32 tables lower to a loop over panels (the product unchanged);
    a shorter table, and a bfloat16 one, to the one dot they always
    were. And only the Gram multiplies a triangle (block columns of 4
    here, so that k = 6 has two: two dots in the loop's body and two in
    the tail): ``t(X) * y``, ``t(X) * Z`` with Z another table of X's
    shape, and a Gram whose precision tier is stamped keep one dot a
    panel; the short and the bfloat16 Gram their one dot."""
    monkeypatch.setattr(strategies, "GRAM_BLOCK", 4)
    rng = np.random.default_rng(5)

    def lowered(n, dtype, sql, **config):
        x = rng.uniform(-1, 1, (n, 6)).astype(np.float32)
        z = rng.uniform(-1, 1, (n, 6)).astype(np.float32)
        y = rng.uniform(-1, 1, (n, 1)).astype(np.float32)
        sess = MatrelSession(mesh=one_device, config=MatrelConfig(**config))
        for name, arr in (("X", x), ("y", y), ("Z", z)):
            sess.register(name, BlockMatrix.from_array(
                jnp.asarray(arr, dtype), arr.shape, one_device,
                P(None, None)))
        plan = sess.compile(sess.sql(sql))
        text = lowered_text(plan)
        x, y, z = (np.asarray(jnp.asarray(v, dtype).astype(jnp.float32),
                              np.float64) for v in (x, y, z))
        want = x.T @ {"t(X) * X": x, "t(X) * y": y, "t(X) * Z": z}[sql]
        got = np.asarray(sess.compute(sess.sql(sql)).data
                         .astype(jnp.float32), np.float64)
        (record,) = plan.meta["products"]
        return ("while" in text, text.count("dot_general"),
                record.get("gram_tiles"), rel_err(got, want))

    long = strategies.LONG_CONTRACTION + 40
    for sql, dots, tiles in (("t(X) * X", 4, [3, 4]),
                             ("t(X) * y", 2, None),
                             ("t(X) * Z", 2, None)):
        *said, err = lowered(long, jnp.float32, sql)
        assert said == [True, dots, tiles] and err < 1e-5
        looped, dots, tiles, err = lowered(long - 80, jnp.float32, sql)
        assert (looped, dots, tiles) == (False, 1, None) and err < 1e-5
    looped, dots, tiles, err = lowered(long, jnp.bfloat16, "t(X) * X")
    assert (looped, dots, tiles) == (False, 1, None) and err < 1e-2
    # a stamped tier owns the product's numerics: the float32 tier is
    # the panelled full square, as it was
    looped, dots, tiles, err = lowered(long, jnp.float32, "t(X) * X",
                                       precision_sla="float32")
    assert (looped, dots, tiles) == (True, 2, None) and err < 1e-5


# -- the other cells' products lower as they did -------------------------------


def _lowered(mesh, spec, shape, dtype, sql):
    """(plan.meta's records, the lowered text) of ``sql`` over tables M
    and N of zeros."""
    sess = MatrelSession(mesh=mesh)
    for name in "MN":
        sess.register(name, BlockMatrix.from_array(
            jax.device_put(jnp.zeros(shape, dtype),
                           jax.sharding.NamedSharding(mesh, spec)),
            shape, mesh, spec))
    plan = sess.compile(sess.sql(sql))
    return plan.meta["products"], lowered_text(plan)


@pytest.mark.parametrize("sql", ["M * N", "t(M) * M", "M * t(M)"])
def test_a_dashboard_product_lowers_as_it_did(one_device, sql):
    """Cell 1's shape: float32 4096^2 on one device, a Gram among them.
    No contraction is long: one dot, no loop, no ``gram_tiles``."""
    records, text = _lowered(one_device, P(None, None), (4096, 4096),
                             jnp.float32, sql)
    assert not any("gram_tiles" in r for r in records)
    assert "while" not in text and text.count("dot_general") == 1


@pytest.mark.parametrize("sql", ["M * N", "t(M) * M", "M * t(M)"])
def test_a_mesh_product_lowers_as_it_did(mesh_square, sql):
    """Cell 4's kind: bfloat16 tables sharded over the 2x2 mesh, a Gram
    among them, and the same tables in float32 (a mesh's local dots are
    not this PR's): the strategy's program, no ``gram_tiles``, no loop
    over panels."""
    for dtype in (jnp.bfloat16, jnp.float32):
        records, text = _lowered(mesh_square, P(*mesh_square.axis_names),
                                 (1024, 1024), dtype, sql)
        assert records and not any("gram_tiles" in r for r in records)
        assert "while" not in text


# -- what the pre-session fit held (PR 44): a ridge term, "high", the 2x4 mesh --


def test_a_ridge_term_is_solved_with_the_gram(one_device, data):
    """``fit(l2=)``'s fact as a query: the penalty is a registered k x k
    table, the chain DP brackets the inverse of the SUM as one solve
    against ``t(X) * y`` (k x 1, never k x N), the answer is the float64
    ridge solution on the tables' values, and a heavy penalty shrinks
    it."""
    x, y, want = data
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    sess = session_of(one_device, x, y)
    norms = []
    for lam in (0.0, 500.0):
        ridge = (lam * np.eye(K)).astype(np.float32)
        sess.register("R", BlockMatrix.from_array(
            jnp.asarray(ridge), ridge.shape, one_device, P(None, None)))
        expr = sess.sql("inv(t(X) * X + R) * t(X) * y")
        plan = sess.compile(expr)
        (solve,) = [n for n, _ in nodes(plan.optimized) if n.kind == "solve"]
        assert solve.shape == (K, 1)
        assert solve.children[0].kind == "elemwise"
        assert plan.meta["rule_hits"]["chain_solve"] == 1
        got = sess.compute(expr).to_numpy()
        assert rel_err(got, np.linalg.solve(
            x64.T @ x64 + lam * np.eye(K), x64.T @ y64)) < 2e-5
        norms.append(float(np.linalg.norm(got)))
    assert rel_err(got, want) > 1e-2 and norms[1] < 0.9 * norms[0]


@pytest.mark.parametrize("case", ["by_rows", "by_rows_high", "canonical"])
def test_a_short_table_on_the_2x4_mesh_is_lstsq(mesh8, case):
    """What ``fit`` / ``fit_fused`` held, on the path that stays: the
    query over eight devices, ``X`` and ``y`` by rows over all of them
    or canonical ``P(x, y)``, against float64 ``lstsq``; a short
    contraction goes through the ranked strategies (nothing is
    multiplied in place), and ``matmul_precision`` "high" multiplies
    ops/gram.py's bfloat16 parts and still recovers theta."""
    rng = np.random.default_rng(44)
    x = rng.standard_normal((256, 8)).astype(np.float32)
    y = (x @ np.linspace(1, 2, 8).reshape(8, 1).astype(np.float32)
         + 0.01 * rng.standard_normal((256, 1)).astype(np.float32))
    high = case == "by_rows_high"
    sess = mesh_session(mesh8, jnp.asarray(x), jnp.asarray(y),
                        canonical=case == "canonical",
                        **({"matmul_precision": "high"} if high else {}))
    expr = sess.sql(SPELLINGS[0])
    plan = sess.compile(expr)
    assert plan.meta["mesh"] == "2x4"
    assert plan.meta["rule_hits"]["chain_solve"] == 1
    assert planner.OWN_ROWS not in plan.meta["executors"]
    assert set(plan.meta["executors"]) <= set(strategies.STRATEGIES)
    assert ("bf16" in lowered_text(plan)) is high
    want = np.linalg.lstsq(x.astype(np.float64), y.astype(np.float64),
                           rcond=None)[0]
    got = sess.compute(expr).to_numpy()
    assert got.shape == (8, 1)
    assert rel_err(got, want) < (2e-5 if high else 2e-6)


# -- the chip's share of the deployment is a share ----------------------------


def test_row_quarters_add_up_to_the_whole_table(one_device, data):
    """model-configs section 4: what four chips would each compute of
    the row-sharded table — ``t(Xi) * Xi`` and ``t(Xi) * yi`` through
    the session, as the cell's chip does for its quarter — adds up to
    the whole table's Gram and right-hand side, and the whole's theta is
    the reference's."""
    x, y, want = data
    whole = session_of(one_device, x, y)
    gram = whole.compute(whole.sql("t(X) * X")).to_numpy()
    rhs = whole.compute(whole.sql("t(X) * y")).to_numpy()
    parts_g = np.zeros((K, K), np.float64)
    parts_r = np.zeros((K, 1), np.float64)
    for i in range(4):
        rows = slice(i * N // 4, (i + 1) * N // 4)
        quarter = session_of(one_device, x[rows], y[rows])
        parts_g += quarter.compute(quarter.sql("t(X) * X")).to_numpy()
        parts_r += quarter.compute(quarter.sql("t(X) * y")).to_numpy()
    assert rel_err(parts_g, gram) < 1e-5 and rel_err(parts_r, rhs) < 1e-5
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    assert rel_err(parts_g, x64.T @ x64) < 1e-5
    assert rel_err(parts_r, x64.T @ y64) < 1e-5
    assert rel_err(np.linalg.solve(parts_g, parts_r), want) < 1e-5
    assert rel_err(whole.compute(whole.sql(SPELLINGS[0])).to_numpy(),
                   want) < 2e-5


# -- the whole table, by rows on a mesh (PR 39; cell linreg_10m_2x2) ------------

#: 4 panels of ACC_PANEL_ROWS and a ragged tail of 520 rows a device; the
#: whole contraction is long
ROWS_A_DEVICE = 4 * strategies.ACC_PANEL_ROWS + 520
MESH_N, MESH_K = 4 * ROWS_A_DEVICE, strategies.GRAM_BLOCK + 4


def whole_config():
    """The benchmark configuration's own module: its generator and its
    plain reference (nothing of the program)."""
    import os
    from benchmarks import run as harness
    return harness.load_module(os.path.join(
        os.path.dirname(harness.__file__), "configs",
        "matrel_linreg_10m_whole.py"))


@pytest.fixture(scope="module")
def by_rows(mesh_square):
    """(session, X, y): the configuration's seeded tables cut by rows
    over the 2x2 mesh's four devices, registered as they lie."""
    from benchmarks.reference import device_key
    x, y = whole_config().generate(mesh_square, MESH_N, MESH_K,
                                   ROWS_A_DEVICE, 0.1, device_key(39))
    return mesh_session(mesh_square, x, y), x, y


def lie(mesh, shape, canonical=False):
    """The spec of a table cut by rows over all devices, or the
    canonical one of its shape."""
    from matrel_tpu.core import padding
    return (padding.canonical_spec(shape, mesh) if canonical
            else P(tuple(mesh.axis_names), None))


def mesh_session(mesh, x, y, canonical=False, **config):
    sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
    for name, arr in (("X", x), ("y", y)):
        spec = lie(mesh, tuple(arr.shape), canonical)
        sess.register(name, BlockMatrix.from_array(
            jax.device_put(arr, jax.sharding.NamedSharding(mesh, spec)),
            tuple(arr.shape), mesh, spec))
    return sess


def collective_ops(plan):
    """{collective: how many such OPERATIONS the compiled program holds}
    (``plan.collectives()`` counts every mention of the name)."""
    import re
    text = plan.hlo()
    found = {op: len(re.findall(rf"\s{op}(?:-start)?\(", text))
             for op in ("all-reduce", "all-gather", "reduce-scatter",
                        "collective-permute", "all-to-all")}
    return {op: n for op, n in found.items() if n}


def test_theta_on_the_mesh_is_the_configurations_reference(by_rows):
    """``session.sql`` + ``compute`` + ``to_numpy`` on the 2x2 mesh over
    the row-partitioned table against the configuration's plain
    reference (panel sums where the rows lie, float64 on the host), at
    the cell's limit; every device holds whole rows, a ragged last
    panel among them."""
    sess, x, y = by_rows
    assert {s.data.shape for s in x.addressable_shards} \
        == {(ROWS_A_DEVICE, MESH_K)}
    assert ROWS_A_DEVICE % strategies.ACC_PANEL_ROWS
    want = whole_config().PanelSums(
        MESH_K, strategies.ACC_PANEL_ROWS).solve(x, y)
    got = sess.compute(sess.sql(SPELLINGS[0])).to_numpy()
    assert got.shape == (MESH_K, 1) and rel_err(got, want) < 5e-6
    assert sess.last_plan()["executors"] == [planner.OWN_ROWS]


def test_the_mesh_gram_is_the_sum_of_its_devices_quarters(by_rows):
    """``test_row_quarters_add_up_to_the_whole_table`` ties a chip's
    share to the whole on one device; this ties the mesh to the shares:
    the mesh's Gram and ``t(X) * y`` are the sums of what
    ``gram_in_panels`` gives for each device's own rows, and the Gram
    is symmetric to the last bit (the all-reduce adds block columns,
    the mirror copies them after it)."""
    sess, x, y = by_rows
    gram = sess.compute(sess.sql("t(X) * X")).to_numpy()
    rhs = sess.compute(sess.sql("t(X) * y")).to_numpy()
    parts_g = np.zeros((MESH_K, MESH_K), np.float64)
    parts_r = np.zeros((MESH_K, 1), np.float64)
    for xs, ys in zip(x.addressable_shards, y.addressable_shards):
        g, r = jax.jit(lambda a, b: strategies.gram_in_panels(
            a, 0, MatrelConfig(), rhs=b))(xs.data, ys.data)
        parts_g += np.asarray(g, np.float64)
        parts_r += np.asarray(r, np.float64)
    assert rel_err(gram, parts_g) < 1e-6 and rel_err(rhs, parts_r) < 1e-6
    assert np.array_equal(gram, gram.T)


def test_the_mesh_program_reduces_once_and_moves_no_table(by_rows):
    """The regression's program on the mesh: ONE all-reduce (the block
    columns' accumulators, Xᵀy's column with the last) and no other
    collective, no all-gather of X, no transposed or gathered array of
    X's shape, one loop over the panels with the triangle's dots and the
    tail's, and the solve inside a shard_map of its own."""
    sess, x, _ = by_rows
    plan = sess.compile(sess.sql(SPELLINGS[0]))
    assert collective_ops(plan) == {"all-reduce": 1}
    text = lowered_text(plan)
    # asked for a block column at a time, combined into the one above
    assert text.count("stablehlo.all_reduce") == len(
        strategies.gram_blocks(MESH_K))
    assert "all_gather" not in text and "collective_permute" not in text
    for rows in (MESH_N, ROWS_A_DEVICE):
        assert f"tensor<{MESH_K}x{rows}xf32>" not in text
    # the panels' loop (the solve's pivots loop beside it): the block
    # columns' dots in its body and in the ragged tail, and no other
    assert "stablehlo.while" in text
    assert text.count("dot_general") == 2 * len(
        strategies.gram_blocks(MESH_K))
    assert text.count("sdy.manual_computation") \
        + text.count("shard_map") >= 2


def test_the_stamps_agree_on_span_meta_and_last_plan(by_rows, tmp_path):
    """``operand_layout``, ``devices``, ``rows_a_device`` and
    ``reduce_bytes`` beside ``gram_tiles`` and ``gram_rides``: the same
    on the ``matrel.plan.strategy`` spans, in ``plan.meta["products"]``
    and from ``last_plan()``; ``chosen`` names the lowering, the Gram's
    all-reduce carries the rider's column and the rider moves nothing
    of its own; ``matrel.dispatch`` says the mesh."""
    from matrel_tpu.obs.trace import profile_spans
    _, x, y = by_rows
    sess = mesh_session(x.sharding.mesh, x, y)
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sess.compute(sess.sql(SPELLINGS[0])).to_numpy()
    finally:
        jax.profiler.stop_trace()
    mine = profile_spans()[before:]
    spans = [r["attrs"] for r in mine if r["name"] == "matrel.plan.strategy"]
    meta = sess.compile(sess.sql(SPELLINGS[0])).meta
    k = MESH_K
    for said in (spans, meta["products"], sess.last_plan()["products"]):
        assert [(p["node"], p["chosen"], p.get("operand_layout"),
                 p.get("devices"), p.get("rows_a_device"),
                 p.get("reduce_bytes"), p.get("gram_tiles"),
                 p.get("gram_rides"), p.get("rides_gram"))
                for p in said] == [
            ("matmul", "cpmm_rows", "row", 4, ROWS_A_DEVICE,
             strategies.gram_reduce_bytes(k, 1), [3, 4], 1, None),
            ("matmul", "cpmm_rows", "row", 4, ROWS_A_DEVICE, 0, None, None,
             True),
            ("solve", "solve", None, None, None, None, None, None, None)]
    assert strategies.gram_reduce_bytes(k, 1) == 4 * (
        256 * 256 + k * (k - 256 + 1))
    assert strategies.gram_reduce_bytes(1000, 1) == 2_504_864
    (dispatch,) = [r["attrs"] for r in mine if r["name"] == "matrel.dispatch"]
    assert dispatch["mesh"] == "2x2" \
        and dispatch["hbm_plan_bytes"] == meta["hbm_plan_bytes"]
    # a device's rows of X and y, and megabytes beside them
    shard = ROWS_A_DEVICE * (k + 1) * 4
    assert shard < meta["hbm_plan_bytes"] < shard + 16 * k * k * 4


def test_a_lone_long_product_by_rows_is_panelled_too(by_rows):
    """``t(X) * y`` with no Gram beside it: ``dot_in_panels`` over every
    device's own rows under the same ``shard_map``, one all-reduce."""
    sess, x, y = by_rows
    plan = sess.compile(sess.sql("t(X) * y"))
    (record,) = plan.meta["products"]
    assert record["chosen"] == "cpmm_rows" and "gram_tiles" not in record
    assert record["reduce_bytes"] == 4 * MESH_K
    assert collective_ops(plan) == {"all-reduce": 1}
    assert "stablehlo.while" in lowered_text(plan)
    got = sess.compute(sess.sql("t(X) * y")).to_numpy()
    want = np.asarray(x, np.float64).T @ np.asarray(y, np.float64)
    assert rel_err(got, want) < 1e-6


@pytest.mark.parametrize("case", ["bfloat16", "short", "high", "canonical",
                                  "forced"])
def test_only_a_long_float32_gram_by_rows_takes_the_lowering(mesh_square,
                                                             case):
    """On a mesh only a long float32 contraction whose operands lie by
    rows over all devices is multiplied where it lies: a bfloat16
    table, a short contraction, ``matmul_precision`` "high" (ops/gram.py's
    split over a ranked strategy), a canonical ``P(x, y)`` table (not
    re-laid behind its owner's back) and a forced strategy plan as they
    did: a ranked strategy, no ``gram_tiles``, no loop over panels."""
    n = MESH_N - (80 * 1024 if case == "short" else 0)
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    config = {"high": {"matmul_precision": "high"},
              "forced": {"strategy_override": "cpmm"}}.get(case, {})
    x = jnp.zeros((n, 8), dtype)
    sess = mesh_session(mesh_square, x, x[:, :1],
                        canonical=case == "canonical", **config)
    plan = sess.compile(sess.sql("t(X) * X"))
    records = [p for p in plan.meta["products"] if p["node"] == "matmul"]
    assert records and all(
        p["chosen"] in strategies.STRATEGIES and "gram_tiles" not in p
        and "operand_layout" not in p for p in records), records
    assert planner.OWN_ROWS not in plan.meta["executors"]
    assert "stablehlo.while" not in lowered_text(plan)
    # and the long float32 Gram by rows beside it does
    if case == "short":
        x = jnp.zeros((MESH_N, 8), jnp.float32)
        taken = mesh_session(mesh_square, x, x[:, :1])
        (record,) = taken.compile(taken.sql("t(X) * X")).meta["products"]
        assert record["chosen"] == planner.OWN_ROWS \
            and record["gram_tiles"] == [1, 1]


def test_a_long_table_by_rows_on_the_2x4_mesh_is_multiplied_in_place(mesh8):
    """``dryrun_multichip``'s regression stage as a test: eight devices
    (the 2x2 fixture above has four), ``X`` and ``y`` by rows over all
    of them, two panels and a ragged tail of 3 rows a device: both
    products are multiplied where the rows lie, nothing but all-reduces
    crosses the mesh, against float64 ``lstsq``."""
    rows = 8 * (strategies.LONG_CONTRACTION // 8 + 3)
    rng = np.random.default_rng(44)
    x = rng.standard_normal((rows, 128)).astype(np.float32)
    y = (x @ rng.standard_normal((128, 1))).astype(np.float32)
    sess = mesh_session(mesh8, jnp.asarray(x), jnp.asarray(y))
    expr = sess.sql(SPELLINGS[0])
    plan = sess.compile(expr)
    assert plan.meta["executors"] == [planner.OWN_ROWS]
    gram, rhs, _ = plan.meta["products"]
    for product in (gram, rhs):
        assert product["devices"] == 8 \
            and product["rows_a_device"] == rows // 8
    assert gram["gram_tiles"] == [1, 1]
    assert set(collective_ops(plan)) == {"all-reduce"}
    want = np.linalg.lstsq(x.astype(np.float64), y.astype(np.float64),
                           rcond=None)[0]
    assert rel_err(sess.compute(expr).to_numpy(), want) < 5e-6


def _described_whole(mesh, canonical):
    """A session over the whole table's SHAPES (no array) on ``mesh``."""
    sess = MatrelSession(mesh=mesh)
    for name, shape in (("X", (10_223_616, 1000)), ("y", (10_223_616, 1))):
        spec = lie(mesh, shape, canonical)
        sess.register(name, BlockMatrix.from_array(
            jax.ShapeDtypeStruct(shape, jnp.float32,
                                 sharding=jax.sharding.NamedSharding(
                                     mesh, spec)), shape, mesh, spec))
    return sess


def test_the_gate_refuses_the_cpmm_plan_of_the_whole_table_by_name(
        mesh_square):
    """From shapes alone, before anything is traced: a canonical
    ``P(x, y)`` whole table plans as it did (``cpmm`` over a transposed
    copy), which no chip holds: a PlanMemoryError that names the
    transpose, the product and its strategy, and says how the table has
    to lie. The same shapes by rows plan at a quarter and megabytes."""
    sess = _described_whole(mesh_square, canonical=True)
    with pytest.raises(planner.PlanMemoryError) as refused:
        sess.compile(sess.sql(SPELLINGS[0]))
    said = str(refused.value)
    assert "transpose 1000x10223616" in said
    assert "matmul 1000x1000 under cpmm" in said and "2x2 mesh" in said
    assert "PartitionSpec(('x', 'y'), None)" in said
    rows = _described_whole(mesh_square, canonical=False)
    meta = rows.compile(rows.sql(SPELLINGS[0])).meta
    assert meta["executors"] == [planner.OWN_ROWS]
    quarter = 2_555_904 * 1001 * 4
    assert quarter < meta["hbm_plan_bytes"] < quarter + 16_000_000
    assert [p.get("rows_a_device") for p in meta["products"]] \
        == [2_555_904, 2_555_904, None]
