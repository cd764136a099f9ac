"""The ``mmchain`` node (PR 54): ``t(X) * (X * v)`` and ``t(X) * (w .* (X
* v))`` over one dense leaf, answered in ONE pass over X
(ops/mmchain.py). The rule that writes it and what it leaves alone, the
planner's verdict by name (``last_plan()["mmchain"]``), the kernel
against the dense products at ragged sizes, LinearRegCG's loop through
``session.sql`` + ``compute`` against a plain float64 loop, the spans,
and the programs it must leave as the parent lowered them."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix
from matrel_tpu.ir import expr as E, rules, stats
from matrel_tpu.ops import mmchain as mmchain_lib
from matrel_tpu.parallel import planner
from matrel_tpu.session import MatrelSession

N, K = 5000, 104        # two whole tiles of 2,048 rows and a ragged tail


@pytest.fixture(scope="module")
def one_device():
    return mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(54)
    return {"X": rng.uniform(-1.0, 1.0, (N, K)).astype(np.float32),
            "p": rng.standard_normal((K, 1)).astype(np.float32),
            "w": rng.uniform(0.5, 2.0, (N, 1)).astype(np.float32),
            "y": rng.standard_normal((N, 1)).astype(np.float32),
            "V": rng.standard_normal((K, 3)).astype(np.float32),
            "lam": np.full((1, 1), 1e-6, np.float32)}


def session_of(mesh, data, spec=P(None, None), **config):
    sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
    for name, arr in data.items():
        sess.register(name, BlockMatrix.from_array(
            jnp.asarray(arr), arr.shape, mesh,
            spec if name in ("X", "w", "y") else P(None, None)))
    return sess


def chain64(data, weighted=False):
    x = data["X"].astype(np.float64)
    q = x @ data["p"]
    return x.T @ (q * data["w"] if weighted else q)


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the rule -----------------------------------------------------------------


@pytest.mark.parametrize("sql,weighted", [
    ("t(X) * (X * p)", False),
    ("t(X) * X * p", False),                    # the chain DP brackets it
    ("t(X) * (X * p) + p * lam", False),
    ("t(X) * (w .* (X * p))", True),
    ("t(X) * ((X * p) .* w)", True)])
def test_the_rule_writes_the_node(one_device, data, sql, weighted):
    sess = session_of(one_device, data, pallas_interpret=True)
    plan = sess.compile(sess.sql(sql))
    assert plan.meta["rule_hits"].get("mmchain_product") == 1
    found = [n for n in planner._nodes(plan.optimized)
             if n.kind == "mmchain"]
    (node,) = found
    assert node.shape == (K, 1) and node.nnz is None
    assert node.attrs["weighted"] is weighted
    assert len(node.children) == (3 if weighted else 2)
    assert node.children[0].kind == "leaf"
    assert not any(n.kind == "matmul" and n.shape == (N, 1)
                   for n in planner._nodes(plan.optimized))
    assert ("weighted" in E.pretty(node)) is weighted


@pytest.mark.parametrize("sql", [
    "inv(t(X) * X) * t(X) * y",         # the normal equations
    "t(X) * X",                         # a Gram
    "t(X) * y",                         # a leaf on the right
    "t(X) * (X .* X) * p",              # no product of X under the transpose
    "X * (t(X) * y)"])                  # the mirror
def test_the_rule_leaves_other_products_alone(one_device, data, sql):
    sess = session_of(one_device, data, pallas_interpret=True)
    sess.compute(sess.sql(sql))
    said = sess.last_plan()
    assert said["mmchain"] == [] and "pallas_mmchain" not in said["executors"]
    assert all(p["node"] != "mmchain" for p in said["products"])


def test_the_node_checks_its_shapes(one_device, data):
    x = E.leaf(BlockMatrix.from_numpy(data["X"], mesh=one_device))
    p = E.leaf(BlockMatrix.from_numpy(data["p"], mesh=one_device))
    w = E.leaf(BlockMatrix.from_numpy(data["w"], mesh=one_device))
    assert E.mmchain(x, p, w).shape == (K, 1)
    with pytest.raises(ValueError, match="dense leaf"):
        E.mmchain(E.transpose(x), p)
    with pytest.raises(ValueError, match="shape mismatch"):
        E.mmchain(x, w)
    with pytest.raises(ValueError, match="weights"):
        E.mmchain(x, p, p)
    # the chain of two different tables is no chain of one
    other = E.leaf(BlockMatrix.from_numpy(data["X"].copy(), mesh=one_device))
    assert rules.mmchain_product(
        E.matmul(E.transpose(x), E.matmul(other, p))) is None
    assert rules.mmchain_product(
        E.matmul(E.transpose(x), E.matmul(x, p))).kind == "mmchain"


def test_the_node_is_integral_where_its_operands_are(one_device):
    ints = BlockMatrix.from_numpy(np.ones((256, 8), np.float32),
                                  mesh=one_device)
    x = E.leaf(ints)
    v = E.leaf(BlockMatrix.from_numpy(np.ones((8, 1), np.float32),
                                      mesh=one_device))
    node = E.mmchain(x, v)
    assert stats.infer_integral(node) == (
        stats.infer_integral(x) and stats.infer_integral(v))
    bx, bv = stats.integral_abs_bound(x), stats.integral_abs_bound(v)
    want = None if None in (bx, bv) else 256.0 * 8.0 * bx * bx * bv
    assert stats.integral_abs_bound(node) == want


# -- the planner's verdict, by name -------------------------------------------


def test_one_read_where_the_kernel_answers(one_device, data):
    sess = session_of(one_device, data, pallas_interpret=True)
    got = sess.compute(sess.sql("t(X) * (X * p)")).to_numpy()
    said = sess.last_plan()
    assert said["executors"] == ["pallas_mmchain"]
    (rec,) = said["mmchain"]
    assert rec == {"rows": N, "cols": K, "weighted": False,
                   "tile_rows": 2048, "bytes_read": 4 * N * K,
                   "one_read": True}
    (product,) = said["products"]
    assert product["node"] == product["chosen"] == "mmchain"
    assert product["mmchain"] == rec
    # the resident table, the vectors, the kernel's lanes of partial
    # sums and the ragged tail's copy: no second table
    assert 4 * N * K < said["hbm_plan_bytes"] < 4 * N * K * 1.3
    assert rel(got, chain64(data)) < 5e-6


@pytest.mark.parametrize("why,config,spec", [
    ("pallas_off", {}, P(None, None)),
    ("matmul_precision", {"pallas_interpret": True,
                          "matmul_precision": "default"}, P(None, None)),
    ("matmul_precision", {"pallas_interpret": True,
                          "matmul_precision": "high"}, P(None, None)),
    ("precision_sla", {"pallas_interpret": True,
                       "precision_sla": "fast"}, P(None, None)),
    ("strategy_override", {"pallas_interpret": True,
                           "strategy_override": "xla"}, P(None, None))])
def test_a_declined_chain_is_the_two_products_by_name(one_device, data, why,
                                                      config, spec):
    sess = session_of(one_device, data, spec, **config)
    got = sess.compute(sess.sql("t(X) * (w .* (X * p))")).to_numpy()
    said = sess.last_plan()
    (rec,) = said["mmchain"]
    assert rec["one_read"] is False and rec["why_not"] == why
    assert rec["bytes_read"] == 2 * 4 * N * K and rec["tile_rows"] == 0
    assert "pallas_mmchain" not in said["executors"]
    kinds = [p["node"] for p in said["products"]]
    assert kinds == ["matmul", "matmul"]        # X * p, then t(X) * (...)
    assert said["products"][1]["mmchain"] == rec
    assert rel(got, chain64(data, weighted=True)) < (
        5e-2 if "precision" in why else 5e-6)


def test_a_mesh_declines_by_name(mesh_square, data):
    sess = session_of(mesh_square, data, P(("x", "y"), None),
                      pallas_interpret=True)
    got = sess.compute(sess.sql("t(X) * (X * p)")).to_numpy()
    said = sess.last_plan()
    (rec,) = said["mmchain"]
    assert rec["one_read"] is False and rec["why_not"] == "mesh"
    kinds = [p["node"] for p in said["products"]]
    assert kinds.count("matmul") == 2 and "mmchain" not in kinds
    assert rel(got, chain64(data)) < 5e-6


def test_bfloat16_tables_decline_by_name(one_device, data):
    sess = session_of(one_device, data, pallas_interpret=True)
    sess.register("X", BlockMatrix.from_array(
        jnp.asarray(data["X"], jnp.bfloat16), (N, K), one_device,
        P(None, None)))
    sess.compute(sess.sql("t(X) * (X * p)"))
    (rec,) = sess.last_plan()["mmchain"]
    assert rec["one_read"] is False and rec["why_not"] == "dtype"


def test_a_wider_v_declines_by_name(one_device, data):
    sess = session_of(one_device, data, pallas_interpret=True)
    got = sess.compute(sess.sql("t(X) * (X * V)")).to_numpy()
    (rec,) = sess.last_plan()["mmchain"]
    assert rec["one_read"] is False and rec["why_not"] == "v_columns"
    x = data["X"].astype(np.float64)
    assert rel(got, x.T @ (x @ data["V"])) < 5e-6


def test_a_table_the_kernel_does_not_take_declines_by_name(one_device):
    rng = np.random.default_rng(3)
    for shape, why in (((4096, 100), "table_columns"),   # 100 % 8 != 0
                       ((100, 16), "rows")):             # under a lane chunk
        sess = session_of(one_device, {
            "X": rng.uniform(-1, 1, shape).astype(np.float32),
            "p": rng.standard_normal((shape[1], 1)).astype(np.float32)},
            pallas_interpret=True)
        sess.compute(sess.sql("t(X) * (X * p)"))
        (rec,) = sess.last_plan()["mmchain"]
        assert rec["why_not"] == why


def test_an_element_sparse_table_is_no_chain(one_device, data):
    rng = np.random.default_rng(5)
    sess = session_of(one_device, {"p": data["p"]}, pallas_interpret=True)
    sess.register("S", COOMatrix.from_edges(
        rng.integers(0, 300, 900), rng.integers(0, K, 900),
        rng.standard_normal(900).astype(np.float32), shape=(300, K)))
    sess.compute(sess.sql("t(S) * (S * p)"))
    said = sess.last_plan()
    assert said["mmchain"] == []
    assert said["spmm"] or said["densified_products"]


def test_a_table_that_lies_by_rows_declines_on_the_chip(one_device, data,
                                                        monkeypatch):
    """On the chip a table whose long dimension is not on the lanes
    would be copied by the kernel's transpose: the planner asks the
    array how it lies (here the CPU's arrays always lie by rows)."""
    from matrel_tpu import config as config_lib
    monkeypatch.setattr(config_lib, "on_tpu", lambda: True)
    leaf = E.leaf(BlockMatrix.from_numpy(data["X"], mesh=one_device))
    assert planner._lies_by_columns(leaf) is False
    node = E.mmchain(leaf, E.leaf(BlockMatrix.from_numpy(
        data["p"], mesh=one_device)))
    facts = planner.mmchain_plan(node, one_device, MatrelConfig())
    assert facts["why_not"] == "layout" and not facts["one_read"]


# -- node = dense products ----------------------------------------------------


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("n,k,tile", [
    (1000, 104, 256),       # three tiles and a tail of 232 rows
    (4096, 40, 2048),       # whole tiles, no tail
    (300, 8, 256),          # one tile, the narrowest table
    (2555, 1000, 1024),     # the cell's k (no multiple of 128), ragged
    (100, 16, 0)])          # no tile at all: the tail alone
def test_the_kernel_equals_the_dense_products(n, k, tile, weighted):
    rng = np.random.default_rng(n + k)
    x = rng.uniform(-1.0, 1.0, (n, k)).astype(np.float32)
    v = rng.standard_normal((k, 1)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, (n, 1)).astype(np.float32)
    ws = (w,) if weighted else ()
    got = np.asarray(jax.jit(lambda *a: mmchain_lib.mmchain(
        *a, tile=tile, interpret=True))(x, v, *ws))
    x64 = x.astype(np.float64)
    q = x64 @ v
    want = x64.T @ (q * w if weighted else q)
    assert got.shape == (k, 1) and got.dtype == np.float32
    assert rel(got, want) < 2e-6
    # and to float32 rounding the un-fused products' own answer
    q32 = jnp.dot(x, v, precision="highest")
    unfused = np.asarray(jnp.dot(x.T, q32 * w if weighted else q32,
                                 precision="highest"))
    assert rel(got, unfused) < 2e-6


def test_tile_rows_is_a_whole_number_of_lane_chunks():
    assert mmchain_lib.tile_rows(2_555_904) == 2048
    assert mmchain_lib.tile_rows(5000) == 2048
    assert mmchain_lib.tile_rows(1000) == 896
    assert mmchain_lib.tile_rows(127) == 0


def test_the_kernels_body_is_looped():
    """A kernel's traced equations cost every process's set-up (PERF.md
    section 6, PR 52): two loops over the tile's sublane groups, not
    125 unrolled copies."""
    jaxpr = jax.make_jaxpr(lambda x, v: mmchain_lib.mmchain(
        x, v, tile=2048, interpret=True))(
        jnp.zeros((4096, 1000), jnp.float32), jnp.zeros((1000, 1)))
    text = str(jaxpr)
    assert text.count("pallas_call") == 1
    assert len(text.splitlines()) < 1000    # unrolled: 25,000


# -- LinearRegCG through the session ------------------------------------------


CG_SQL = {"p0": "t(X) * y", "r0": "p * neg", "rr": "t(r) * r",
          "q": "t(X) * (X * p) + p * lam", "a": "rr / (t(p) * q)",
          "beta": "beta + p * a", "r": "r + q * a",
          "p": "p * (rr2 / rr) - r"}


def _cg_session(sess, tol=1e-6):
    """LinearRegCG.dml's loop, a statement a line; (beta, rounds,
    the chains' records, plan lookups that compiled after the first
    round)."""
    def step(line, into):
        out = sess.compute(sess.sql(CG_SQL[line]))
        sess.register(into, out)
        return out, sess.last_plan()

    step("p0", "p")
    step("r0", "r")
    rr = float(step("rr", "rr")[0].to_numpy()[0, 0])
    target, rounds, chains, missed = rr * tol ** 2, 0, [], 0
    while rounds < K and rr > target:
        said = [step("q", "q")[1]]
        chains.extend(said[0]["mmchain"])
        said += [step(line, line)[1] for line in ("a", "beta", "r")]
        rr_new, s = step("rr", "rr2")
        said += [s, step("p", "p")[1]]
        sess.register("rr", rr_new)
        rr = float(rr_new.to_numpy()[0, 0])
        missed += rounds > 0 and sum(s["hit"] is False for s in said)
        rounds += 1
    return sess.table("beta").to_numpy(), rounds, chains, missed


def _cg_plain(x, y, lam, tol=1e-6):
    """The same loop in float64 numpy."""
    x, y = x.astype(np.float64), y.astype(np.float64)
    r = -(x.T @ y)
    p = -r
    rr = float(np.sum(r * r))
    target, beta, rounds = rr * tol ** 2, np.zeros_like(r), 0
    while rounds < x.shape[1] and rr > target:
        q = x.T @ (x @ p) + lam * p
        a = rr / float(np.sum(p * q))
        beta, r = beta + a * p, r + a * q
        rr_new = float(np.sum(r * r))
        p, rr = -r + (rr_new / rr) * p, rr_new
        rounds += 1
    return beta, rounds


@pytest.mark.parametrize("seed", [11, 12])
def test_linregcg_through_the_session_equals_the_plain_loop(one_device,
                                                            seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1.0, 1.0, (N, K)).astype(np.float32)
    y = (x @ rng.standard_normal((K, 1)).astype(np.float32)
         + 0.1 * rng.standard_normal((N, 1)).astype(np.float32))
    sess = session_of(one_device, {
        "X": x, "y": y, "lam": np.full((1, 1), 1e-6, np.float32),
        "neg": np.full((1, 1), -1.0, np.float32),
        "beta": np.zeros((K, 1), np.float32)}, pallas_interpret=True)
    beta, rounds, chains, missed = _cg_session(sess)
    want, want_rounds = _cg_plain(x, y, 1e-6)
    assert rounds == want_rounds and 3 <= rounds <= 12
    assert rel(beta, want) < 1e-5
    assert len(chains) == rounds and all(c["one_read"] for c in chains)
    # p, r, beta and the scalars are new arrays every round: templates
    assert missed == 0
    # and it is the least-squares answer CG was stopped short of
    x64 = x.astype(np.float64)
    exact = np.linalg.solve(x64.T @ x64 + 1e-6 * np.eye(K),
                            x64.T @ y.astype(np.float64))
    assert rel(beta, exact) < 1e-4


# -- the spans ----------------------------------------------------------------


def test_a_chain_has_a_span_at_every_dispatch(one_device, data, tmp_path):
    from matrel_tpu.obs import trace as trace_lib
    sess = session_of(one_device, data, pallas_interpret=True)
    expr = sess.sql("t(X) * (X * p)")
    sess.compute(expr)                  # compiled outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    mark = max((r["span_id"] for r in trace_lib.profile_spans()), default=0)
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        sess.compute(expr)
        sess.compute(expr)
    finally:
        jax.profiler.stop_trace()
    spans = [r for r in trace_lib.profile_spans()
             if r["span_id"] > mark and r["name"] == "matrel.mmchain.plan"]
    assert len(spans) == 2
    for r in spans:
        assert r["attrs"]["hit"] is True and r["attrs"]["one_read"] is True
        assert r["attrs"]["rows"] == N and r["attrs"]["cols"] == K
        assert r["attrs"]["tile_rows"] == 2048
        assert r["attrs"]["bytes_read"] == 4 * N * K
        assert r["attrs"]["weighted"] is False


def test_a_declined_chain_has_a_span_that_names_why(one_device, data,
                                                    tmp_path):
    from matrel_tpu.obs import trace as trace_lib
    sess = session_of(one_device, data)             # no Pallas on the CPU
    expr = sess.sql("t(X) * (X * p)")
    sess.compute(expr)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    mark = max((r["span_id"] for r in trace_lib.profile_spans()), default=0)
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        sess.compute(expr)
    finally:
        jax.profiler.stop_trace()
    (span,) = [r for r in trace_lib.profile_spans()
               if r["span_id"] > mark
               and r["name"] == "matrel.mmchain.plan"]
    assert span["attrs"]["one_read"] is False
    assert span["attrs"]["why_not"] == "pallas_off"


# -- the programs it must leave alone -----------------------------------------


def _lowered_hash(sess, sql):
    plan = sess.compile(sess.sql(sql))
    args = [leaf.attrs["matrix"].data for leaf in plan.leaf_order] \
        + list(plan.extra_args)
    text = plan.jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def _zeros(mesh, spec, shapes):
    return {name: BlockMatrix.from_array(jnp.zeros(shape, jnp.float32),
                                         shape, mesh, spec)
            for name, shape in shapes.items()}


REGRESSION = {"X": (139264, 40), "y": (139264, 1)}    # a long contraction
CATALOG = {"M": (512, 512), "N": (512, 512), "A": (1000, 100),
           "B": (100, 1000), "C": (1000, 100)}
PARENTS_PROGRAMS = [
    ("linreg_10m_1c", "inv(t(X) * X) * t(X) * y",
     "1cd6cb6ebca6d76b68611276daf7dda615c63024f65411d1b7a0fe4880c79872"),
    ("linreg_10m_2x2", "inv(t(X) * X) * t(X) * y",
     "462979b15f4ea60076d2243cc3ac8f559b0c4ac7de0dd114719dd94591f50e96"),
    ("relational_small_1c", "rowsum(M * N)",
     "f70e0fa8e6715d1bc72bada78366662bce065fff4ee0ac626807e746c7351dd2"),
    ("relational_small_1c", "rowsum(A * B * C)",
     "cfa3d83e1b23fa75ff45d733b0b6fff89ac74d63b3f82e8102df59ad30720a47"),
    ("relational_small_1c", 'SELECT rowcount(select(M, "v > 0.9")) FROM M',
     "dd5ead5450d21a20b4ea27c8cb9ad8a20b7f5b2e49669fbcc84690a519462d83")]


@pytest.mark.parametrize("cell,sql,want", PARENTS_PROGRAMS,
                         ids=[f"{c}-{i}" for i, (c, _, _)
                              in enumerate(PARENTS_PROGRAMS)])
def test_the_other_dense_cells_lower_to_the_parents_programs(
        cell, sql, want, one_device, mesh_square):
    """Both regression cells' query (on one device and by rows over a
    2 x 2 mesh, a long contraction so that the panelled lowerings run)
    and the dense catalog's three queries lower for the chip to the
    text the parent commit (c7b6c66) lowers them to, by SHA-256
    recorded there in this container's jax, default config: the rule
    does not match them and the planner's new branch is not theirs."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    mesh = mesh_square if cell == "linreg_10m_2x2" else one_device
    spec = P(("x", "y"), None) if cell == "linreg_10m_2x2" \
        else P(None, None)
    sess = MatrelSession(mesh=mesh, config=MatrelConfig())
    shapes = CATALOG if cell == "relational_small_1c" else REGRESSION
    for name, table in _zeros(mesh, spec, shapes).items():
        sess.register(name, table)
    assert _lowered_hash(sess, sql) == want
    assert sess.compile(sess.sql(sql)).meta.get("mmchain") is None
