"""The ``sampled`` node (PR 46): ``S ./ (A * B)`` and ``S .* (A * B)`` for
an element-sparse leaf ``S``, wanted only at S's entries. The rule that
writes it, the two fused products of it through ``session.sql`` +
``compute`` against float64 numpy (a matrix with dense lines and one
without, values that are no bfloat16's), what ``last_plan()`` says, the
array it stays anywhere else, and the gate that refuses that array by
name where it does not fit."""

import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import coo as coo_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix
from matrel_tpu.ir import expr as E, rules
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.parallel.planner import PlanMemoryError
from matrel_tpu.session import MatrelSession

SQL_H = "H .* (t(W) * (V / (W * H))) / t(colsum(W))"
SQL_W = "W .* ((V / (W * H)) * t(H)) / t(rowsum(H))"
USERS, MOVIES, RANK, HOT = 1500, 7000, 16, 128


def _session(config=None):
    """One chip's session: a 1x1 mesh of the first device."""
    import jax
    from matrel_tpu.core import mesh as mesh_lib
    return MatrelSession(
        mesh=mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1]),
        config=config or MatrelConfig())


@pytest.fixture
def one_chip(monkeypatch):
    """What the chip is to a COOMatrix (tests/test_gnmf.py's fixture,
    with room for a slab): the compact Pallas executors of one device,
    interpreted, plans past the small-plan threshold, a gather table of
    500 rows at the most so that a product over the users runs in three
    source panels (one over the movies in fifteen)."""
    cfg = MatrelConfig(pallas_interpret=True)
    was = config_lib._default_config
    config_lib.set_default_config(cfg)
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
    monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 500 * 512)
    yield cfg
    config_lib._default_config = was


def _ratings(rng, hot: bool, exact: bool):
    """A ratings matrix as the rule sees the real one
    (tests/test_gnmf.py's ``_ratings_with_hot_movies``, halved): where
    ``hot``, 128 hot movies of ~125 ratings (a column of 1,500 users
    pays from 6 on) over a tail of 7,000 movies of one or two (a user
    holds 16 of them at the most of most: a row pays from 20 on);
    values 1 to 5 where ``exact``, else float32 numbers that are no
    bfloat16's; one cell listed twice."""
    cols = [np.arange(MOVIES), rng.integers(0, MOVIES, 1_000)]
    if hot:
        cols.append(rng.choice(MOVIES, HOT, replace=False)[
            rng.integers(0, HOT, 16_000)])
    cols = np.concatenate(cols)
    keys = np.unique(rng.integers(0, USERS, cols.size) * MOVIES + cols)
    rows, cols = keys // MOVIES, keys % MOVIES
    vals = (rng.integers(1, 6, rows.size).astype(np.float32) if exact
            else rng.uniform(0.5, 5.0, rows.size).astype(np.float32))
    if not exact:
        rows, cols = np.append(rows, rows[7]), np.append(cols, cols[7])
        vals = np.append(vals, np.float32(1.25))
    order = rng.permutation(rows.size)
    return COOMatrix.from_edges(rows[order], cols[order], vals[order],
                                shape=(USERS, MOVIES))


def _factors(rng, s):
    w = rng.uniform(0.1, 1.0, (USERS, RANK)).astype(np.float32)
    h = rng.uniform(0.1, 1.0, (RANK, MOVIES)).astype(np.float32)
    return w, h, BlockMatrix.from_numpy(w, mesh=s.mesh), \
        BlockMatrix.from_numpy(h, mesh=s.mesh)


# -- the rule -------------------------------------------------------------------


def _leaves(rng, inner=8):
    r = rng.integers(0, 30, 50)
    c = rng.integers(0, 20, 50)
    S = COOMatrix.from_edges(r, c, np.ones(50, np.float32), shape=(30, 20))
    A = BlockMatrix.from_numpy(
        rng.random((30, inner), dtype=np.float32)).expr()
    B = BlockMatrix.from_numpy(
        rng.random((inner, 20), dtype=np.float32)).expr()
    D = BlockMatrix.from_numpy(rng.random((30, 20), dtype=np.float32)).expr()
    return S.expr(), A, B, D


@pytest.mark.parametrize("build,fires", [
    (lambda S, A, B, D: E.elemwise("div", S, E.matmul(A, B)), "div"),
    (lambda S, A, B, D: E.elemwise("mul", S, E.matmul(A, B)), "mul"),
    (lambda S, A, B, D: E.elemwise("mul", E.matmul(A, B), S), "mul"),
    # a dense leaf samples nothing; (A * B) / S is dense (x / 0 = 0
    # everywhere S is not); no other element-wise op has S's structure
    (lambda S, A, B, D: E.elemwise("div", D, E.matmul(A, B)), None),
    (lambda S, A, B, D: E.elemwise("div", E.matmul(A, B), S), None),
    (lambda S, A, B, D: E.elemwise("add", S, E.matmul(A, B)), None),
    # a product of a sparse operand is no dense product
    (lambda S, A, B, D: E.elemwise("mul", S, E.matmul(
        S, E.matmul(E.transpose(B), B))), None),
], ids=["S./(AB)", "S.*(AB)", "(AB).*S", "dense./(AB)", "(AB)./S",
        "S+(AB)", "S.*(S*(tB*B))"])
def test_the_rule_fires_on_what_it_sees(rng, build, fires):
    S, A, B, D = _leaves(rng)
    counts = {}
    out = rules.optimize(build(S, A, B, D), counts=counts)
    if fires is None:
        assert out.kind == "elemwise" and "sampled_product" not in counts
        return
    assert out.kind == "sampled" and out.attrs["op"] == fires
    assert out.children == (S, A, B)
    assert out.shape == S.shape and out.nnz == S.nnz
    assert counts["sampled_product"] == 1


def test_the_rule_leaves_a_product_wider_than_the_tables(rng):
    S, A, B, _ = _leaves(rng, inner=E.COO_NARROW_MAX + 1)
    out = rules.optimize(E.elemwise("div", S, E.matmul(A, B)))
    assert out.kind == "elemwise"
    S, A, B, _ = _leaves(rng, inner=E.COO_NARROW_MAX)
    assert rules.optimize(
        E.elemwise("div", S, E.matmul(A, B))).kind == "sampled"


# -- the fused products -------------------------------------------------------------


@pytest.mark.parametrize("orientation", ["transposed", "forward"])
@pytest.mark.parametrize("hot,exact", [(True, True), (True, False),
                                       (False, False)],
                         ids=["bfloat16-slab", "float32-slab", "no-slab"])
def test_both_products_match_float64(rng, one_chip, hot, exact,
                                     orientation):
    """t(W) * (V ./ (W H)) and (V ./ (W H)) * t(H) inside the two
    updates, against float64 numpy, entry by entry. rtol 5e-6: the program's sums are
    float32 (an entry's dot of 16 terms, a hot movie's 125 quotients
    added on the MXU at ``highest``), the quotient itself a float32
    division and two more by the sums of the update: a dozen roundings
    of 6e-8 an entry, read 2.1e-6 at the worst of 112,000 entries."""
    V = _ratings(rng, hot, exact)
    s = _session(one_chip)
    w, h, W, H = _factors(rng, s)
    s.register("V", V)
    s.register("W", W)
    s.register("H", H)
    Vd = V.to_dense().astype(np.float64)
    Q = np.where(Vd != 0, Vd / (w.astype(np.float64) @ h), 0.0)
    sql, want = {
        "transposed": (SQL_H, h * (w.T @ Q) / w.sum(0)[:, None]),
        "forward": (SQL_W, w * (Q @ h.T) / h.sum(1)[None, :])}[orientation]
    got = s.compute(s.sql(sql)).to_numpy()
    np.testing.assert_allclose(got, want, rtol=5e-6)
    said = s.last_plan()
    assert said["densified_products"] == [] and said["spmm"] == []
    assert said["executors"] == ["pallas_spmv"]
    (rec,) = said["sampled"]
    assert (rec["orientation"], rec["op"]) == (orientation, "div")
    assert rec["k"] == rec["inner"] == RANK
    assert rec["entries"] == V.nnz and rec["overflow_edges"] == 0
    # W is the product's dense side and the factor whose rows the
    # transposed plan's sources name (t(H) in the forward one); the
    # destination's rows and the dot are the kernel's (PR 47)
    assert rec["shared_gather"] is True and rec["dot"] == "kernel"
    assert rec["source_panels"] == (4 if orientation == "transposed"
                                    else 15)
    # a panel of table rows each source panel: no split for a window
    assert rec["panels"] == rec["source_panels"]
    if hot:
        # the dense lines through matrel_sampled_lines, 512 slab rows a
        # step (PR 57)
        assert rec["lines"] == HOT and rec["panel_rows"] == 512
        assert rec["lines_by"] == "kernel" and "lines_why_not" not in rec
        assert 0 < rec["dense_entries"] < V.nnz
        assert rec["slab_dtype"] == ("bfloat16" if exact
                                     else "float32")
    else:
        assert (rec["lines"], rec["dense_entries"], rec["slab_dtype"],
                rec["panel_rows"], rec["lines_by"]) == (0, 0, "", 0, "")
    assert 0 < rec["hbm_plan_bytes"] <= said["hbm_plan_bytes"]


@pytest.mark.parametrize("sql,shared", [
    ("X * (V .* (W * H))", False), ("((W * H) .* V) * Y", False),
    ("((W * H) .* V) * t(H)", True)],
    ids=["side-before", "side-after", "side-a-factor"])
def test_the_sampled_product_by_mul_and_a_side_that_is_no_factor(
        rng, one_chip, sql, shared):
    """``.*`` either way round, and a dense side that is not one of the
    product's factors (its rows are gathered beside the factor's: the
    kernel's second rows operand)."""
    V = _ratings(rng, hot=True, exact=False)
    s = _session(one_chip)
    w, h, W, H = _factors(rng, s)
    x = rng.uniform(-1, 1, (5, USERS)).astype(np.float32)
    y = rng.uniform(-1, 1, (MOVIES, 3)).astype(np.float32)
    for name, m in (("V", V), ("W", W), ("H", H),
                    ("X", BlockMatrix.from_numpy(x, mesh=s.mesh)),
                    ("Y", BlockMatrix.from_numpy(y, mesh=s.mesh))):
        s.register(name, m)
    P = V.to_dense().astype(np.float64) * (w.astype(np.float64) @ h)
    want = {"X * (V .* (W * H))": x @ P, "((W * H) .* V) * Y": P @ y,
            "((W * H) .* V) * t(H)": P @ h.T}[sql]
    got = s.compute(s.sql(sql)).to_numpy()
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    (rec,) = s.last_plan()["sampled"]
    assert rec["op"] == "mul" and rec["shared_gather"] is shared
    assert rec["dot"] == "kernel"
    assert s.last_plan()["densified_products"] == []


def test_a_zero_denominator_gives_zero(rng, one_chip):
    """x / 0 = 0, as the element-wise div gives it: a movie whose column
    of H is zero has W H = 0 at every one of its entries."""
    V = _ratings(rng, hot=True, exact=False)
    s = _session(one_chip)
    w, h, W, H = _factors(rng, s)
    dense = V._get_wide_plan().dense
    tail = np.flatnonzero(dense.column_of < 0)[0]
    h[:, [dense.lines[0], tail]] = 0.0  # a dense line and one of the tail
    s.register("V", V)
    s.register("W", W)
    s.register("H", BlockMatrix.from_numpy(h, mesh=s.mesh))
    got = s.compute(s.sql("(V / (W * H)) * t(H)")).to_numpy()
    Vd = V.to_dense().astype(np.float64)
    D = w.astype(np.float64) @ h
    Q = np.where(D != 0, Vd / np.where(D != 0, D, 1.0), 0.0)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, Q @ h.T, rtol=5e-6)


def test_a_blocks_layout_plan_with_an_overflow_tail(rng, monkeypatch):
    """The layout a mesh's host gives a COOMatrix (``blocks``, one heavy
    row past the capacity in the scalar overflow list): the same
    answer, the overflow entries sampled like the rest."""
    cfg = MatrelConfig(pallas_interpret=True)
    was = config_lib._default_config
    config_lib.set_default_config(cfg)
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "blocks")
    try:
        m = 20_000
        r = np.where(rng.random(m) < 0.3, 7, rng.integers(0, 2048, m))
        c = rng.integers(0, 512, m)
        V = COOMatrix.from_edges(r, c, rng.uniform(0.5, 2, m), shape=(2048,
                                                                      512))
        assert V._get_wide_plan().ov_rows is not None
        s = _session(cfg)
        w = rng.uniform(0.1, 1, (2048, 8)).astype(np.float32)
        h = rng.uniform(0.1, 1, (8, 512)).astype(np.float32)
        s.register("V", V)
        s.register("W", BlockMatrix.from_numpy(w, mesh=s.mesh))
        s.register("H", BlockMatrix.from_numpy(h, mesh=s.mesh))
        got = s.compute(s.sql("(V / (W * H)) * t(H)")).to_numpy()
        (rec,) = s.last_plan()["sampled"]
        assert rec["layout"] == "blocks" and rec["overflow_edges"] > 0
        Vd = V.to_dense().astype(np.float64)
        np.testing.assert_allclose(
            got, (Vd / (w.astype(np.float64) @ h)) @ h.T, rtol=3e-6)
    finally:
        config_lib._default_config = was


# -- the kernel (PR 47) --------------------------------------------------------------


def _kernel_plan(name, rng, powers_of_two=False):
    """(plan, rows, cols, vals) of a matrix of 300 columns whose chunks
    are what ``name`` says: ``windows`` — 39 entries a row, a chunk of
    2,048 slots in row order names ~52 rows, every chunk takes a
    128-row window; ``whole`` — 1,500 entries a block of 512 rows, one
    chunk that spans it; ``both`` — a block of each kind and one with
    both; ``short-block`` — blocks of 64 rows, shorter than a window."""
    from matrel_tpu.ops import pallas_spmv as pc
    n_rows, n_cols, block = 1536, 300, 512
    if name == "windows":
        rows = rng.integers(0, n_rows, 60_000)
    elif name == "whole":
        rows = rng.integers(0, n_rows, 4_500)
    elif name == "both":
        rows = np.concatenate([
            rng.integers(0, 512, 20_000), rng.integers(512, 1024, 1_500),
            1024 + rng.integers(0, 60, 4_000),
            rng.integers(1024, 1536, 1_500)])
    else:
        n_rows, block = 320, 64
        rows = rng.integers(0, n_rows, 6_000)
    rows = rng.permutation(rows)
    cols = rng.integers(0, n_cols, rows.size)
    vals = (2.0 ** rng.integers(0, 3, rows.size) if powers_of_two
            else rng.uniform(0.5, 5.0, rows.size)).astype(np.float32)
    plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                    block=block, layout="chunks", hubs=False)
    win = np.asarray(pc.wide_windows(plan)[0][0])
    assert plan.overflow == () and {
        "windows": (win >= 0).all(), "whole": (win < 0).all(),
        "both": sorted(set(win[plan.chunk_block == 2] >= 0)) == [False, True],
        "short-block": (win < 0).all()}[name], win
    return plan, rows, cols, vals


def _sampled_oracle(rows, cols, vals, op, src, dst, Z, n_rows):
    """float64: sum over the entries of ``(val op <src[col], dst[row]>)
    * Z[col]`` by row, ``x / 0 = 0``."""
    d = np.einsum("ek,ek->e", src.astype(np.float64)[cols],
                  dst.astype(np.float64)[rows])
    v = vals.astype(np.float64)
    q = v * d if op == "mul" else np.where(
        d != 0, v / np.where(d != 0, d, 1.0), 0.0)
    want = np.zeros((n_rows, Z.shape[1]))
    np.add.at(want, rows, q[:, None] * Z.astype(np.float64)[cols])
    return want


def _sampled_kernel(plan, Z, op, of_src, of_dst, passes=3):
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    static, statics, arrays = pc.plan_operands(plan)
    return np.asarray(pc.sampled_matmat_parts(
        static, statics, arrays, jnp.asarray(Z), op,
        None if of_src is None else jnp.asarray(of_src),
        jnp.asarray(of_dst), passes=passes, interpret=True))


@pytest.mark.parametrize("op", ["div", "mul"])
@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared", "second-rows-operand"])
@pytest.mark.parametrize("name", ["windows", "whole", "both", "short-block"])
def test_the_sampled_kernel_matches_float64(rng, name, shared, op):
    """``matrel_sampled_scatter_chunks`` interpreted, on chunks that
    take a window, chunks that take the block, both inside one block
    and a block shorter than a window; the source rows the scatter's
    own or a second operand; ``./`` and ``.*``; some destination rows
    ZERO (a zero denominator gives 0) and every block's last chunk
    padded (a padded slot gives 0, not 0 / 0): against float64 at the
    2e-6 of max |want| the file holds a product to."""
    plan, rows, cols, vals = _kernel_plan(name, rng)
    k, inner = 24, 16
    src = rng.uniform(0.1, 1.0, (plan.n_cols, inner)).astype(np.float32)
    dst = rng.uniform(0.1, 1.0, (plan.n_rows, inner)).astype(np.float32)
    dst[rng.integers(0, plan.n_rows, 40)] = 0.0
    Z = src if shared else rng.uniform(-1, 1, (plan.n_cols, k)).astype(
        np.float32)
    got = _sampled_kernel(plan, Z, op, None if shared else src, dst)
    want = _sampled_oracle(rows, cols, vals, op, src, dst, Z, plan.n_rows)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    empty = np.setdiff1d(np.arange(plan.n_rows), rows)
    assert not got[empty].any()


def _lines_case(rng, role, dtype, rows, count, width=256, others=400):
    """A slab of ``rows`` x ``width`` cells, a third of them entries
    (1 to 5 in bfloat16, no bfloat16's in float32), ``count`` lines of
    ``others`` and zero columns past them; the factors and the dense
    side 128 lanes wide, the row of ``R`` that the first line names ZERO
    (a zero denominator down a whole column of the slab)."""
    import jax.numpy as jnp
    cells = rng.integers(1, 6, (rows, width)).astype(np.float32)
    if dtype == "float32":
        cells += rng.uniform(0.001, 0.4, cells.shape).astype(np.float32)
    cells *= rng.random(cells.shape) < 0.35
    cells[:, count:] = 0.0
    lines = np.sort(rng.choice(others, count, replace=False)).astype(np.int32)
    P = rng.uniform(0.1, 1.0, (rows, 128)).astype(np.float32)
    R = rng.uniform(0.1, 1.0, (others, 128)).astype(np.float32)
    R[lines[0]] = 0.0
    n_z, n_y = (others, rows) if role == "sources" else (rows, others)
    Z = rng.uniform(-1, 1, (n_z, 128)).astype(np.float32)
    Y = rng.uniform(-1, 1, (n_y, 128)).astype(np.float32)
    return (jnp.asarray(cells, dtype), jnp.asarray(lines),
            *(jnp.asarray(a) for a in (P, R, Z, Y)))


@pytest.mark.parametrize("count", [256, 200], ids=["whole-groups", "padded"])
@pytest.mark.parametrize("rows", [256, 300, 70],
                         ids=["whole-tiles", "ragged-tail", "under-a-tile"])
@pytest.mark.parametrize("op", ["div", "mul"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("role", ["sources", "destinations"])
def test_the_lines_kernel_matches_float64_and_the_loop(rng, role, dtype, op,
                                                       rows, count):
    """``matrel_sampled_lines`` interpreted at a row tile of 128 (two
    whole steps; two and a ragged third, whose rows past the slab's end
    the kernel masks; one step taller than the slab), either role, either
    slab, ``./`` and ``.*``, the lines a whole number of lane groups or
    padded to one: against float64 at the 2e-6 of max |want| the file
    holds a product to, and against XLA's loop of panels, the path it
    replaces, at the same."""
    import jax
    from matrel_tpu.ops import pallas_spmv as pc, sampled_lines
    slab, lines, P, R, Z, Y = _lines_case(rng, role, dtype, rows, count)
    got = np.asarray(jax.jit(lambda y: sampled_lines.sampled_lines(
        y, role, slab, lines, Z, op, P, R, tile=128, interpret=True))(Y))
    of_src, of_dst = (R, P) if role == "sources" else (P, R)
    loop = np.asarray(pc._sampled_lines_xla(Y, role, slab, lines, Z, op,
                                            of_src, of_dst))
    s, p, r, z = (np.asarray(a).astype(np.float64)
                  for a in (slab[:, :count], P, R[lines], Z))
    d = p @ r.T
    q = s * d if op == "mul" else np.where(
        (s != 0) & (d != 0), s / np.where(d != 0, d, 1.0), 0.0)
    want = np.asarray(Y).astype(np.float64)
    if role == "sources":
        want = want + q @ z[np.asarray(lines)]
    else:
        want[np.asarray(lines)] += q.T @ z
    assert got.shape == want.shape and np.all(np.isfinite(got))
    for other in (want, loop):
        assert np.max(np.abs(got - other)) / np.max(np.abs(want)) < 2e-6
    if role == "destinations":      # the lines it does not name: untouched
        rest = np.setdiff1d(np.arange(Y.shape[0]), np.asarray(lines))
        np.testing.assert_array_equal(got[rest], np.asarray(Y)[rest])


def test_who_multiplies_the_lines_is_said_and_the_loop_still_answers(
        rng, one_chip, monkeypatch):
    """``sampled_lines.plan`` from the slab's shape alone: the kernel at
    the tallest row tile that fits ``VMEM_LIMIT`` at the slab's width, a
    shorter one where that does not, XLA's loop with ``lines_why_not``
    where none does — and a session whose slab no tile fits says so in
    ``last_plan()`` and answers the product through the loop, to the
    same float64."""
    from matrel_tpu.ops import sampled_lines
    assert sampled_lines.plan(4_224, 2) == {
        "lines_by": "kernel", "panel_rows": 512}
    assert sampled_lines.plan(9_600, 4)["panel_rows"] == 128
    assert sampled_lines.plan(9_600, 2)["panel_rows"] == 256
    assert sampled_lines.plan(40_960, 2) == {
        "lines_by": "xla", "lines_why_not": "vmem", "panel_rows": 8192}
    V = _ratings(rng, hot=True, exact=True)
    s = _session(one_chip)
    w, h, W, H = _factors(rng, s)
    for name, m in (("V", V), ("W", W), ("H", H)):
        s.register(name, m)
    with monkeypatch.context() as tight:
        tight.setattr(sampled_lines, "VMEM_LIMIT", 1 << 19)
        got = s.compute(s.sql("(V / (W * H)) * t(H)")).to_numpy()
    (rec,) = s.last_plan()["sampled"]
    assert (rec["lines_by"], rec["lines_why_not"], rec["panel_rows"]) == (
        "xla", "vmem", 8192)
    kernel = coo_lib.sampled_facts(V._get_wide_plan(), V.nnz, True)
    assert kernel["lines_by"] == "kernel"
    # the loop keeps a panel's float32 cells, dot and quotient beside
    # the slab; the kernel nothing
    assert rec["hbm_plan_bytes"] - kernel["hbm_plan_bytes"] == \
        3 * 4 * 8192 * kernel["lines"]
    Vd = V.to_dense().astype(np.float64)
    want = (Vd / (w.astype(np.float64) @ h)) @ h.T
    np.testing.assert_allclose(got, want, rtol=5e-6)


@pytest.mark.parametrize("orientation", ["transposed", "forward"])
def test_a_window_as_tall_as_the_chunk_needs(rng, one_chip, monkeypatch,
                                             ladder_cells, orientation):
    """The ladder (PR 49) under both sampled products: over a matrix
    whose chunks take a 128-row window, a 256-row one and the whole
    block in both orientations, the kernel gives what it gives with the
    windows withheld (every chunk the whole block's one-hot, twice),
    bit for bit, and ``last_plan()["sampled"]`` says how many chunks
    take which height."""
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    # the residual alone, in one source panel: the lines of 400 entries
    # would pay as dense lines, a table of 500 rows would cut them up
    monkeypatch.setattr(coo_lib, "_DENSE_SHARE", 0.0)
    monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 4096 * 512)
    rows, cols = ladder_cells
    n = 2048
    V = COOMatrix.from_edges(
        rows, cols, rng.uniform(0.5, 5.0, rows.size).astype(np.float32),
        shape=(n, n))
    s = _session(one_chip)
    w = rng.uniform(0.1, 1.0, (n, RANK)).astype(np.float32)
    h = rng.uniform(0.1, 1.0, (RANK, n)).astype(np.float32)
    s.register("V", V)
    s.register("W", BlockMatrix.from_numpy(w, mesh=s.mesh))
    s.register("H", BlockMatrix.from_numpy(h, mesh=s.mesh))
    flipped = orientation == "transposed"
    got = s.compute(s.sql(SQL_H if flipped else SQL_W)).to_numpy()
    (rec,) = s.last_plan()["sampled"]
    assert rec["orientation"] == orientation and rec["dense_entries"] == 0
    assert rec["source_panels"] == 1 and rec["layout"] == "chunks"
    assert sorted(rec["window_rows"]) == ["128", "256"]
    assert rec["window_rows"]["256"] == 1 and rec["window_rows"]["128"] > 8
    assert rec["windowed_chunks"] == sum(rec["window_rows"].values()) \
        == rec["chunks"] - 1
    Vd = V.to_dense().astype(np.float64)
    Q = np.where(Vd != 0, Vd / (w.astype(np.float64) @ h), 0.0)
    want = (h * (w.T @ Q) / w.sum(0)[:, None] if flipped
            else w * (Q @ h.T) / h.sum(1)[None, :])
    np.testing.assert_allclose(got, want, rtol=5e-6)
    # the product itself, with its windows and without
    plan = V._get_wide_plan(transposed=flipped)
    static, statics, arrays = pc.plan_operands(plan)
    src, dst = (w, h.T) if flipped else (h.T, w)

    def product(arrays):
        return np.asarray(pc.sampled_matmat_parts(
            static, statics, arrays, jnp.asarray(src), "div", None,
            jnp.asarray(dst), interpret=True))

    assert all(wins is not None for _, _, wins in arrays)
    np.testing.assert_array_equal(product(arrays), product(tuple(
        (tables, ov, None) for tables, ov, _ in arrays)))


@pytest.mark.parametrize("passes,lo,hi", [(3, 0.0, 2e-6), (2, 2e-6, 3e-4),
                                          (1, 3e-4, 3e-2)])
def test_passes_change_the_scatter_and_not_the_dot(rng, passes, lo, hi):
    """``passes`` are the bfloat16 parts of the scatter's contributions
    (PERF.md section 2's controls read them so): fewer of them move a
    product of arbitrary values by what a part holds. The entry's dot
    takes the destination's rows in THREE parts whatever ``passes``
    says: rows (131,329, 257) that need all three (2^17 + 2^8 + 1)
    against sources (1, -1) give the dot 2^17 exactly, and with values
    and a scattered side that are small powers of two every
    contribution fits ONE part, so the answer is exact at every
    ``passes`` (a dot from two parts would read 131,071, from one
    130,816)."""
    plan, rows, cols, vals = _kernel_plan("both", rng)
    src = rng.uniform(0.1, 1.0, (plan.n_cols, 16)).astype(np.float32)
    dst = rng.uniform(0.1, 1.0, (plan.n_rows, 16)).astype(np.float32)
    got = _sampled_kernel(plan, src, "div", None, dst, passes)
    want = _sampled_oracle(rows, cols, vals, "div", src, dst, src,
                           plan.n_rows)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert lo <= err < hi, err
    # the exact case, through the second rows operand
    plan, rows, cols, vals = _kernel_plan("both", rng, powers_of_two=True)
    ones = np.zeros((plan.n_cols, 2), np.float32)
    ones[:, 0], ones[:, 1] = 1.0, -1.0
    tall = np.zeros((plan.n_rows, 2), np.float32)
    tall[:, 0], tall[:, 1] = 131329.0, 257.0
    twos = (2.0 ** rng.integers(0, 3, (plan.n_cols, 8))).astype(np.float32)
    exact = _sampled_kernel(plan, twos, "mul", ones, tall, passes)
    np.testing.assert_array_equal(exact, _sampled_oracle(
        rows, cols, vals, "mul", ones, tall, twos, plan.n_rows))


def test_a_plain_product_lowers_to_the_recorded_program(rng):
    """The GNMF guard: ``coo_leaf x dense`` through
    ``compact_matmat_parts`` lowers for the chip to a recorded text —
    the Mosaic kernel's serialized body (which embeds paths and line
    numbers) read back and printed without debug info — by its SHA-256,
    recorded in this container's jax: the sampled kernel is a second
    ``pallas_call``, and nothing of it is in a plain product's program.
    Recorded anew by PR 49, which gave the plain kernel its 256-row
    body on purpose (PR 47's record: 6a25b79a...ae268c5); a PR that
    means to leave this product alone leaves the hash alone."""
    import base64
    import hashlib
    import re
    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from matrel_tpu.ops import pallas_spmv as pc
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    fixed = np.random.default_rng(7)
    rows = np.concatenate([fixed.integers(0, 512, 30_000),
                           fixed.integers(512, 1024, 1_500)])
    cols = fixed.integers(0, 300, rows.size)
    plan = spmv_lib.build_spmv_plan(
        rows, cols, fixed.standard_normal(rows.size).astype(np.float32),
        1024, 300, layout="chunks", hubs=False)
    static, statics, arrays = pc.plan_operands(plan)
    text = jax.jit(lambda pa, x: pc.compact_matmat_parts(
        static, statics, pa, x, 3, False)).trace(
        arrays, jax.ShapeDtypeStruct((300, 128), jnp.float32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("matrel_spmm_scatter_chunks") == 1
    assert "matrel_sampled" not in text

    def body(m):
        with mlir.make_ir_context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return mod.operation.get_asm(enable_debug_info=False)

    text, n = re.subn(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body,
                      text)
    assert n == 1
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "63f5fe2df950e45070a30e40e93db5bc"
        "d183c43629c6f0ffca80baab6967e394")


# -- anywhere else it is the array it was ----------------------------------------------


def test_alone_or_beside_a_wide_side_it_is_densified_and_says_so(
        rng, one_chip):
    V = _ratings(rng, hot=False, exact=False)
    s = _session(one_chip)
    w, h, W, H = _factors(rng, s)
    wide = rng.uniform(-1, 1, (MOVIES, 130)).astype(np.float32)
    for name, m in (("V", V), ("W", W), ("H", H),
                    ("Y", BlockMatrix.from_numpy(wide, mesh=s.mesh))):
        s.register(name, m)
    Vd = V.to_dense().astype(np.float64)
    Q = Vd / (w.astype(np.float64) @ h)
    fell = [{"shape": [USERS, MOVIES], "entries": V.nnz,
             "bytes": 4 * USERS * MOVIES}]
    for sql, want in (("V / (W * H)", Q), ("(V / (W * H)) * Y", Q @ wide),
                      ("rowsum(V / (W * H))", Q.sum(1, keepdims=True))):
        got = s.compute(s.sql(sql)).to_numpy()
        assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 3e-6
        said = s.last_plan()
        assert said["sampled"] == [] and said["densified_products"] == fell
    # the sampled node is in the plan all the same: the rule fires by
    # what it sees, the lowering decides
    assert "sampled" in s.sql("V / (W * H)").optimized().kind


def test_off_the_chips_executor_the_product_is_the_dense_one(rng):
    """No compact-table executor (a default CPU session; a mesh): the
    product of a sampled node is today's answer, the leaf densified."""
    V = _ratings(rng, hot=False, exact=False)
    s = _session()
    w, h, W, H = _factors(rng, s)
    s.register("V", V)
    s.register("W", W)
    s.register("H", H)
    got = s.compute(s.sql("(V / (W * H)) * t(H)")).to_numpy()
    Vd = V.to_dense().astype(np.float64)
    want = (Vd / (w.astype(np.float64) @ h)) @ h.T
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 3e-6
    said = s.last_plan()
    assert said["sampled"] == [] and len(said["densified_products"]) == 1


def test_the_gnmf_updates_say_what_they_said(rng, one_chip):
    """The Euclidean updates hold no sampled node: their plans list
    their coo_leaf products under ``spmm`` as before and nothing under
    ``sampled``."""
    V = _ratings(rng, hot=True, exact=True)
    s = _session(one_chip)
    _, _, W, H = _factors(rng, s)
    s.register("V", V)
    s.register("W", W)
    s.register("H", H)
    for sql, orientation in (("H .* (t(W) * V) / (t(W) * W * H)",
                              "transposed"),
                             ("W .* (V * t(H)) / (W * H * t(H))",
                              "forward")):
        s.compute(s.sql(sql))
        said = s.last_plan()
        assert said["sampled"] == [] and said["densified_products"] == []
        (rec,) = said["spmm"]
        assert rec["orientation"] == orientation
        assert rec["dense_lines"] == HOT and rec["dense_dtype"] == "bfloat16"
        assert "sampled_product" not in s.sql(sql).optimized().kind


# -- the gate -------------------------------------------------------------------------


def test_the_unfused_quotient_is_refused_by_name_before_anything_is_made(
        rng, one_chip, monkeypatch):
    """``V / (W * H)`` alone at a size that does not fit: refused at
    once, by name, with nothing densified and nothing multiplied; under
    its product the same matrices are answered."""
    import dataclasses
    cfg = dataclasses.replace(one_chip, hbm_budget_bytes=2 * 4 * USERS
                              * MOVIES)
    V = _ratings(rng, hot=False, exact=False)
    monkeypatch.setattr(COOMatrix, "to_block", lambda *a, **k: pytest.fail(
        "the leaf was densified"))
    s = _session(cfg)
    _, _, W, H = _factors(rng, s)
    s.register("V", V)
    s.register("W", W)
    s.register("H", H)
    with pytest.raises(PlanMemoryError, match="the sampled node "
                       rf"{USERS}x{MOVIES} ./ .*would\s+DENSIFY"):
        s.compute(s.sql("V / (W * H)"))
    with pytest.raises(PlanMemoryError, match="the sampled node"):
        s.compute(s.sql("rowsum(V / (W * H))"))
    out = s.compute(s.sql(SQL_W))
    assert out.shape == (USERS, RANK)
    assert s.last_plan()["hbm_plan_bytes"] < cfg.hbm_budget_bytes
