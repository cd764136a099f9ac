"""The ``semiring`` node (PR 50): ``rowmax`` / ``rowmin`` over a column
join with merge "mul" of an element-sparse leaf and one row, answered as
ONE (max | min, ×) product from the leaf's entries. The rule that writes
it, the product against the program's own dense lowering of the same
query (negative values, empty rows, a full row, skewed degrees, a
repeated cell), the chunk grid's reduction kernel against XLA's segment
reduction, weakly connected components through ``session.sql`` +
``compute`` against scipy, what ``last_plan()`` says, the cap an
un-matched join still meets, and the programs it must leave alone.
Since PR 51 also over plans WITH hub chunks (``matrel_spmv_reduce_hubs``:
the slots of skewed sources take their value from the hub table)."""

import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import coo as coo_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix
from matrel_tpu.ir import expr as E, rules
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.relational import ops as R
from matrel_tpu.session import MatrelSession

ROUND_SQL = 'elemmax(L, rowmax(joincols(A, t(L), "mul")))'


def _session(config=None):
    """One chip's session: a 1x1 mesh of the first device."""
    import jax
    from matrel_tpu.core import mesh as mesh_lib
    return MatrelSession(
        mesh=mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1]),
        config=config or MatrelConfig())


@pytest.fixture
def one_chip(monkeypatch):
    """What the chip is to a COOMatrix (tests/test_sampled.py's
    fixture): the compact Pallas executors of one device, interpreted,
    and plans in chunks whatever their size — without hub chunks (at
    this scale the rule would make every source a hub; the cases that
    want some ask :func:`_with_hubs`)."""
    cfg = MatrelConfig(pallas_interpret=True)
    was = config_lib._default_config
    config_lib.set_default_config(cfg)
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 0)
    yield cfg
    config_lib._default_config = was


HUB_KINDS = ("hubs", "hubs-one-step")


def _with_hubs(monkeypatch, kind):
    """The build's rule as it takes rows at this scale where ``kind`` is
    one of ``HUB_KINDS``: a hub table of 12 rows walked in steps of 8
    (two steps tall), or of 2 rows in the code's own step of 64,
    every row taken that holds a source."""
    if kind not in HUB_KINDS:
        return
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    if kind == "hubs":
        monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 12)
        monkeypatch.setattr(spmv_lib, "HUB_WALK", 8)
    else:
        monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 2)


# -- the rule -------------------------------------------------------------------


def _operands(rng, n=30, m=20):
    r, c = rng.integers(0, n, 50), rng.integers(0, m, 50)
    S = COOMatrix.from_edges(r, c, np.ones(50, np.float32), shape=(n, m))

    def dense(shape):
        return BlockMatrix.from_numpy(
            rng.random(shape, dtype=np.float32)).expr()

    return S.expr(), dense((m, 1)), dense((n, 1)), dense((n, m)), \
        dense((2, m))


def _join(S, b, merge="mul"):
    return R.join_on_cols(S, b, merge)


@pytest.mark.parametrize("build,fires", [
    (lambda S, x, y, D, B2: E.agg(_join(S, x.t()), "max", "row"), "max"),
    (lambda S, x, y, D, B2: E.agg(_join(S, x.t()), "min", "row"), "min"),
    (lambda S, x, y, D, B2: E.elemwise(
        "max", y, E.agg(_join(S, x.t()), "max", "row")), "max"),
    (lambda S, x, y, D, B2: E.elemwise(
        "min", y, E.agg(_join(S, x.t()), "min", "row")), "min"),
    # the merge commutes: the row may come first
    (lambda S, x, y, D, B2: E.agg(_join(x.t(), S), "max", "row"), "max"),
    # a dense leaf is joined as it always was
    (lambda S, x, y, D, B2: E.agg(_join(D, x.t()), "max", "row"), None),
    # another structured merge, and a callable, are no product
    (lambda S, x, y, D, B2: E.agg(_join(S, x.t(), "add"), "max", "row"),
     None),
    (lambda S, x, y, D, B2: E.agg(
        _join(S, x.t(), lambda a, b: a * b), "max", "row"), None),
    # two rows joined: a (2n x m) matrix, no product with a column
    (lambda S, x, y, D, B2: E.agg(_join(S, B2), "max", "row"), None),
    # the extremum along the other axis, and a sum, stay aggregates
    (lambda S, x, y, D, B2: E.agg(_join(S, x.t()), "max", "col"), None),
    (lambda S, x, y, D, B2: E.agg(_join(S, x.t()), "sum", "row"), None),
], ids=["rowmax", "rowmin", "elemmax(rowmax)", "elemmin(rowmin)",
        "row-first", "dense-leaf", "merge-add", "merge-callable",
        "two-rows", "colmax", "rowsum"])
def test_the_rule_fires_on_what_it_sees(rng, build, fires):
    S, x, y, D, B2 = _operands(rng)
    counts = {}
    out = rules.optimize(build(S, x, y, D, B2), counts=counts)
    node = out if out.kind != "elemwise" else out.children[1]
    if fires is None:
        assert "semiring_product" not in counts
        assert node.kind == "agg"
        return
    assert counts["semiring_product"] == 1
    assert node.kind == "semiring" and node.attrs["reduce"] == fires
    assert node.shape == (30, 1) and node.nnz is None
    leaf, col = node.children
    assert leaf.kind == "coo_leaf" and col.shape == (20, 1)
    assert "(" + fires in E.pretty(out)


def test_the_node_refuses_what_it_cannot_mean(rng):
    S, x, y, D, B2 = _operands(rng)
    with pytest.raises(ValueError, match="unknown semiring reduction"):
        E.semiring("sum", S, x)
    with pytest.raises(ValueError, match="coo_leaf"):
        E.semiring("max", D, x)
    with pytest.raises(ValueError, match="shape mismatch"):
        E.semiring("max", S, y)


# -- fused = the dense lowering ------------------------------------------------


def _hub_matrix(rng, kind):
    """(rows, cols, n, m) of a matrix whose SOURCES are skewed, for a
    plan with hub chunks (``_with_hubs``: the table holds the 1,536 — or
    256 — columns of most entries, which are the first, two hundred of
    them heavier still): four blocks of rows. Block 0 holds most
    entries, from hubs and others alike, so its hub slots fill several
    registers whose runs of table rows differ, and most of its rows hold
    entries of both sets; its rows 3 to 40 only hubs reach, row 41 only
    others. Block 1 holds 300 hub entries (one register of its one hub
    chunk is all padding) and none other. Block 2 holds no hub entry, so
    it owns no hub chunk, and block 3 three entries, of rows 0, 0 and 5
    (its chunk's padding lies behind them in one row of 128 slots)."""
    hubs, least = (1536, 8) if kind == "hubs" else (256, 40)
    others = 2_500
    n, m = 512 * 3 + 90, hubs + others
    heavy = np.arange(hubs) < 200
    of_hubs = np.repeat(np.arange(hubs),
                        least + heavy * rng.integers(5, 30, hubs))
    of_others = np.repeat(hubs + np.arange(others),
                          rng.integers(1, 4, others))
    rng.shuffle(of_hubs), rng.shuffle(of_others)
    to_hubs = rng.integers(0, 512, of_hubs.size)
    to_hubs[to_hubs == 41] = 42
    to_hubs[:300] += 512                                  # block 1
    to_others = rng.integers(0, 512, of_others.size)
    to_others[(to_others >= 3) & (to_others <= 40)] = 41
    to_others[::2] += 1024                                # block 2
    # column 2 is a hub, the last is none
    rows = np.concatenate([to_hubs, to_others, [1536, 1536, 1541]])
    cols = np.concatenate([of_hubs, of_others, [2, m - 1, 7]])
    return rows, cols, n, m


def _matrix(rng, kind, n=700, m=600):
    """Seeded COO matrices as the issue lists them: values of both
    signs, rows with no entry, and by ``kind`` a full row (every
    column), skewed degrees (a third of the entries in four rows), a
    cell listed twice, skewed SOURCES (``HUB_KINDS``:
    :func:`_hub_matrix`) or a block of three entries (PR 51: rows 0 and
    5 of a block and padding behind them in one row of 128 slots)."""
    if kind in HUB_KINDS:
        rows, cols, n, m = _hub_matrix(rng, kind)
        keys = np.unique(rows.astype(np.int64) * m + cols)
        order = rng.permutation(keys.size)
        return COOMatrix.from_edges(
            (keys // m)[order], (keys % m)[order],
            rng.normal(size=keys.size).astype(np.float32), shape=(n, m))
    at = rng.choice(n * m, min(9_000, n * m // 4), replace=False)
    rows, cols = at // m, at % m
    if kind == "short-block":
        last = rows >= 512
        rows, cols = rows[~last], cols[~last]
        rows = np.append(rows, [512, 512, 517])
        cols = np.append(cols, [3, 90, 11])
    if kind == "skewed":
        rows[:3_000] = rng.integers(0, 4, 3_000) * 97
    empty = rng.choice(n, n // 17, replace=False)
    keep = ~np.isin(rows, empty)
    rows, cols = rows[keep], cols[keep]
    if kind == "full-row":
        full = int(np.setdiff1d(np.arange(n), empty)[5])
        rows = np.concatenate([rows[rows != full], np.full(m, full)])
        cols = np.concatenate([cols[:rows.size - m], np.arange(m)])
    keys = np.unique(rows.astype(np.int64) * m + cols)
    rows, cols = keys // m, keys % m
    vals = rng.normal(size=rows.size).astype(np.float32)
    if kind == "repeated-cell":
        rows, cols = np.append(rows, rows[:7]), np.append(cols, cols[:7])
        vals = np.append(vals, rng.normal(size=7).astype(np.float32))
    order = rng.permutation(rows.size)
    return COOMatrix.from_edges(rows[order], cols[order], vals[order],
                                shape=(n, m))


def _oracle(A: COOMatrix, x, reduce):
    """numpy over the dense matrix: what ``_agg`` over the dense
    ``join_cols`` computes."""
    prod = A.to_dense() * x[None, :].astype(np.float32)
    return (prod.max if reduce == "max" else prod.min)(axis=1)


KINDS = ["plain", "full-row", "skewed", "repeated-cell", "short-block",
         *HUB_KINDS]


def _assert_both_sets_busy(plan):
    """The plan of a ``HUB_KINDS`` matrix is what its docstring says."""
    hub = plan.hubs
    real = hub.idx < hub.ids.size
    assert hub is not None and real.sum() == hub.entries > 4_000
    assert (plan.val != 0).sum() > 4_000
    # blocks 0 and 1 own hub chunks, 2 none, 3 one of one real slot
    assert sorted(set(hub.chunk_block)) == [0, 1, 3]
    assert (hub.chunk_block == 0).sum() >= 3
    # where the table is several walk steps tall, runs that differ
    assert len({tuple(w) for w in zip(hub.first.ravel(), hub.rows.ravel())}
               ) > (spmv_lib.HUB_WALK < 64)
    # a register that is all padding, and runs that differ
    regs = real.reshape(-1, spmv_lib.HUB_REG)
    assert (~regs.any(axis=1)).any()
    of_rows = np.asarray(plan.off)[np.asarray(plan.chunk_block) == 0]
    main_rows = set(of_rows[np.asarray(plan.val)[
        np.asarray(plan.chunk_block) == 0] != 0])
    hub_rows = set(hub.off[hub.chunk_block == 0][real[hub.chunk_block == 0]])
    assert len(main_rows & hub_rows) > 300       # rows of both sets
    assert set(range(3, 41)) <= hub_rows - main_rows     # hubs alone
    assert 41 in main_rows - hub_rows


@pytest.mark.parametrize("reduce", ["max", "min"])
@pytest.mark.parametrize("kind", KINDS)
def test_fused_is_the_dense_lowering(rng, one_chip, monkeypatch, kind,
                                     reduce):
    """The same SQL through the rule (fused, the kernel) and with the
    rule batch off (the join materialised, the leaf densified): equal
    to the last bit, for labels of both signs."""
    _with_hubs(monkeypatch, kind)
    A = _matrix(rng, kind)
    x = rng.normal(size=(A.shape[1], 1)).astype(np.float32)
    sql = f'row{reduce}(joincols(A, t(x), "mul"))'
    got = {}
    for name, cfg in (("fused", one_chip),
                      ("dense", MatrelConfig(rewrite_rules=False))):
        s = _session(cfg)
        s.register("A", A)
        s.register("x", BlockMatrix.from_numpy(x, mesh=s.mesh))
        got[name] = s.compute(s.sql(sql)).to_numpy()[:, 0]
        said = s.last_plan()
        if name == "fused":
            (rec,) = said["semiring"]
            assert rec["how"] == "kernel" and rec["reduce"] == reduce
            assert rec["layout"] == "chunks" and rec["overflow_edges"] == 0
            assert rec["full_rows"] == (kind == "full-row")
            assert not said["densified_products"]
            assert said["executors"] == ["pallas_spmv"]
            assert bool(rec["hub_chunks"]) == (kind in HUB_KINDS)
            if kind in HUB_KINDS:
                assert 0.5 < rec["hub_entry_share"] < 0.8
                assert rec["hub_slots"] == rec["hub_chunks"] * spmv_lib.CHUNK
                assert rec["slots"] == rec["hub_slots"] \
                    + rec["chunks"] * spmv_lib.CHUNK
                assert rec["hub_walk_rows"] >= 2 * rec["hub_chunks"] \
                    * spmv_lib.HUB_WALK
            else:
                assert rec["hub_slots"] == rec["hub_walk_rows"] == 0
                assert rec["hub_entry_share"] == 0.0
        else:
            assert not said["semiring"] and said["densified_products"]
    np.testing.assert_array_equal(got["fused"], got["dense"])
    np.testing.assert_array_equal(got["fused"], _oracle(A, x[:, 0], reduce))
    # the zeros of missing cells took part: no row of a matrix this
    # sparse but the full one may read past 0
    bound = np.zeros_like(got["fused"])
    if kind == "full-row":
        full = np.flatnonzero(np.bincount(A.rows, minlength=700) == 600)
        bound[full] = -np.inf if reduce == "max" else np.inf
    assert np.all(got["fused"] >= bound if reduce == "max"
                  else got["fused"] <= bound)


@pytest.mark.parametrize("reduce", ["max", "min"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_kernel_is_the_xla_fallback(rng, one_chip, monkeypatch, kind,
                                        reduce):
    import jax.numpy as jnp
    _with_hubs(monkeypatch, kind)
    A = _matrix(rng, kind).entry_view()
    x = jnp.asarray(rng.normal(size=A.shape[1]).astype(np.float32))
    plan = A._get_plan()
    assert spmv_lib.rows_in_order(plan)
    if kind in HUB_KINDS:
        _assert_both_sets_busy(plan)
    else:
        assert plan.hubs is None
    kernel = coo_lib.semiring_apply(A, plan, x, reduce, interpret=True)
    xla = coo_lib.semiring_apply(A, None, x, reduce)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(xla),
                                  _oracle(A, np.asarray(x), reduce))


def test_a_default_session_answers_through_xla(rng):
    """No Pallas on the CPU: the segment reduction, still from the
    entries alone."""
    A = _matrix(rng, "plain")
    x = rng.normal(size=(600, 1)).astype(np.float32)
    s = _session()
    s.register("A", A)
    s.register("x", BlockMatrix.from_numpy(x, mesh=s.mesh))
    got = s.compute(s.sql('rowmin(joincols(A, t(x), "mul"))')).to_numpy()
    np.testing.assert_array_equal(got[:, 0], _oracle(A, x[:, 0], "min"))
    said = s.last_plan()
    assert said["semiring"][0]["how"] == "xla"
    assert said["executors"] == ["xla"] and not said["densified_products"]


def test_on_a_mesh_the_column_is_replicated(rng, mesh8):
    A = _matrix(rng, "skewed")
    x = rng.normal(size=(600, 1)).astype(np.float32)
    s = MatrelSession(mesh=mesh8)
    s.register("A", A)
    s.register("x", BlockMatrix.from_numpy(x, mesh=mesh8))
    got = s.compute(s.sql('rowmax(joincols(A, t(x), "mul"))')).to_numpy()
    np.testing.assert_array_equal(got[:, 0], _oracle(A, x[:, 0], "max"))
    assert s.last_plan()["semiring"][0]["how"] == "xla"


@pytest.mark.parametrize("why", ["hub-chunks", "input-order", "blocks",
                                 "hub-registers-by-table-row",
                                 "padding-behind-two-rows"])
def test_a_plan_the_kernel_cannot_read_is_not_handed_to_it(rng, why,
                                                           monkeypatch):
    """The segmented scan takes for granted that in a row of 128 slots
    those of one destination row lie side by side; the dispatch asks the
    tables themselves, the hub chunks' too."""
    rows = rng.integers(0, 1024, 40_000)
    cols = rng.integers(0, 900, rows.size)
    if why == "blocks":
        plan = spmv_lib.build_spmv_plan(rows, cols, None, 1024, 900)
    elif why in ("input-order", "padding-behind-two-rows"):
        plan = spmv_lib.build_spmv_plan(rows, cols, None, 1024, 900,
                                        layout="chunks", hubs=False)
        assert spmv_lib.rows_in_order(plan)
        del plan._rows_in_order
        flat = np.asarray(plan.off).reshape(-1).copy()
        if why == "input-order":
            flat[[3, 4]] = flat[[4000, 3]]
        else:
            # what the fills laid until PR 51: a padded slot's ``off`` 0,
            # here behind real slots of other rows, which the scan would
            # take for row 0's run going on (and add to it: the one-hot
            # places every run's end, and this would be a second for
            # row 0)
            pad = np.asarray(plan.val).reshape(-1) == 0
            assert pad.any() and flat[pad].min() > 0
            flat[pad] = 0
        plan.off = flat.reshape(np.asarray(plan.off).shape)
    else:
        monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 2)
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
        plan = spmv_lib.build_spmv_plan(rows, cols, None, 1024, 900,
                                        layout="chunks")
        hub = plan.hubs
        assert hub.ids.size == 256 and spmv_lib.rows_in_order(plan)
        del plan._rows_in_order
        real = hub.idx < hub.ids.size
        if why == "hub-chunks":
            # hub chunks are no reason by themselves (until PR 51 they
            # were); one slot out of its row's run is
            flat = hub.off.reshape(-1).copy()
            flat[[3, 4]] = flat[[900, 3]]
            assert flat[3] != flat[5]
            hub.off = flat.reshape(hub.off.shape)
        else:
            # a plan file of PRs 42 to 50: a block's hub slots by table
            # row alone, in input order inside one
            for b in np.unique(hub.chunk_block):
                at = hub.chunk_block == b
                n = int(real[at].sum())
                order = np.argsort(hub.idx[at].ravel()[:n] >> 7,
                                   kind="stable")
                for t in (hub.idx, hub.off, hub.val):
                    flat = t[at].ravel()
                    flat[:n] = flat[:n][order]
                    t[at] = flat.reshape(-1, spmv_lib.CHUNK)
            # the same registers hold the same slots: the same walks
            first, walked = spmv_lib.hub_walks(hub.idx, hub.ids.size)
            np.testing.assert_array_equal(first, hub.first)
            np.testing.assert_array_equal(walked, hub.rows)
    assert not spmv_lib.rows_in_order(plan)


# -- weakly connected components ------------------------------------------------


def _graph(rng, n=2_000):
    """An undirected graph of several components, one an isolated pair,
    some vertices with no edge at all; both directions of every edge."""
    comp = rng.integers(0, 12, n)           # a vertex's would-be component
    comp[[n - 2, n - 1]] = 99               # the isolated pair
    a, b = rng.integers(0, n, 6 * n), rng.integers(0, n, 6 * n)
    same = (comp[a] == comp[b]) & (a != b)
    lo, hi = np.minimum(a, b)[same], np.maximum(a, b)[same]
    keys = np.unique(lo.astype(np.int64) * n + hi)
    lo, hi = keys // n, keys % n
    lo, hi = np.append(lo, n - 2), np.append(hi, n - 1)
    rows, cols = np.concatenate([lo, hi]), np.concatenate([hi, lo])
    order = rng.permutation(rows.size)
    return rows[order], cols[order], n


def _scipy_labels(rows, cols, n):
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    count, comp = connected_components(
        sp.coo_matrix((np.ones(rows.size), (rows, cols)), shape=(n, n)),
        directed=False)
    top = np.zeros(count)
    np.maximum.at(top, comp, np.arange(n) + 1.0)
    return count, top[comp]


@pytest.mark.parametrize("kind", ["plain", "hubs-one-step"])
def test_wcc_through_sql_is_scipys_components(rng, one_chip, monkeypatch,
                                              kind):
    """``kind``: without hub chunks, and with the 256 vertices of most
    edges in a hub table (the cell's plan since PR 51)."""
    # four even blocks: "auto" would keep the blocks layout
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "chunks")
    _with_hubs(monkeypatch, kind)
    rows, cols, n = _graph(rng)
    count, want = _scipy_labels(rows, cols, n)
    assert count > 12                       # the pair, the lone vertices
    s = _session(one_chip)
    s.register("A", COOMatrix.from_edges(rows, cols, None, shape=(n, n)))
    builds = coo_lib.plan_builds()
    L = BlockMatrix.from_numpy(
        np.arange(1, n + 1, dtype=np.float32)[:, None], mesh=s.mesh)
    hits = []
    for rounds in range(1, 60):
        s.register("L", L)
        new = s.compute(s.sql(ROUND_SQL))
        said = s.last_plan()
        hits.append(said["hit"])
        (rec,) = said["semiring"]
        assert rec["how"] == "kernel" and rec["full_rows"] == 0
        assert not said["densified_products"]
        assert bool(rec["hub_chunks"]) == (kind in HUB_KINDS)
        s.register("Lnew", new)
        changed = s.compute(s.sql("count(Lnew - L)")).to_numpy()[0, 0]
        L = new
        if changed == 0:
            break
    got = L.to_numpy()[:, 0]
    assert int((got != want).sum()) == 0
    assert np.unique(got).size == count
    assert 2 < rounds < 59
    # L is a new array every round: only the first round compiled
    assert hits[0] is False and all(hits[1:])
    assert coo_lib.plan_builds() - builds == 1


# -- what the rule leaves alone -------------------------------------------------


def test_an_unmatched_join_meets_the_cap_it_always_met(rng):
    """Two rows joined at a size whose join is 160G entries: refused by
    name where the join is materialised, as the parent refuses it; the
    one-row query beside it is answered."""
    n = 400_000
    at = rng.choice(n, 5_000, replace=False)
    A = COOMatrix.from_edges(at, (at * 7 + 1) % n, None, shape=(n, n))
    s = _session()
    s.register("A", A)
    s.register("B", BlockMatrix.from_numpy(
        rng.random((n, 2), dtype=np.float32), mesh=s.mesh))
    s.register("x", BlockMatrix.from_numpy(
        rng.random((n, 1), dtype=np.float32), mesh=s.mesh))
    with pytest.raises(Exception, match="join_pair_cap_entries = 67108864"):
        s.compute(s.sql('rowmax(joincols(A, t(B), "mul"))'))
    assert MatrelConfig().join_pair_cap_entries == 1 << 26
    out = s.compute(s.sql('rowmax(joincols(A, t(x), "mul"))')).to_numpy()
    assert out.shape == (n, 1) and out.max() > 0 and out.min() == 0


def test_a_small_unmatched_join_densifies_and_says_so(rng):
    A = _matrix(rng, "plain", n=60, m=50)
    s = _session()
    s.register("A", A)
    s.register("B", BlockMatrix.from_numpy(
        rng.random((50, 2), dtype=np.float32), mesh=s.mesh))
    s.compute(s.sql('rowmax(joincols(A, t(B), "mul"))'))
    said = s.last_plan()
    assert not said["semiring"] and said["densified_products"]
    assert s._last_plan.meta["densified_joins"] == 1


def test_the_registry_counts_fused_and_densified_products(rng, tmp_path):
    """``semiring.fused`` a product answered from the entries,
    ``semiring.densified`` a "mul" join that densified its leaf, and
    the rule's own hit counter, a query each (obs on)."""
    import jax
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.obs.metrics import REGISTRY
    A = _matrix(rng, "plain", n=60, m=50)
    s = MatrelSession(
        mesh=mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1]),
        config=MatrelConfig(obs_level="on", obs_event_log=str(
            tmp_path / "events.jsonl")))
    s.register("A", A)
    for name, cols in (("x", 1), ("B", 2)):
        s.register(name, BlockMatrix.from_numpy(
            rng.random((50, cols), dtype=np.float32), mesh=s.mesh))

    def read():
        return [REGISTRY.counter(n).value for n in (
            "semiring.fused", "semiring.densified",
            "optimizer.rule.semiring_product")]

    before = read()
    s.compute(s.sql('rowmax(joincols(A, t(x), "mul"))'))
    assert [a - b for a, b in zip(read(), before)] == [1, 0, 1]
    s.compute(s.sql('rowmax(joincols(A, t(B), "mul"))'))
    assert [a - b for a, b in zip(read(), before)] == [1, 1, 1]


def test_the_reckoning_holds_the_plan_and_no_join(rng, one_chip):
    """``hbm_plan_bytes`` holds the plan's tables and a panel, not the
    (n x m) join; the record names what answers."""
    from matrel_tpu.ops import pallas_spmv as pc
    A = _matrix(rng, "plain")
    s = _session(one_chip)
    s.register("A", A)
    s.register("x", BlockMatrix.from_numpy(
        rng.random((600, 1), dtype=np.float32), mesh=s.mesh))
    s.compute(s.sql('rowmax(joincols(A, t(x), "mul"))'))
    said = s.last_plan()
    (rec,) = said["semiring"]
    assert rec["hbm_plan_bytes"] == pc.plan_bytes(rec["chunks"],
                                                  spmv_lib.CHUNK)
    (product,) = [p for p in said["products"] if p["node"] == "semiring"]
    assert product["chosen"] == "coo_reduce" and product["layout"] == "chunks"
    assert rec["hbm_plan_bytes"] <= said["hbm_plan_bytes"] \
        < rec["hbm_plan_bytes"] + 4 * 700 * 600


def _lowered_hash(text, kernels):
    """SHA-256 of a program lowered for the chip, the Mosaic kernels'
    serialized bodies (which embed paths and line numbers) read back
    and printed without debug info (tests/test_sampled.py's)."""
    import base64
    import hashlib
    import re
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(m):
        with mlir.make_ir_context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return mod.operation.get_asm(enable_debug_info=False)

    text, n = re.subn(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body,
                      text)
    assert n == kernels
    return hashlib.sha256(text.encode()).hexdigest()


def _fixed_edges():
    fixed = np.random.default_rng(7)
    rows = np.concatenate([fixed.integers(0, 512, 30_000),
                           fixed.integers(512, 1024, 1_500)])
    cols = fixed.integers(0, 300, rows.size)
    vals = fixed.standard_normal(rows.size).astype(np.float32)
    return fixed, rows, cols, vals


@pytest.mark.parametrize("program", ["coo_leaf-matvec", "pagerank_edges"])
def test_the_sum_products_lower_to_the_parents_programs(program,
                                                        monkeypatch):
    """The (+, x) paths this PR shares tables with — the coo_leaf
    matvec's ``compact_apply`` over a chunked plan, and
    ``pagerank_edges``' ten-round loop over a plan with hub chunks —
    lower for the chip to the text the parent commit (bd40130) lowers
    them to, by SHA-256 recorded there in this container's jax: the
    reduction kernel is another ``pallas_call`` and nothing of it is in
    their programs."""
    import jax
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.workloads import pagerank
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    fixed, rows, cols, vals = _fixed_edges()
    if program == "coo_leaf-matvec":
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, 1024, 300,
                                        layout="chunks", hubs=False)
        static = (1024, 300, plan.block, spmv_lib.LO)
        text = jax.jit(lambda t, x: pc.compact_apply(
            static, t, (), x, 3, False)).trace(
            pc.compact_tables(plan),
            jax.ShapeDtypeStruct((300,), jnp.float32)
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("matrel_spmv_scatter_chunks") == 1
        assert "matrel_spmv_reduce" not in text
        assert _lowered_hash(text, 1) == (
            "7102c25ffeaba7c1a27f342881e5c3e0"
            "36a30b2e0642d4f3bccfc02d538ec4d0")
        return
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 2)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    cols = np.where(fixed.random(rows.size) < 0.6,
                    fixed.integers(0, 40, rows.size), cols)
    plan = spmv_lib.build_spmv_plan(rows, cols, vals, 1024, 1024,
                                    layout="chunks", hubs=True)
    assert plan.hubs is not None
    static = (1024, 1024, plan.block, spmv_lib.LO)
    loop = pagerank._compact_runner_loop(1024, 10, 0.85, static, 0, 3,
                                         False)
    text = loop.trace(
        pc.compact_tables(plan), (),
        jax.ShapeDtypeStruct((1024,), jnp.float32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("matrel_spmv_scatter_hubs") == 1
    assert "matrel_spmv_reduce" not in text
    assert _lowered_hash(text, 2) == (
        "001e90dad6af7f1a5322e73fbf7887ce"
        "ba4abd95c7c3935400bdba349896f9c2")
