"""GNMF (Lee and Seung's multiplicative updates) through ``session.sql`` +
``compute`` over a registered COOMatrix (PR 37): the two updates against
float64 numpy, the k-wide compact product in chunks and source panels
under them, the plan templates that answer an update whose factors are
new arrays, the chain DP's bracketing of the two denominators, and the
densifying fall-through refused by name."""

import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import coo as coo_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.parallel.planner import PlanMemoryError, hbm_report
from matrel_tpu.session import MatrelSession

def _session(config=None):
    """One chip's session: a 1x1 mesh of the first device."""
    import jax
    from matrel_tpu.core import mesh as mesh_lib
    return MatrelSession(
        mesh=mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1]),
        config=config or MatrelConfig())


SQL_H = "H .* (t(W) * V) / (t(W) * W * H)"
SQL_W = "W .* (V * t(H)) / (W * H * t(H))"
USERS, MOVIES, ENTRIES = 3000, 700, 40000


def _ratings(rng):
    """A small skewed ratings matrix: a third of the entries in one
    block of users, a tenth of the movies hold half of them, distinct
    cells, values 1 to 5."""
    rows = rng.integers(0, USERS, ENTRIES)
    rows[:ENTRIES // 3] = rng.integers(512, 1024, ENTRIES // 3)
    cols = rng.integers(0, MOVIES, ENTRIES)
    cols[::2] = rng.integers(0, MOVIES // 10, ENTRIES // 2)
    keys = np.unique(rows * MOVIES + cols)
    rows, cols = keys // MOVIES, keys % MOVIES
    return COOMatrix.from_edges(
        rows, cols, rng.integers(1, 6, rows.size).astype(np.float32),
        shape=(USERS, MOVIES))


@pytest.fixture
def compact_one_device(monkeypatch):
    """What the chip is to a COOMatrix: the compact Pallas executors of
    one device (interpreted), plans past the small-plan threshold as the
    real matrix's are, and a gather table of 1,000 rows at the most, so
    that t(V) * W runs in source panels as the 480,189 users do."""
    cfg = MatrelConfig(pallas_interpret=True, cse_enable=True)
    was = config_lib._default_config
    config_lib.set_default_config(cfg)
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
    monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 1000 * 512)
    yield cfg
    config_lib._default_config = was


def _fit(session, V, W, H, iterations=3):
    session.register("V", V)
    said = []
    for _ in range(iterations):
        for name, sql in (("H", SQL_H), ("W", SQL_W)):
            session.register("W", W)
            session.register("H", H)
            out = session.compute(session.sql(sql))
            said.append(session.last_plan())
            if name == "H":
                H = out
            else:
                W = out
    return W, H, said


def _fit_float64(V, W, H, iterations=3):
    Vd = V.to_dense().astype(np.float64)
    W, H = W.astype(np.float64), H.astype(np.float64)
    for _ in range(iterations):
        H = H * (W.T @ Vd) / (W.T @ W @ H)
        W = W * (Vd @ H.T) / (W @ H @ H.T)
    return W, H


@pytest.mark.parametrize("rank", [16, 128])
def test_three_iterations_match_float64(rng, compact_one_device, rank):
    V = _ratings(rng)
    s = _session(compact_one_device)
    w0 = rng.random((USERS, rank), dtype=np.float32) + 1e-3
    h0 = rng.random((rank, MOVIES), dtype=np.float32) + 1e-3
    W, H, said = _fit(s, V, BlockMatrix.from_numpy(w0, mesh=s.mesh),
                      BlockMatrix.from_numpy(h0, mesh=s.mesh))
    want_w, want_h = _fit_float64(V, w0, h0)
    for got, want in ((W.to_numpy(), want_w), (H.to_numpy(), want_h)):
        assert np.all(np.isfinite(got)) and np.all(got >= 0)
        assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 3e-6
    # both orientations ran the compact k-wide product over chunks, the
    # transposed one in source panels; nothing densified or overflowed
    fwd = next(r for p in said for r in p["spmm"]
               if r["orientation"] == "forward")
    bwd = next(r for p in said for r in p["spmm"]
               if r["orientation"] == "transposed")
    assert fwd["layout"] == bwd["layout"] == "chunks"
    assert fwd["k"] == bwd["k"] == rank
    assert (fwd["source_panels"], fwd["table"]) == (1, "hbm")
    # 3,000 users and the zero row, 1,000 rows a table
    assert (bwd["source_panels"], bwd["table"]) == (4, "panelled")
    assert fwd["overflow_edges"] == bwd["overflow_edges"] == 0
    assert fwd["entries"] == bwd["entries"] == V.nnz
    # a block's slots lie in row order: the hub block's chunks hold few
    # users each and take a 128-row window, on a build and on a hit
    for facts in (fwd, bwd):
        assert 0 < facts["windowed_chunks"] <= facts["chunks"]
        again = [r["windowed_chunks"] for p in said for r in p["spmm"]
                 if r["orientation"] == facts["orientation"]]
        assert again == [facts["windowed_chunks"]] * 3
    assert all(not p["densified_products"] for p in said)
    assert all("pallas_spmv" in p["executors"] for p in said)


@pytest.mark.parametrize("transposed", [False, True],
                         ids=["forward", "transposed_in_panels"])
def test_the_ratings_plans_have_no_hub_chunks(rng, compact_one_device,
                                              transposed):
    """PR 42 lays a plan's HUB slots by the hub table's row. A ratings
    matrix's plans have none, though its hot movies are hubs by any
    count (the same entries through PageRank's door get a table): every
    part keeps its slots by destination row, a window a chunk where the
    rows allow, five tables on the device, and the k-wide kernel alone
    in the lowered product."""
    import jax
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    V = _ratings(rng)
    plan = V._get_wide_plan(transposed=transposed)
    parts = [p for _, p in getattr(plan, "parts", ((0, plan),))]
    assert len(parts) == (4 if transposed else 1)
    for part in parts:
        assert part.hubs is None and part.chunk_block is not None
        assert len(pc.compact_tables(part)) == 5
        real = part.val != 0
        for b in np.unique(part.chunk_block):
            mine = part.chunk_block == b
            assert (np.diff(part.off[mine][real[mine]]) >= 0).all()
    if not transposed:
        hubbed = spmv_lib.build_spmv_plan(
            V.rows, V.cols, V.vals, *V.shape, layout="chunks")
        assert hubbed.hubs is not None
    static, part_statics, part_arrays = pc.plan_operands(plan)
    n_in = V.shape[0] if transposed else V.shape[1]
    text = jax.jit(lambda pa, x: pc.compact_matmat_parts(
        static, part_statics, pa, x, 3, False)).trace(
        part_arrays, jax.ShapeDtypeStruct((n_in, 128), jnp.float32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("matrel_spmm_scatter_chunks") >= len(parts)
    assert "scatter_hubs" not in text


def test_new_factor_arrays_hit_the_plan_templates(rng, compact_one_device):
    """W and H are new arrays every update: the first iteration compiles
    its two programs, every later update rebinds them, and V's plans are
    built once."""
    V = _ratings(rng)
    s = _session(compact_one_device)
    builds = coo_lib.plan_builds()
    w0 = rng.random((USERS, 16), dtype=np.float32) + 1e-3
    h0 = rng.random((16, MOVIES), dtype=np.float32) + 1e-3
    _, _, said = _fit(s, V, BlockMatrix.from_numpy(w0, mesh=s.mesh),
                      BlockMatrix.from_numpy(h0, mesh=s.mesh))
    assert [p["hit"] for p in said] == [False, False] + [True] * 4
    # what a hit says of the plans is what the build said
    for build, hit in zip(said[:2], said[2:4]):
        assert hit["spmm"] == build["spmm"]
        assert "windowed_chunks" in hit["spmm"][0]
    assert coo_lib.plan_builds() == builds + 2
    assert s.plan_cache_info()["plans"] == 2
    # a second fit from new factors: nothing compiles, nothing is built
    _, _, again = _fit(s, V, BlockMatrix.from_numpy(w0 + 1, mesh=s.mesh),
                       BlockMatrix.from_numpy(h0 + 1, mesh=s.mesh))
    assert all(p["hit"] for p in again)
    assert coo_lib.plan_builds() == builds + 2
    assert s.plan_cache_info()["plans"] == 2


def test_the_denominators_bracket_around_the_small_gram(rng):
    """t(W) * W * H is (t(W) * W) * H, a rank x rank Gram first, and
    W * H * t(H) is W * (H * t(H)): the other bracketings hold the
    users x movies dense product."""
    from matrel_tpu import executor
    V = _ratings(rng)
    s = _session()
    s.register("V", V)
    s.register("W", BlockMatrix.from_numpy(
        rng.random((USERS, 16), dtype=np.float32), mesh=s.mesh))
    s.register("H", BlockMatrix.from_numpy(
        rng.random((16, MOVIES), dtype=np.float32), mesh=s.mesh))

    def matmul_shapes(sql):
        plan = executor.compile_expr(s.sql(sql), s.mesh, s.config)
        out = []

        def walk(n):
            if n.kind == "matmul":
                out.append(tuple(c.shape for c in n.children))
            for c in n.children:
                walk(c)

        walk(plan.optimized)
        return out

    h = matmul_shapes(SQL_H)
    assert ((16, USERS), (USERS, 16)) in h            # t(W) * W
    assert ((16, 16), (16, MOVIES)) in h              # (.) * H
    assert not any(USERS in a and MOVIES in b or (a == (USERS, 16)
                   and b == (16, MOVIES)) for a, b in h
                   if (a, b) != ((16, USERS), (USERS, MOVIES)))
    w = matmul_shapes(SQL_W)
    assert ((16, MOVIES), (MOVIES, 16)) in w          # H * t(H)
    assert ((USERS, 16), (16, 16)) in w               # W * (.)
    assert ((USERS, 16), (16, MOVIES)) not in w       # never W * H


def test_a_densifying_fall_through_that_does_not_fit_is_refused_by_name(rng):
    """A dense side of more than 128 columns densifies the sparse leaf:
    priced at plan time, refused by name where it does not fit, run
    (and said) where it does."""
    V = _ratings(rng)
    wide = rng.random((MOVIES, 200), dtype=np.float32)
    dense_bytes = 4 * USERS * MOVIES
    tight = _session(MatrelConfig(hbm_budget_bytes=dense_bytes))
    tight.register("V", V)
    tight.register("D", BlockMatrix.from_numpy(wide, mesh=tight.mesh))
    with pytest.raises(PlanMemoryError, match="DENSIFY.*200 columns, more "
                       "than the 128"):
        tight.compute(tight.sql("V * D"))
    # with room the densified product runs, and the plan says so; the
    # same product of 128 columns runs through the tables
    roomy = _session()
    roomy.register("V", V)
    roomy.register("D", BlockMatrix.from_numpy(wide, mesh=roomy.mesh))
    roomy.register("N", BlockMatrix.from_numpy(wide[:, :128].copy(),
                                               mesh=roomy.mesh))
    out = roomy.compute(roomy.sql("V * N"))
    assert roomy.last_plan()["spmm"][0]["k"] == 128
    assert not roomy.last_plan()["densified_products"]
    np.testing.assert_allclose(out.to_numpy(), V.to_dense() @ wide[:, :128],
                               rtol=2e-5, atol=1e-4)
    out = roomy.compute(roomy.sql("V * D"))
    said = roomy.last_plan()
    assert said["densified_products"] == [
        {"shape": [USERS, MOVIES], "entries": V.nnz, "bytes": dense_bytes}]
    assert not said["spmm"]
    np.testing.assert_allclose(out.to_numpy(), V.to_dense() @ wide,
                               rtol=2e-5, atol=1e-4)


def test_the_planner_prices_the_tables_and_a_panel(rng, compact_one_device):
    """hbm_plan_bytes of a coo_leaf product holds the SpMV plan's tables
    and one panel's gathered rows, and its plan.strategy record says
    what runs."""
    from matrel_tpu import executor
    from matrel_tpu.ops import pallas_spmv as pc
    V = _ratings(rng)
    s = _session(compact_one_device)
    s.register("V", V)
    s.register("H", BlockMatrix.from_numpy(
        rng.random((16, MOVIES), dtype=np.float32), mesh=s.mesh))
    plan = executor.compile_expr(s.sql("V * t(H)"), s.mesh, s.config)
    (rec,) = [r for r in hbm_report(plan.optimized) if r["node"] == "matmul"]
    assert rec["chosen"] == "coo_spmm" and rec["layout"] == "chunks"
    assert rec["panels"] == [1, 1] and rec["refused_hbm"] == []
    chunks = V._get_wide_plan().src8.shape[0]
    assert rec["hbm_plan_bytes"] >= pc.wide_plan_bytes(chunks,
                                                       spmv_lib.CHUNK)
    assert plan.meta["hbm_plan_bytes"] == rec["hbm_plan_bytes"]


def test_the_spans_say_what_the_reader_says(rng, compact_one_device,
                                            tmp_path):
    """Under a profiler session every dispatch of an update carries a
    ``matrel.spmm.plan`` span a coo_leaf product, with the attributes
    ``last_plan`` gives, and the template's answer is a ``matrel.plan``
    span with ``hit`` true."""
    import jax
    from matrel_tpu.obs.trace import profile_spans
    V = _ratings(rng)
    s = _session(compact_one_device)
    w0 = rng.random((USERS, 16), dtype=np.float32) + 1e-3
    h0 = rng.random((16, MOVIES), dtype=np.float32) + 1e-3
    W, H, _ = _fit(s, V, BlockMatrix.from_numpy(w0, mesh=s.mesh),
                   BlockMatrix.from_numpy(h0, mesh=s.mesh), iterations=1)
    before = len(profile_spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, _, said = _fit(s, V, W, H, iterations=1)
    finally:
        jax.profiler.stop_trace()
    mine = profile_spans()[before:]
    plans = [r for r in mine if r["name"] == "matrel.spmm.plan"]
    assert [r["attrs"]["orientation"] for r in plans] == ["transposed",
                                                          "forward"]
    for r, p in zip(plans, said):
        assert r["attrs"]["hit"] is True
        assert {k: v for k, v in r["attrs"].items() if k != "hit"} \
            == p["spmm"][0]
        assert set(p["spmm"][0]) == {
            "orientation", "k", "layout", "entries", "slots", "chunks",
            "source_panels", "table", "overflow_edges", "panels",
            "plan_bytes", "windowed_chunks"}
    lookups = [r for r in mine if r["name"] == "matrel.plan"]
    assert [r["attrs"] for r in lookups] == [
        {"via": "template", "hit": True}] * 2
    assert not [r for r in mine if r["name"] == "matrel.compile"]
