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
    that t(V) * W runs in source panels as the 480,189 users do. No room
    for a slab (PR 43: a line of 3,000 cells would pay from 11 entries
    on, and every movie would leave the tables these tests are about;
    the dense part's own tests give it room)."""
    cfg = MatrelConfig(pallas_interpret=True)
    was = config_lib._default_config
    config_lib.set_default_config(cfg)
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
    monkeypatch.setattr(coo_lib, "_DENSE_SHARE", 0.0)
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


@pytest.mark.parametrize("orientation", ["transposed", "forward"])
def test_a_window_as_tall_as_the_chunk_needs(rng, compact_one_device,
                                             monkeypatch, ladder_cells,
                                             orientation):
    """The ladder (PR 49) under both of GNMF's sparse products: over a
    matrix whose chunks take a 128-row window, a 256-row one and the
    whole block in both orientations, ``last_plan()["spmm"]`` says how
    many take which height, and the product is the product with the
    windows withheld, bit for bit."""
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    # one gather table: in panels of 1,000 sources, two of them empty,
    # the build lays this matrix out in blocks
    monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 4096 * 512)
    rows, cols = ladder_cells
    n, rank = 2048, 16
    V = COOMatrix.from_edges(
        rows, cols, rng.integers(1, 6, rows.size).astype(np.float32),
        shape=(n, n))
    s = _session(compact_one_device)
    x = rng.random((n, rank), dtype=np.float32)
    flipped = orientation == "transposed"
    s.register("V", V)
    s.register("X", BlockMatrix.from_numpy(x.T if flipped else x,
                                           mesh=s.mesh))
    got = s.compute(s.sql("X * V" if flipped else "V * X")).to_numpy()
    (rec,) = s.last_plan()["spmm"]
    assert rec["orientation"] == orientation and rec["layout"] == "chunks"
    assert rec["source_panels"] == 1
    assert sorted(rec["window_rows"]) == ["128", "256"]
    assert rec["window_rows"]["256"] == 1 and rec["window_rows"]["128"] > 8
    assert rec["windowed_chunks"] == sum(rec["window_rows"].values()) \
        == rec["chunks"] - 1
    plan = V._get_wide_plan(transposed=flipped)
    static, statics, arrays = pc.plan_operands(plan)

    def product(arrays):
        return np.asarray(pc.compact_matmat_parts(
            static, statics, arrays, jnp.asarray(x), 3, True))

    assert all(wins is not None for _, _, wins in arrays)
    mine = product(arrays)
    np.testing.assert_array_equal(mine, product(tuple(
        (tables, ov, None) for tables, ov, _ in arrays)))
    np.testing.assert_array_equal(got.T if flipped else got, mine)


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
            "plan_bytes", "windowed_chunks", "window_rows"}
    lookups = [r for r in mine if r["name"] == "matrel.plan"]
    assert [r["attrs"] for r in lookups] == [
        {"via": "template", "hit": True}] * 2
    assert not [r for r in mine if r["name"] == "matrel.compile"]


# -- the dense part (PR 43) ---------------------------------------------------

WIDE_MOVIES, HOT_MOVIES = 12_000, 128


def _ratings_with_hot_movies(rng, stars=1.1):
    """A ratings matrix as the rule sees the real one: 128 hot movies of
    ~250 ratings (a column of 3,000 users pays from 12 on, from 9 in
    bfloat16) over a tail of 14,000, one a movie at the least and two at
    the most of most; a user holds 15 of 12,000 (a row pays from 47 on,
    from 34 in bfloat16: the first group or two of users may, and hold
    a tenth of what the hot movies hold). Distinct cells, values 1 to 5 times
    ``stars`` (1.1: no bfloat16 holds them; 1: every one does)."""
    hot = rng.choice(WIDE_MOVIES, HOT_MOVIES, replace=False)
    cols = np.concatenate([hot[rng.integers(0, HOT_MOVIES, 32_000)],
                           np.arange(WIDE_MOVIES),
                           rng.integers(0, WIDE_MOVIES, 2_000)])
    keys = np.unique(rng.integers(0, USERS, cols.size) * WIDE_MOVIES + cols)
    rows, cols = keys // WIDE_MOVIES, keys % WIDE_MOVIES
    vals = rng.integers(1, 6, rows.size) * np.float32(stars)
    return hot, COOMatrix.from_edges(rows, cols, vals.astype(np.float32),
                                     shape=(USERS, WIDE_MOVIES))


@pytest.fixture
def compact_with_room(compact_one_device, monkeypatch):
    """``compact_one_device`` with the slab's share of the device as it
    ships, and a gather table of 2,000 rows (the users are two source
    panels, the movies seven)."""
    monkeypatch.undo()
    monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
    monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 2000 * 512)
    return compact_one_device


@pytest.mark.parametrize("rank,stars", [(16, 1.1), (128, 1.1), (16, 1)],
                         ids=["r16-float32", "r128-float32", "r16-bfloat16"])
def test_a_fit_over_a_slab_matches_float64(rng, compact_with_room, rank,
                                           stars):
    """Both updates through ``session.sql`` + ``compute`` on a matrix
    whose hot movies lie in a slab: the factors are float64's, both
    orientations say the same slab, the slots hold the rest, and the
    templates answer as before. Ratings that are bfloat16's lie in a
    bfloat16 slab, to the same 3e-6."""
    hot, V = _ratings_with_hot_movies(rng, stars)
    s = _session(compact_with_room)
    builds = coo_lib.plan_builds()
    w0 = rng.random((USERS, rank), dtype=np.float32) + 1e-3
    h0 = rng.random((rank, WIDE_MOVIES), dtype=np.float32) + 1e-3
    W, H, said = _fit(s, V, BlockMatrix.from_numpy(w0, mesh=s.mesh),
                      BlockMatrix.from_numpy(h0, mesh=s.mesh))
    want_w, want_h = _fit_float64(V, w0, h0)
    for got, want in ((W.to_numpy(), want_w), (H.to_numpy(), want_h)):
        assert np.all(np.isfinite(got)) and np.all(got >= 0)
        assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 3e-6
    assert coo_lib.plan_builds() == builds + 2
    assert [p["hit"] for p in said] == [False, False] + [True] * 4
    held = int(np.isin(V.cols, hot).sum())
    by = {r["orientation"]: r for p in said for r in p["spmm"]}
    for facts in by.values():
        assert facts["dense_axis"] == "columns"
        assert facts["entries"] + facts["dense_entries"] == V.nnz
        assert facts["entries"] <= facts["slots"]
        assert facts["overflow_edges"] == 0
        if stars == 1:
            assert facts["dense_dtype"] == "bfloat16"
            assert facts["dense_lines"] % 128 == 0
            assert facts["dense_lines"] >= HOT_MOVIES
            assert facts["dense_entries"] >= held
            assert facts["dense_bytes"] == 2 * USERS * facts["dense_lines"]
        else:
            assert facts["dense_dtype"] == "float32"
            assert facts["dense_lines"] == HOT_MOVIES
            assert facts["dense_entries"] == held
            assert facts["dense_bytes"] == 4 * USERS * HOT_MOVIES
    assert (by["forward"]["source_panels"],
            by["transposed"]["source_panels"]) == (7, 2)
    # a hit says of the slab what the build said
    for build, hit in zip(said[:2], said[2:4]):
        assert hit["spmm"] == build["spmm"]
    assert all(not p["densified_products"] for p in said)
    assert all("pallas_spmv" in p["executors"] for p in said)
    # one slab: the forward plan's is the transposed plan's
    assert V._get_wide_plan().dense is V._get_wide_plan(True).dense


def test_the_planner_counts_the_slab_once(rng, compact_with_room):
    """hbm_plan_bytes of a coo_leaf product over a plan with a dense
    part: the residual's tables and panel, and the slab, once."""
    from matrel_tpu import executor
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.parallel import planner
    _, V = _ratings_with_hot_movies(rng)
    s = _session(compact_with_room)
    s.register("V", V)
    s.register("H", BlockMatrix.from_numpy(
        rng.random((16, WIDE_MOVIES), dtype=np.float32), mesh=s.mesh))
    s.register("W", BlockMatrix.from_numpy(
        rng.random((USERS, 16), dtype=np.float32), mesh=s.mesh))
    slab = 4 * USERS * HOT_MOVIES
    for sql, transposed in (("V * t(H)", False), ("t(W) * V", True)):
        plan = executor.compile_expr(s.sql(sql), s.mesh, s.config)
        (rec,) = [r for r in hbm_report(plan.optimized)
                  if r["node"] == "matmul"]
        assert rec["chosen"] == "coo_spmm" and rec["refused_hbm"] == []
        wide = V._get_wide_plan(transposed)
        facts = coo_lib.plan_facts(wide, V.nnz)
        tables = sum(pc.wide_plan_bytes(*np.asarray(p.src8).shape)
                     for _, p in wide.parts)
        assert slab <= facts["plan_bytes"] - 13 * facts["slots"] < tables \
            - 13 * facts["slots"] + slab + 1
        (node,) = [n for n in planner._nodes(plan.optimized)
                   if n.attrs.get("coo_product")]
        assert node.attrs["coo_product"]["bytes"] == facts["plan_bytes"]
        operands = 4 * 16 * (USERS + WIDE_MOVIES)
        assert facts["plan_bytes"] <= rec["hbm_plan_bytes"] \
            < facts["plan_bytes"] + slab + 64 * operands


def test_a_tight_device_gets_a_smaller_slab_not_a_refusal(rng, monkeypatch):
    """The slab is built to what the device has left: whatever budget
    serves the product without a slab serves it with the rule in force,
    and the refusal of a budget too small for the tables alone is the
    one it always was (no ``PlanMemoryError`` the slab caused)."""
    from matrel_tpu import executor
    hot, _ = _ratings_with_hot_movies(rng)
    state = rng.bit_generator.state
    was = config_lib._default_config
    served = {}
    try:
        for share in (coo_lib._DENSE_SHARE, 0.0):
            monkeypatch.setattr(coo_lib, "_DENSE_SHARE", share)
            monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
            monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
            for budget in (4 << 20, 12 << 20, 14 << 20, 16 << 20, 64 << 20):
                cfg = MatrelConfig(pallas_interpret=True,
                                   hbm_budget_bytes=budget)
                config_lib.set_default_config(cfg)
                rng.bit_generator.state = state
                _, V = _ratings_with_hot_movies(rng)
                s = _session(cfg)
                s.register("V", V)
                s.register("H", BlockMatrix.from_numpy(np.ones(
                    (16, WIDE_MOVIES), np.float32), mesh=s.mesh))
                try:
                    plan = executor.compile_expr(s.sql("V * t(H)"), s.mesh,
                                                 s.config)
                    said = plan.meta["spmm"][0]
                    served[share, budget] = said.get("dense_lines", 0)
                    assert plan.meta["hbm_plan_bytes"] <= budget
                except PlanMemoryError:
                    served[share, budget] = None
    finally:
        config_lib._default_config = was
    share = max(k[0] for k in served)
    for budget in (4 << 20, 12 << 20, 14 << 20, 16 << 20, 64 << 20):
        if served[0.0, budget] is None:
            assert served[share, budget] is None
        else:
            assert served[share, budget] is not None, (budget, served)
    assert served[0.0, 4 << 20] is None             # the tables alone
    assert served[share, 64 << 20] == HOT_MOVIES    # room: the slab
    assert 0 in (served[share, 12 << 20], served[share, 14 << 20],
                 served[share, 16 << 20])           # tight: none, served
