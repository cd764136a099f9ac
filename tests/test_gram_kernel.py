"""The long Gram as ONE kernel (PR 55, ops/gram_kernel.py): ``t(X) * X``
over a table whose rows lie on the lanes, the upper triangle in blocks
of 128, a second product ``t(X) * Y`` in the ragged last block's spare
rows. The kernel (interpreted) against a float64 oracle at ragged
sizes, the planner's verdict by name (``last_plan()["products"]``
``gram_kernel``), the regression through the session, the cold record,
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
from matrel_tpu.ir import expr as E
from matrel_tpu.ops import gram_kernel
from matrel_tpu.parallel import planner, strategies
from matrel_tpu.session import MatrelSession

LONG = strategies.LONG_CONTRACTION


@pytest.fixture(scope="module")
def one_device():
    return mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def session_of(mesh, tables, spec=P(None, None), **config):
    sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
    for name, arr in tables.items():
        sess.register(name, arr if isinstance(arr, COOMatrix)
                      else BlockMatrix.from_array(
                          jnp.asarray(arr), arr.shape, mesh,
                          spec if arr.shape[0] >= LONG else P(None, None)))
    return sess


# -- the kernel = the Gram ----------------------------------------------------


@pytest.mark.parametrize("n,k,tile,m", [
    (n, k, tile, m) for n, k, tile in [
        (5000, 200, 2048),      # two tiles, a tail of 904 rows, 2 blocks
        (3000, 328, 1024),      # two tiles, a tail of 952 rows, 3 blocks
        (2100, 1000, 2048),     # the cell's k: 8 blocks, the last of 104
        (2048, 256, 1024)]      # whole tiles and whole blocks: no spare row
    for m in (0, 1, 8) if m <= gram_kernel.rider_room(k)])
def test_the_kernel_is_the_gram(n, k, tile, m):
    rng = np.random.default_rng(n + k + m)
    x = rng.uniform(-1.0, 1.0, (n, k)).astype(np.float32)
    y = (x @ rng.standard_normal((k, m)) + 0.1 * rng.standard_normal(
        (n, m))).astype(np.float32) if m else None
    out = jax.jit(lambda x, y: strategies.gram_in_tiles(
        x, MatrelConfig(), rhs=y, tile=tile, interpret=True))(x, y)
    gram = np.asarray(out[0] if m else out)
    x64 = x.astype(np.float64)
    assert gram.shape == (k, k) and gram.dtype == np.float32
    assert rel(gram, x64.T @ x64) < 2e-6
    assert (gram == gram.T).all()           # symmetric bit for bit
    if m:
        rode = np.asarray(out[1])
        assert rode.shape == (k, m)
        assert rel(rode, x64.T @ y.astype(np.float64)) < 2e-6
        panels = np.asarray(strategies.dot_in_panels(x, 0, y, 0,
                                                     MatrelConfig()))
        assert rel(rode, panels) < 4e-6     # two float32 sums, each 2e-6


def test_blocks_below_the_diagonal_are_never_multiplied():
    """36 of 64 tiles at k = 1000: what the kernel leaves of the square
    is zero below the diagonal's blocks, and one mirror fills it."""
    rng = np.random.default_rng(55)
    x = rng.uniform(-1.0, 1.0, (1024, 1000)).astype(np.float32)
    upper, rode = gram_kernel.gram_upper(
        x, tile=1024, precision=jax.lax.Precision.HIGHEST, interpret=True)
    upper = np.asarray(upper)
    assert rode is None and gram_kernel.tiles(1000) == (36, 64)
    for i in range(8):
        for j in range(8):
            tile = upper[128 * i:128 * (i + 1), 128 * j:128 * (j + 1)]
            assert (np.abs(tile).max() > 0) == (j >= i), (i, j)


def test_the_kernels_body_is_looped():
    """A kernel's traced equations cost every process's set-up (PERF.md
    section 6, PR 52): three call sites of ONE block product in loops,
    not 36 unrolled ones."""
    text = str(jax.make_jaxpr(lambda x, y: gram_kernel.gram_upper(
        x, y, tile=2048, precision=jax.lax.Precision.HIGHEST,
        interpret=True))(jnp.zeros((4096, 1000), jnp.float32),
                         jnp.zeros((4096, 1), jnp.float32)))
    assert text.count("pallas_call") == 1
    assert text.count("dot_general") == 3


def test_what_the_kernel_keeps_in_vmem():
    """Two tiles of the table, the accumulator and the last block's
    scratch: 27 MB at the cell's k under the 64 MB the kernel may take;
    a table of 2,048 columns does not fit and the planner says so."""
    assert gram_kernel.vmem_bytes(1000, 2048) < 32 << 20
    assert gram_kernel.vmem_bytes(1536, 2048) < gram_kernel.VMEM_LIMIT
    assert gram_kernel.vmem_bytes(2048, 2048) > gram_kernel.VMEM_LIMIT
    assert gram_kernel.rider_room(1000) == strategies.gram_rider_room(1000)


# -- who decides: the planner, by name ----------------------------------------


def _described(mesh, shape, dtype=jnp.float32, spec=P(None, None)):
    """A leaf of a described shape: the planner asks shapes and dtypes."""
    from jax.sharding import NamedSharding
    return E.leaf(BlockMatrix.from_array(
        jax.ShapeDtypeStruct(shape, dtype,
                             sharding=NamedSharding(mesh, spec)),
        shape, mesh, spec))


def _gram_of(x, side="AtA"):
    return (E.matmul(E.transpose(x), x) if side == "AtA"
            else E.matmul(x, E.transpose(x)))


WHY_NOT = [
    ("contraction", dict(shape=(328, LONG + 8), side="AAt"), {}),
    ("dtype", dict(dtype=jnp.bfloat16), {}),
    ("matmul_precision", {}, dict(matmul_precision="high")),
    ("precision_sla", {}, dict(precision_sla="exact")),
    ("strategy_override", {}, dict(strategy_override="rmm")),
    ("pallas_off", {}, dict(pallas_interpret=False)),
    ("columns", dict(shape=(LONG + 8, 100)), {}),       # ragged sublanes
    ("columns", dict(shape=(LONG + 8, 128)), {}),       # one block
    ("columns", dict(shape=(LONG + 8, 2048)), {})]      # over VMEM


@pytest.mark.parametrize("why,table,config", WHY_NOT,
                         ids=[f"{w}-{i}" for i, (w, _, _)
                              in enumerate(WHY_NOT)])
def test_the_planner_declines_by_name(one_device, why, table, config):
    """Every reason but the mesh, the layout and the rows, each reached
    by a shape, a dtype or an existing config field alone, on the node
    and on the stamped plan's record."""
    config = MatrelConfig(**{"pallas_interpret": True, **config})
    x = _described(one_device, table.get("shape", (LONG + 8, 328)),
                   table.get("dtype", jnp.float32))
    plan = planner.annotate_strategies(_gram_of(x, table.get("side", "AtA")),
                                       one_device, config)
    facts = planner.gram_kernel_plan(plan, one_device, config)
    assert facts == {"one_read": False, "rider": 0, "tile_rows": 0,
                     "tiles": [], "why_not": why}
    (rec,) = planner.hbm_report(plan)
    assert rec["gram_kernel"] == facts
    if planner.long_gram(plan, one_device, config) is not None:
        # the loop multiplies it: its own count of block products
        assert rec["gram_tiles"] == list(strategies.gram_tiles(plan.shape[0]))
    else:
        assert "gram_tiles" not in rec


def test_the_planner_engages_by_what_it_sees(one_device):
    config = MatrelConfig(pallas_interpret=True)
    x = _described(one_device, (LONG + 8, 1000))
    plan = planner.annotate_strategies(_gram_of(x), one_device, config)
    (rec,) = planner.hbm_report(plan)
    assert rec["gram_kernel"] == {"one_read": True, "rider": 0,
                                  "tile_rows": 2048, "tiles": [36, 64]}
    assert rec["gram_tiles"] == [36, 64]
    # a short Gram, and a product that is none, are nobody's
    short = _gram_of(_described(one_device, (LONG - 8, 1000)))
    other = E.matmul(E.transpose(x), _described(one_device, (LONG + 8, 1000)))
    for node in (short, other):
        plan = planner.annotate_strategies(node, one_device, config)
        assert planner.gram_kernel_plan(plan, one_device, config) is None
        assert "gram_kernel" not in planner.hbm_report(plan)[0]


def test_a_table_that_lies_by_rows_declines_on_the_chip(one_device,
                                                        monkeypatch):
    """On the chip a table whose long dimension is not on the lanes
    would be copied by the kernel's transpose (the loop multiplies it in
    place in either layout): the planner asks the array how it lies.
    Here the CPU's arrays always lie by rows, and a computed operand has
    no array to ask."""
    from matrel_tpu import config as config_lib
    monkeypatch.setattr(config_lib, "on_tpu", lambda: True)
    x = E.leaf(BlockMatrix.from_array(
        jnp.zeros((LONG + 8, 328), jnp.float32), (LONG + 8, 328),
        one_device, P(None, None)))
    for base in (x, E.elemwise("mul", x, x)):
        node = planner.annotate_strategies(_gram_of(base), one_device,
                                           MatrelConfig())
        facts = planner.gram_kernel_plan(node, one_device, MatrelConfig())
        assert facts["why_not"] == "layout" and not facts["one_read"]


def test_a_table_shorter_than_a_lane_chunk_declines(one_device,
                                                    monkeypatch):
    monkeypatch.setattr(planner, "LONG_CONTRACTION", 64)
    config = MatrelConfig(pallas_interpret=True)
    node = planner.annotate_strategies(
        _gram_of(_described(one_device, (100, 328))), one_device, config)
    assert planner.gram_kernel_plan(node, one_device, config)["why_not"] \
        == "rows"


# -- the regression through the session ---------------------------------------


@pytest.fixture(scope="module")
def regression():
    rng = np.random.default_rng(5)
    n, k = LONG + 2000, 328             # 64 whole tiles and 976 rows
    x = rng.uniform(-1.0, 1.0, (n, k)).astype(np.float32)
    theta = rng.standard_normal((k, 1)).astype(np.float32)
    y = (x @ theta + 0.1 * rng.standard_normal((n, 1))).astype(np.float32)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    return {"X": x, "y": y}, np.linalg.solve(x64.T @ x64, x64.T @ y64)


def test_the_regression_reads_the_table_once(one_device, regression):
    """``inv(t(X) * X) * t(X) * y``: ONE ``pallas_call`` over X, t(X) *
    y inside it (no loop over panels is left but the tail's), the
    plan's record and ``last_plan()`` say so."""
    tables, theta = regression
    sess = session_of(one_device, tables, pallas_interpret=True)
    expr = sess.sql("inv(t(X) * X) * t(X) * y")
    got = sess.compute(expr).to_numpy()
    assert rel(got, theta) < 5e-6
    said = sess.last_plan()
    assert said["executors"] == ["pallas_gram", "xla"]
    gram, rider, _ = said["products"]
    assert gram["gram_kernel"] == {"one_read": True, "rider": 1,
                                   "tile_rows": 2048, "tiles": [6, 9]}
    assert gram["gram_tiles"] == [6, 9] and gram["gram_rides"] == 1
    assert rider["rides_gram"] is True and "gram_kernel" not in rider
    plan = sess.compile(expr)
    text = plan.jitted.lower(*[leaf.attrs["matrix"].data
                               for leaf in plan.leaf_order]).as_text()
    n = tables["X"].shape[0]
    head = n // 2048 * 2048
    assert f"tensor<{n - head}x328xf32>" in text     # the tail's slice
    assert f"tensor<{strategies.ACC_PANEL_ROWS}x" not in text


@pytest.mark.parametrize("config,why", [
    (dict(), "pallas_off"),
    (dict(pallas_interpret=True, matmul_precision="high"),
     "matmul_precision")])
def test_a_declined_gram_says_why_and_runs_the_loop(one_device, regression,
                                                    config, why):
    tables, theta = regression
    sess = session_of(one_device, tables, **config)
    got = sess.compute(sess.sql("inv(t(X) * X) * t(X) * y")).to_numpy()
    assert rel(got, theta) < (5e-6 if why == "pallas_off" else 1e-3)
    said = sess.last_plan()
    assert "pallas_gram" not in said["executors"]
    gram = said["products"][0]
    assert gram["gram_kernel"]["why_not"] == why
    assert not gram["gram_kernel"]["one_read"]
    if why == "pallas_off":
        assert gram["gram_tiles"] == list(strategies.gram_tiles(328))


def test_a_gram_alone_and_a_default_precision_gram(one_device, regression):
    """No rider: the kernel without ``y``; and the configuration's
    precision is the kernel's (``default``: one pass, here the CPU's
    float32 either way)."""
    tables, _ = regression
    x64 = tables["X"].astype(np.float64)
    for precision in ("highest", "default"):
        sess = session_of(one_device, tables, pallas_interpret=True,
                          matmul_precision=precision)
        got = sess.compute(sess.sql("t(X) * X")).to_numpy()
        assert rel(got, x64.T @ x64) < 2e-6
        assert (got == got.T).all()
        (gram,) = sess.last_plan()["products"]
        assert gram["gram_kernel"]["one_read"] and "gram_rides" not in gram


def test_the_mesh_keeps_the_loop_and_its_stamps(mesh_square, regression):
    """``why_not`` mesh: by rows over the 2 x 2 mesh the Gram is the
    loop a device at a time, its stamps to the letter (cell
    ``linreg_10m_2x2`` compares them at limit 0)."""
    tables, theta = regression
    n = tables["X"].shape[0] // 4 * 4
    tables = {name: arr[:n] for name, arr in tables.items()}
    sess = session_of(mesh_square, tables, spec=P(("x", "y"), None),
                      pallas_interpret=True)
    got = sess.compute(sess.sql("inv(t(X) * X) * t(X) * y")).to_numpy()
    assert rel(got, theta) < 1e-3       # the tables lost two rows
    said = sess.last_plan()
    assert "pallas_gram" not in said["executors"]
    gram = said["products"][0]
    assert gram["gram_kernel"] == {"one_read": False, "rider": 0,
                                   "tile_rows": 0, "tiles": [],
                                   "why_not": "mesh"}
    assert (gram["gram_tiles"], gram["gram_rides"], gram["devices"],
            gram["rows_a_device"]) == (list(strategies.gram_tiles(328)), 1,
                                       4, n // 4)


def test_the_lowering_leaves_a_cold_record(one_device, regression):
    from matrel_tpu.obs import trace as trace_lib
    tables, _ = regression
    mark = max((r["span_id"] for r in trace_lib.cold_spans()), default=0)
    sess = session_of(one_device, tables, pallas_interpret=True)
    sess.compile(sess.sql("inv(t(X) * X) * t(X) * y"))
    (rec,) = [r for r in trace_lib.cold_spans()
              if r["span_id"] > mark and r["name"] == "gram.plan"]
    assert rec["attrs"] == {"hit": False, "one_read": True, "rider": 1,
                            "tile_rows": 2048, "tiles": [6, 9]}


# -- the programs it must leave alone -----------------------------------------


def _zeros(mesh, spec, shapes):
    return {name: BlockMatrix.from_array(jnp.zeros(shape, jnp.float32),
                                         shape, mesh, spec)
            for name, shape in shapes.items()}


def _ratings(shape, entries=4000):
    fixed = np.random.default_rng(7)
    return COOMatrix.from_edges(
        fixed.integers(0, shape[0], entries),
        fixed.integers(0, shape[1], entries),
        fixed.integers(1, 6, entries).astype(np.float32), shape=shape)


ROWS = LONG + 8192          # a long contraction: the panelled lowerings run
CG = {"X": (ROWS, 328), "y": (ROWS, 1), "p": (328, 1), "lam": (1, 1)}
NMF = {"W": (ROWS, 128), "H": (128, 512)}
PARENTS_PROGRAMS = [
    ("linreg_10m_2x2", "inv(t(X) * X) * t(X) * y",
     "f8e605b0bf79782297bc430a8db4d93f"
     "4ec77f90531b26a113d3d50194740bb6"),
    ("linregcg_10m_1c", "t(X) * y",
     "f4354f2306f94b4b5793ec766199ff53"
     "04dbcd7d6592cadb50e68e59f6db5484"),
    ("linregcg_10m_1c", "t(X) * (X * p) + p * lam",
     "68a46edf83cab47e186f344979288bc4"
     "e23520407ef209755785597f0e1b0c83"),
    ("gnmf_netflix_r128_1c", "H .* (t(W) * V) / (t(W) * W * H)",
     "bc29c4123edb43a466e943ccd775cac0"
     "9e9b1a72b68eb6ab9cc995fb3d1c4588"),
    ("gnmf_netflix_r128_1c", "W .* (V * t(H)) / (W * H * t(H))",
     "613ba0de03f0e6921c5b621cc4bb0db4"
     "6fd7e0957cd2929100d6770980ad9f03"),
    ("pnmf_netflix_r128_1c", "H .* (t(W) * (V / (W * H))) / t(colsum(W))",
     "054d78c8f3a1bf10c0f002d79db436ae"
     "1b20e344cede2fbbec0e7f05baf5dc0b")]


def parents_program(cell, sql, one_device, mesh_square):
    """(plan, SHA-256 of the program lowered for the chip) of one
    statement of a cell whose Grams the kernel must not take: X by rows
    over the 2 x 2 mesh (``mesh``; 328 columns: one device would engage),
    LinearRegCG's statements (no Gram), the NMF updates' ``t(W) * W`` at
    rank 128 (``columns``) — the one-device sessions with Pallas
    interpreted, so that nothing but the planner's verdict declines."""
    if cell == "linreg_10m_2x2":
        sess = MatrelSession(mesh=mesh_square, config=MatrelConfig())
        tables = _zeros(mesh_square, P(("x", "y"), None),
                        {"X": CG["X"], "y": CG["y"]})
    else:
        sess = MatrelSession(mesh=one_device, config=MatrelConfig(
            pallas_interpret=True))
        tables = _zeros(one_device, P(None, None),
                        CG if cell == "linregcg_10m_1c" else NMF)
        if cell != "linregcg_10m_1c":
            tables["V"] = _ratings((ROWS, 512))
    for name, table in tables.items():
        sess.register(name, table)
    plan = sess.compile(sess.sql(sql))
    args = [leaf.attrs["matrix"].data for leaf in plan.leaf_order] \
        + list(plan.extra_args)
    text = plan.jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return plan, hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("cell,sql,want", PARENTS_PROGRAMS,
                         ids=[f"{c}-{i}" for i, (c, _, _)
                              in enumerate(PARENTS_PROGRAMS)])
def test_the_other_cells_lower_to_the_parents_programs(
        cell, sql, want, one_device, mesh_square):
    """The mesh regression, LinearRegCG's statements and the NMF
    updates lower for the chip to the text the parent commit (6a603b1)
    lowers them to, by SHA-256 recorded there in this container's jax:
    where the kernel declines, the loop is the parent's."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    plan, got = parents_program(cell, sql, one_device, mesh_square)
    assert got == want
    assert "pallas_gram" not in plan.meta["executors"]
    grams = [p["gram_kernel"] for p in plan.meta.get("products", ())
             if "gram_kernel" in p]
    # the long Grams: the mesh's t(X) * X, GNMF's t(W) * W at rank 128
    assert [g["why_not"] for g in grams] == (
        ["mesh"] if cell == "linreg_10m_2x2"
        else ["columns"] if "t(W) * W" in sql else [])
    if cell == "linreg_10m_2x2":
        gram = plan.meta["products"][0]
        assert (gram["gram_tiles"], gram["gram_rides"], gram["devices"],
                gram["rows_a_device"]) == ([3, 4], 1, 4, ROWS // 4)
