"""``register_delta(kind="rows")`` (PR 56): a batch of rows REPLACES rows
of a dense float32 table on one device in place, and the regression's
views ``t(X) * X`` and ``t(X) * y`` follow from the rows that left and
the rows that came (ir/delta.derive_rows_patch, serve/ivm.py,
executor.rows_update / rows_patch). What the sliding-window deployment
of the benchmark (``fivm_linreg_window_10m``) rests on, at test size."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from matrel_tpu import executor as executor_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import delta as delta_lib
from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.parallel import planner
from matrel_tpu.session import MatrelSession

N, K, C = 2048, 32, 128             # a ring of 16 slots
THETA = "inv(t(X) * X) * t(X) * y"
VIEWS = ("t(X) * X", "t(X) * y")


@pytest.fixture(scope="module")
def one_device():
    return mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])


class Window:
    """A session over X (N x K) and y with both views asked once, the
    host's own copy of the two tables, and ticks of the ring."""

    def __init__(self, mesh, seed, **config):
        self.rng = np.random.default_rng(seed)
        self.theta_star = self.rng.standard_normal((K, 1)) \
            .astype(np.float32)
        self.x, self.y = self.batch(N)
        config.setdefault("result_cache_max_bytes", 1 << 26)
        self.sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
        self.tables = {name: BlockMatrix.from_numpy(arr.copy(), mesh=mesh)
                       for name, arr in (("X", self.x), ("y", self.y))}
        for name, table in self.tables.items():
            self.sess.register(name, table)
        for text in VIEWS:
            self.sess.compute(self.sess.sql(text))
        self.ticks = 0

    def batch(self, rows):
        x = self.rng.uniform(-1, 1, (rows, K)).astype(np.float32)
        return x, (x @ self.theta_star + 0.1 * self.rng.standard_normal(
            (rows, 1))).astype(np.float32)

    def write(self, ids=None):
        if ids is None:
            at = self.ticks % (N // C) * C
            ids = np.arange(at, at + C)
        xb, yb = self.batch(len(ids))
        self.x[ids], self.y[ids] = xb, yb
        self.ticks += 1
        return [self.sess.register_delta(name, (ids, rows), kind="rows")
                for name, rows in (("X", xb), ("y", yb))]

    def read(self, text):
        return self.sess.compute(self.sess.sql(text)).to_numpy()

    def want(self):
        x, y = self.x.astype(np.float64), self.y.astype(np.float64)
        gram, rhs = x.T @ x, x.T @ y
        return gram, rhs, np.linalg.solve(gram, rhs)


def _err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- REPLACE, bit for bit, in place -------------------------------------------


@pytest.mark.parametrize("ids", [np.arange(256, 384),
                                 np.array([5, 1999, 17, 1024, 3])],
                         ids=["one-run", "scattered"])
def test_rows_replaces_bit_for_bit_and_in_place(one_device, ids):
    w = Window(one_device, 1)
    table = w.sess.table("X")
    said = w.write(ids)
    assert all(s["in_place"] and s["delta_kind"] == "rows" for s in said)
    assert said[0]["rows"] == len(ids)
    # the registered BlockMatrix is the object it was, with the new array
    assert w.sess.table("X") is table is w.tables["X"]
    np.testing.assert_array_equal(table.to_numpy(), w.x)
    np.testing.assert_array_equal(w.sess.table("y").to_numpy(), w.y)
    assert said[0]["upload_bytes"] == len(ids) * K * 4


def test_a_delta_without_a_cache_still_writes_in_place(one_device):
    w = Window(one_device, 2, result_cache_max_bytes=0)
    said = w.write()
    assert [s["examined"] for s in said] == [0, 0]
    np.testing.assert_array_equal(w.sess.table("X").to_numpy(), w.x)
    assert _err(w.read(THETA), w.want()[2]) < 2e-6


def test_rows_payloads_are_checked_by_name(one_device):
    w = Window(one_device, 3)
    xb = np.zeros((4, K), np.float32)
    with pytest.raises(ValueError, match="ids repeat"):
        w.sess.register_delta("X", ([1, 7, 1, 9], xb), kind="rows")
    with pytest.raises(ValueError, match="out of bounds"):
        w.sess.register_delta("X", ([1, 2, 3, N], xb), kind="rows")
    with pytest.raises(ValueError, match=r"values \(c, 32\)"):
        w.sess.register_delta("X", ([1, 2, 3], xb), kind="rows")
    with pytest.raises(ValueError, match="unknown delta kind 'row'"):
        w.sess.register_delta("X", ([1, 2, 3, 4], xb), kind="row")
    # auto: ids (c,) with values (c, m) can only mean rows
    d = delta_lib.as_delta((np.arange(4), xb), w.sess.table("X"))
    assert (d.kind, d.start, d.signature()) == (
        "rows", 0, ("rows", (N, K), 4, True))
    with pytest.raises(delta_lib.DeltaIneligible, match="replaces rows"):
        d.to_dense_numpy()


# -- the views follow ----------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 2147483999])
def test_views_and_theta_equal_a_recompute_over_turnovers(one_device, seed):
    """Three turnovers of the ring: every read of every tick within a
    float32 product's distance of float64 over the table as it stands,
    and no worse at the end than at the start (the views are carried as
    two words)."""
    w = Window(one_device, seed)
    errs = []
    for _ in range(3 * N // C):
        said = w.write()
        assert all(s["patched"] >= 1 and s["killed"] == s["no_rule"]
                   for s in said)
        theta, xty, gram = (w.read(THETA), w.read("t(X) * y"),
                            w.read("t(X) * X"))
        want = w.want()
        errs.append((_err(gram, want[0]), _err(xty, want[1]),
                     _err(theta, want[2])))
    assert np.max(errs) < 2e-6
    assert np.max(errs[-8:]) < 3 * max(np.max(errs[:8]), 2e-7)
    assert w.sess.result_cache_info()["patched"] == 3 * (3 * N // C)


def test_the_compensated_view_beats_plain_accumulation(one_device):
    """The second word is what keeps thousands of ``view += small -
    small`` from drifting: the same corrections added in plain float32
    end further from float64 than the pair does."""
    rng = np.random.default_rng(5)
    patch = executor_lib.rows_patch("gram", True, MatrelConfig())
    base = rng.uniform(-1, 1, (4096, K)).astype(np.float32)
    exact = base.astype(np.float64).T @ base.astype(np.float64)
    hi = jnp.asarray(exact, jnp.float32)
    lo = jnp.zeros_like(hi)
    plain = np.asarray(hi)
    at = np.int32(0)
    for _ in range(300):
        new = rng.uniform(-1, 1, (16, K)).astype(np.float32)
        old = rng.uniform(-1, 1, (16, K)).astype(np.float32)
        exact += new.astype(np.float64).T @ new - old.astype(
            np.float64).T @ old
        plain = plain + (new.T @ new) - (old.T @ old)
        hi, lo = patch(hi, lo, jnp.asarray(new), jnp.asarray(old),
                       jnp.asarray(new), at)
    assert _err(np.asarray(hi), exact) < 0.5 * _err(plain, exact)
    assert _err(np.asarray(hi, np.float64) + np.asarray(lo), exact) < 2e-7


def test_theta_is_a_solve_over_both_views_and_no_table(one_device):
    w = Window(one_device, 4)
    w.write()
    w.read(THETA)
    said = w.sess.last_plan()
    assert (said["views_hit"], said["table_pass"], said["root_hit"]) \
        == (2, False, False)
    plan = w.sess._last_plan
    assert all("result_cache" in leaf.attrs for leaf in plan.leaf_order)
    assert sorted(leaf.shape for leaf in plan.leaf_order) \
        == [(K, 1), (K, K)]
    kinds = set()

    def walk(n):
        kinds.add(n.kind)
        for c in n.children:
            walk(c)

    walk(plan.optimized)
    assert "solve" in kinds and "matmul" not in kinds
    # the second statement is the cached view itself
    w.read("t(X) * y")
    assert w.sess.last_plan()["root_hit"] is True
    # with no view of t(X) * y in the cache the product is the table's
    fresh = Window(one_device, 4)
    fresh.sess._result_cache.clear()
    fresh.sess.compute(fresh.sess.sql("t(X) * X"))
    fresh.read(THETA)
    assert fresh.sess.last_plan()["views_hit"] == 1
    assert fresh.sess.last_plan()["table_pass"] is True


def test_a_chain_finds_a_cached_sub_product_wherever_it_stands(one_device):
    """``_rc_chain``: any run of a product chain's factors that a
    statement cached, not only the parser's own left brackets."""
    rng = np.random.default_rng(6)
    sess = MatrelSession(mesh=one_device, config=MatrelConfig(
        result_cache_max_bytes=1 << 26))
    for name, shape in (("A", (24, 16)), ("B", (16, 40)), ("D", (40, 8)),
                        ("E", (8, 12))):
        sess.register(name, BlockMatrix.from_numpy(
            rng.uniform(-1, 1, shape).astype(np.float32), mesh=one_device))
    want = np.linalg.multi_dot([sess.table(n).to_numpy() for n in "ABDE"])
    sess.compute(sess.sql("B * D"))
    got = sess.compute(sess.sql("A * B * D * E")).to_numpy()
    assert sess.last_plan()["views_hit"] == 1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    sess.compute(sess.sql("B * D * E"))
    got = sess.compute(sess.sql("t(B) * B * D * E")).to_numpy()
    # the longer run wins: B * D * E, not B * D
    assert sess.last_plan()["views_hit"] == 1
    assert sorted(leaf.shape for leaf in sess._last_plan.leaf_order) \
        == [(16, 12), (16, 40)]
    b = sess.table("B").to_numpy()
    np.testing.assert_allclose(
        got, b.T @ np.linalg.multi_dot(
            [sess.table(n).to_numpy() for n in "BDE"]),
        rtol=1e-4, atol=1e-4)


# -- the bound and the re-base ------------------------------------------------


def test_the_bound_composes_and_a_forced_tiny_one_rebases(one_device,
                                                          monkeypatch):
    w = Window(one_device, 7)
    step = delta_lib.rows_patch_bound(C, N)
    assert step == pytest.approx(2 * 2.0 ** -20 * C / N, rel=1e-3)
    said = w.write()
    assert said[0]["err_bound"] == pytest.approx(step)
    assert said[1]["err_bound"] == pytest.approx(2 * step)   # t(X) * y
    assert (said[0]["rebased"], said[0]["table_passes"]) == (0, 0)
    ents = [e for _k, e in w.sess._result_cache.items_snapshot()]
    assert {e.delta_rule for e in ents} == {"rows"}
    assert all(e.delta_gen >= 1 and e.ivm_id is not None for e in ents)

    monkeypatch.setattr(delta_lib, "ROWS_REBASE_BOUND", 1e-12)
    tiny = Window(one_device, 7)
    said = tiny.write()
    assert [s["rebased"] for s in said] == [2, 1]
    assert [s["table_passes"] for s in said] == [2, 1]
    assert said[0]["patched"] == 2 and said[0]["reused_plans"] == 0
    assert tiny.sess._delta_plane.stats["rebases"] == 3
    assert said[1]["err_bound"] == 0.0          # a fresh execution's
    want = tiny.want()
    assert _err(tiny.read(THETA), want[2]) < 2e-6
    assert _err(tiny.read("t(X) * y"), want[1]) < 2e-6


def test_mv113_proves_the_patched_views(one_device):
    from matrel_tpu.analysis import delta_pass
    w = Window(one_device, 8)
    for _ in range(5):
        w.write()
    assert delta_pass.verify_patched_entries(w.sess) == []


# -- nothing grows, nothing compiles ------------------------------------------


def test_nothing_grows_over_200_ticks_and_a_second_tick_compiles_nothing(
        one_device, monkeypatch):
    monkeypatch.setattr(delta_lib, "ROWS_REBASE_BOUND", 1.0)  # no re-base
    w = Window(one_device, 9)

    def sizes():
        rc = w.sess._result_cache
        plane = w.sess._delta_plane
        keys = [k for k, _e in rc.items_snapshot()]
        return {"entries": len(keys), "plans": len(w.sess._plan_cache),
                "stale": rc.info()["stale_entries"],
                "views": len(plane._rows_views),
                "programs": sum(len(v.programs)
                                for v in plane._rows_views.values()),
                "legacy_programs": len(plane._programs),
                "jitted": len(executor_lib._ROWS_PROGRAMS),
                "bytes": rc.info()["bytes"],
                "key_chars": max(len(k) for k in keys)
                - len(str(w.sess._delta_gen))}

    def compiled():
        return (w.sess._delta_plane.stats["patch_compiles"],
                sum(fn._cache_size()
                    for fn in executor_lib._ROWS_PROGRAMS.values()),
                sum(r["name"] == "compile"
                    for r in trace_lib.cold_spans()))

    for _ in range(2):
        w.write(), w.read(THETA), w.read("t(X) * y")
    first, programs = sizes(), compiled()
    assert first["views"] == 2 and first["programs"] == 3
    for _ in range(200):
        said = w.write()
        w.read(THETA), w.read("t(X) * y")
        assert said[0]["reused_plans"] == 2 and said[1]["reused_plans"] == 1
    assert sizes() == first
    assert compiled() == programs
    assert w.sess._delta_plane.stats["patch_reuses"] >= 600
    assert w.sess._delta_gen == 404
    assert _err(w.read(THETA), w.want()[2]) < 2e-6


# -- what is refused by name ----------------------------------------------------


def test_deltas_that_would_materialise_a_table_are_refused_by_name(
        one_device, monkeypatch):
    monkeypatch.setattr(delta_lib, "MATERIALIZE_MAX_BYTES", N * K * 4 - 1)
    w = Window(one_device, 10)
    cfg = w.sess.config
    with pytest.raises(delta_lib.DeltaTooLarge,
                       match="MATERIALIZE_MAX_BYTES"):
        w.sess.register_delta("X", np.ones((N, K), np.float32),
                              kind="dense")
    coo = delta_lib.as_delta((np.arange(600), np.arange(600) % K,
                              np.ones(600)), w.sess.table("X"), "coo")
    with pytest.raises(delta_lib.DeltaTooLarge, match="dense form"):
        coo.to_dense_numpy()
    with pytest.raises(delta_lib.DeltaTooLarge, match="one-hot factors"):
        coo.factors(one_device, cfg.replace(delta_rank_max=1 << 20))
    # nothing was rebound, nothing killed
    np.testing.assert_array_equal(w.sess.table("X").to_numpy(), w.x)
    assert w.sess.result_cache_info()["entries"] == 2
    # a coo batch past the budget still registers: its patches are
    # refused one by one (logged) and the views take the kill
    said = w.sess.register_delta(
        "X", (np.arange(600), np.arange(600) % K, np.ones(600)), kind="coo")
    assert (said["patched"], said["killed"]) == (0, 2)
    # and a copy of a table the device cannot hold twice is refused
    small = Window(one_device, 10, hbm_budget_bytes=int(1.5 * N * K * 4))
    with pytest.raises(planner.PlanMemoryError, match="corrected copy"):
        small.sess.register_delta("X", ([3], [5], [1.0]), kind="coo")
    with pytest.raises(planner.PlanMemoryError,
                       match="rows delta refused before anything was "
                       "uploaded"):
        big = np.zeros((N // 2, K), np.float32)
        small.sess.register_delta("X", (np.arange(N // 2), big),
                                  kind="rows")
    np.testing.assert_array_equal(small.sess.table("X").to_numpy(), small.x)


def test_rows_on_a_mesh_copy_and_kill(mesh_square):
    """What the in-place programs do not take does what a rebind does:
    the name is rebound to a corrected copy and its dependents die."""
    rng = np.random.default_rng(12)
    x = rng.uniform(-1, 1, (256, 16)).astype(np.float32)
    sess = MatrelSession(mesh=mesh_square, config=MatrelConfig(
        result_cache_max_bytes=1 << 26))
    old = BlockMatrix.from_numpy(x, mesh=mesh_square)
    sess.register("X", old)
    sess.compute(sess.sql("t(X) * X"))
    rows = rng.uniform(-1, 1, (8, 16)).astype(np.float32)
    said = sess.register_delta("X", (np.arange(40, 48), rows), kind="rows")
    x[40:48] = rows
    assert (said["in_place"], said["patched"], said["killed"]) \
        == (False, 0, 1)
    assert sess.table("X") is not old
    np.testing.assert_array_equal(sess.table("X").to_numpy(), x)
    got = sess.compute(sess.sql("t(X) * X")).to_numpy()
    np.testing.assert_allclose(got, x.T @ x, rtol=1e-4, atol=1e-4)


def test_the_reckoning_holds_one_table(one_device):
    w = Window(one_device, 13)
    said = w.write()
    table, col = N * K * 4, N * 4
    views = 3 * (K * K * 4 + K * 4)
    assert said[0]["hbm_plan_bytes"] == table + col + 2 * C * K * 4 + views
    assert said[1]["hbm_plan_bytes"] \
        == table + col + 2 * C * 4 + 3 * K * 4
    plan = planner.rows_delta_plan(w.tables["X"], C, [], [], one_device,
                                   w.sess.config)
    assert plan == {"hbm_plan_bytes": table + 2 * C * K * 4,
                    "table_bytes": table}


# -- spans ----------------------------------------------------------------------


def test_register_delta_has_its_spans(one_device):
    w = Window(one_device, 14, obs_flight_recorder=256)
    w.write()
    w.sess._flight.clear() if hasattr(w.sess._flight, "clear") else None
    before = len(w.sess._flight.snapshot())
    w.write()
    w.read(THETA)
    spans = [r for r in w.sess._flight.snapshot()[before:]
             if r.get("kind", "span") == "span" or "name" in r]
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r.get("attrs", {}))
    assert len(by_name["delta"]) == 2
    assert by_name["delta"][0]["patched"] == 2
    assert by_name["delta"][0]["in_place"] is True
    assert [a["bytes"] for a in by_name["delta.upload"]] \
        == [C * K * 4, C * 4]
    assert all(a["in_place"] and a["rows"] == C and a["hbm_plan_bytes"]
               for a in by_name["delta.update"])
    patches = by_name["delta.patch"]
    assert sorted(a["form"] for a in patches) == ["gram", "left", "right"]
    assert all(a["rule"] == "rows" and a["reused"] is True
               and a["table_pass"] is False and a["err_bound"] > 0
               for a in patches)
    assert "delta.rebase" not in by_name
    probe = by_name["rc.probe"][-1]
    assert (probe["views_hit"], probe["table_pass"]) == (2, False)


# -- the programs of the cells that are there ---------------------------------

REGRESSION = {"X": (139264, 40), "y": (139264, 1)}    # a long contraction
SOLVER = dict(REGRESSION, p=(40, 1), lam=(1, 1))
CATALOG = {"M": (512, 512), "N": (512, 512), "A": (1000, 100),
           "B": (100, 1000), "C": (1000, 100)}
PARENTS_PROGRAMS = [
    ("linreg_10m_1c", "inv(t(X) * X) * t(X) * y",
     "1cd6cb6ebca6d76b68611276daf7dda615c63024f65411d1b7a0fe4880c79872"),
    ("linreg_10m_2x2", "inv(t(X) * X) * t(X) * y",
     "462979b15f4ea60076d2243cc3ac8f559b0c4ac7de0dd114719dd94591f50e96"),
    ("linregcg_10m_1c", "t(X) * (X * p) + p * lam",
     "cc7ad522748c18b4e3a8f0d251b0f06caefbe4d953a5501f35227a5ab6c0ff27"),
    ("linregcg_10m_1c", "t(X) * y",
     "770e22c342a2ddcfef010ae8766bd40b51fe7c922675d7e188afe3b033121962"),
    ("relational_small_1c", "rowsum(M * N)",
     "f70e0fa8e6715d1bc72bada78366662bce065fff4ee0ac626807e746c7351dd2"),
    ("relational_small_1c", "rowsum(A * B * C)",
     "cfa3d83e1b23fa75ff45d733b0b6fff89ac74d63b3f82e8102df59ad30720a47"),
    ("relational_small_1c", 'SELECT rowcount(select(M, "v > 0.9")) FROM M',
     "dd5ead5450d21a20b4ea27c8cb9ad8a20b7f5b2e49669fbcc84690a519462d83")]


@pytest.mark.parametrize("cell,sql,want", PARENTS_PROGRAMS,
                         ids=[f"{c}-{i}" for i, (c, _, _)
                              in enumerate(PARENTS_PROGRAMS)])
def test_the_cells_that_are_there_lower_to_the_parents_programs(
        cell, sql, want, one_device, mesh_square):
    """The three regression cells' statements and the dense catalog's
    queries, the result cache off as their configurations have it,
    lower for the chip to the text the parent commit (55e3681) lowers
    them to, by SHA-256 recorded there in this container's jax: the
    chain consult, the delta plane and the rows programs are not on
    their path."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    on_mesh = cell == "linreg_10m_2x2"
    mesh = mesh_square if on_mesh else one_device
    spec = P(("x", "y"), None) if on_mesh else P(None, None)
    sess = MatrelSession(mesh=mesh, config=MatrelConfig())
    shapes = {"relational_small_1c": CATALOG,
              "linregcg_10m_1c": SOLVER}.get(cell, REGRESSION)
    for name, shape in shapes.items():
        sess.register(name, BlockMatrix.from_array(
            jnp.zeros(shape, jnp.float32), shape, mesh, spec))
    plan = sess.compile(sess.sql(sql))
    args = [leaf.attrs["matrix"].data for leaf in plan.leaf_order] \
        + list(plan.extra_args)
    text = plan.jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want
    assert sess._delta_plane is None and sess.last_plan() == {}
