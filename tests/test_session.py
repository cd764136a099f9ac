"""Session hygiene: plan-cache LRU eviction bounds and builder
config-conflict warnings (long-lived sessions must not grow HBM pins
without bound, and a second builder must not silently lose its
settings)."""

import logging

import pytest

import numpy as np

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.session import MatrelSession, reset_session


class TestPlanCacheEviction:
    def test_count_bound_evicts_lru(self, mesh8, rng):
        sess = MatrelSession(
            mesh=mesh8, config=MatrelConfig(plan_cache_max_plans=3))
        # a shape each: a new array of a known shape is no new plan (a
        # template answers it)
        mats = [BlockMatrix.from_numpy(
            rng.standard_normal((8, 8 * (i + 1))).astype(np.float32),
            mesh=mesh8) for i in range(5)]
        for m in mats:
            sess.compute(m.expr().t())
        assert sess.plan_cache_info()["plans"] == 3
        keys_before = list(sess._plan_cache)
        # the OLDEST (mats[0]) was evicted: compiling it again inserts
        # a fresh entry and evicts the current LRU
        sess.compile(mats[0].expr().t())
        keys_after = list(sess._plan_cache)
        assert keys_after[-1] not in keys_before   # new entry appended
        assert keys_before[0] not in keys_after    # LRU evicted
        assert sess.plan_cache_info()["plans"] == 3

    def test_a_template_outlives_its_plans_eviction(self, mesh8, rng):
        # the NMF cells stand on it: their two updates' plans each count
        # the shared 4 GB slab against plan_cache_max_bytes and evict
        # one another, and the templates go on answering
        sess = MatrelSession(
            mesh=mesh8, config=MatrelConfig(plan_cache_max_plans=1))

        def t_of(cols):
            return BlockMatrix.from_numpy(
                rng.standard_normal((8, cols)).astype(np.float32),
                mesh=mesh8).expr().t()
        sess.compute(t_of(8))
        sess.compute(t_of(16))          # evicts 8's plan
        assert sess.plan_cache_info()["evicted"] == 1
        for cols in (8, 16, 8):
            sess.compute(t_of(cols))
            assert sess.last_plan()["hit"] is True
        assert sess.plan_cache_info() == {
            "plans": 1, "hoisted_bytes": 0, "evicted": 1}
        assert sess.mqo_info()["template_hits"] == 3

    def test_lru_order_on_hit(self, mesh8, rng):
        sess = MatrelSession(
            mesh=mesh8, config=MatrelConfig(plan_cache_max_plans=2))
        a = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        b = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        c = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        pa = sess.compile(a.expr().t())
        sess.compile(b.expr().t())
        assert sess.compile(a.expr().t()) is pa    # hit refreshes a
        sess.compile(c.expr().t())                 # evicts b (LRU)
        assert sess.compile(a.expr().t()) is pa    # a survived
        assert sess.plan_cache_info()["plans"] == 2

    def test_byte_budget_evicts_hoisted_payloads(self, mesh8, rng):
        # COO plans hoist their table payloads into extra_args; a tiny
        # byte budget must evict old plans once exceeded
        from matrel_tpu.core.coo import COOMatrix
        sess = MatrelSession(
            mesh=mesh8, config=MatrelConfig(plan_cache_max_bytes=1,
                                            plan_cache_max_plans=64))
        x = BlockMatrix.from_numpy(
            rng.standard_normal((2000, 2)).astype(np.float32),
            mesh=mesh8)
        plans = []
        for seed in range(3):
            # ≥1 MB of plan tables so the payloads actually hoist
            m = 400_000
            r = rng.integers(0, 2000, m)
            c = rng.integers(0, 2000, m)
            v = rng.standard_normal(m).astype(np.float32)
            A = COOMatrix.from_edges(r, c, v, shape=(2000, 2000))
            plans.append(sess.compile(A.multiply(x.expr())))
        assert any(p.extra_args for p in plans), \
            "fixture too small: nothing hoisted"
        info = sess.plan_cache_info()
        # with a 1-byte budget only the newest plan may stay
        assert info["plans"] == 1
        # sole-plan exception: the just-inserted plan is never evicted
        assert list(sess._plan_cache.values())[0] is plans[-1]

    def test_sole_plan_never_evicted(self, mesh8, rng):
        from matrel_tpu.core.coo import COOMatrix
        sess = MatrelSession(
            mesh=mesh8, config=MatrelConfig(plan_cache_max_bytes=1))
        r = rng.integers(0, 500, 20_000)
        c = rng.integers(0, 500, 20_000)
        A = COOMatrix.from_edges(r, c, shape=(500, 500))
        x = BlockMatrix.from_numpy(
            rng.standard_normal((500, 2)).astype(np.float32), mesh=mesh8)
        p = sess.compile(A.multiply(x.expr()))
        assert sess.compile(A.multiply(x.expr())) is p


class TestBuilderConflicts:
    def test_explicit_config_conflict_warns(self, caplog):
        reset_session()
        s1 = MatrelSession.builder().config(use_pallas=True).get_or_create()
        with caplog.at_level(logging.WARNING, logger="matrel_tpu"):
            s2 = MatrelSession.builder().config(
                use_pallas=False).get_or_create()
        assert s2 is s1
        assert any("ignoring the requested config" in r.message
                   for r in caplog.records)

    def test_default_builder_does_not_warn(self, caplog):
        reset_session()
        MatrelSession.builder().config(block_size=256).get_or_create()
        with caplog.at_level(logging.WARNING, logger="matrel_tpu"):
            MatrelSession.builder().get_or_create()
        assert not [r for r in caplog.records
                    if "ignoring the requested" in r.message]

    def test_mesh_conflict_warns(self, mesh8, mesh4x2, caplog):
        reset_session()
        MatrelSession.builder().mesh(mesh8).get_or_create()
        with caplog.at_level(logging.WARNING, logger="matrel_tpu"):
            MatrelSession.builder().mesh(mesh4x2).get_or_create()
        assert any("ignoring the requested mesh" in r.message
                   for r in caplog.records)

    def test_same_mesh_no_warning(self, mesh8, caplog):
        reset_session()
        MatrelSession.builder().mesh(mesh8).get_or_create()
        with caplog.at_level(logging.WARNING, logger="matrel_tpu"):
            MatrelSession.builder().mesh(mesh8).get_or_create()
        assert not [r for r in caplog.records
                    if "ignoring the requested" in r.message]


def test_iterative_queries_under_aggressive_eviction(mesh8, rng):
    """An iterative workload whose per-step queries exceed the plan
    cache: evicted plans recompile transparently and results stay
    correct across many steps (long-lived-session shape)."""
    sess = MatrelSession(
        mesh=mesh8, config=MatrelConfig(plan_cache_max_plans=2))
    mats = [sess.from_numpy(
        rng.standard_normal((12, 12)).astype(np.float32))
        for _ in range(4)]
    oracles = [m.to_numpy() for m in mats]
    state = np.eye(12, dtype=np.float32)
    S = sess.from_numpy(state)
    for step in range(8):
        m = step % 4                      # cycles past the cache bound
        out = sess.compute(S.expr().multiply(mats[m].expr()))
        want = state @ oracles[m]
        np.testing.assert_allclose(out.to_numpy(), want, rtol=2e-3,
                                   atol=2e-3, err_msg=f"step {step}")
        state = want
        S = sess.from_numpy(state)
    assert sess.plan_cache_info()["plans"] <= 2


class TestPlanCacheCallableKeys:
    """The plan key must distinguish callable attrs (ADVICE r2 high):
    pre-fix, two queries differing only in a predicate/merge callable
    shared one cache entry and the second silently returned the first's
    results."""

    def test_where_predicates_key_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        sess.register("A", sess.from_numpy(a))
        pos = sess.compute(sess.sql("SELECT A WHERE v > 0")).to_numpy()
        neg = sess.compute(sess.sql("SELECT A WHERE v < 0")).to_numpy()
        np.testing.assert_allclose(pos, np.where(a > 0, a, 0), rtol=1e-5)
        np.testing.assert_allclose(neg, np.where(a < 0, a, 0), rtol=1e-5)

    def test_joinvalue_merge_exprs_key_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((4, 4)).astype(np.float32)
        sess.register("A", sess.from_numpy(a))
        add = sess.compute(
            sess.sql("rowsum(joinvalue(A, A, 'x + y'))")).to_numpy()
        sub = sess.compute(
            sess.sql("rowsum(joinvalue(A, A, 'x - y'))")).to_numpy()
        assert not np.allclose(add, sub)

    def test_raw_lambdas_key_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        m = sess.from_numpy(rng.standard_normal((8, 8)).astype(np.float32))
        a = m.to_numpy()
        hi = sess.compute(m.expr().select_value(lambda v: v > 0.5)).to_numpy()
        lo = sess.compute(m.expr().select_value(lambda v: v < -0.5)).to_numpy()
        np.testing.assert_allclose(hi, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(lo, np.where(a < -0.5, a, 0), rtol=1e-5)

    def test_identical_sql_text_still_hits_cache(self, mesh8, rng):
        # correctness must not cost the cache: re-parsing the same query
        # makes a fresh callable, but the attached source key matches
        sess = MatrelSession(mesh=mesh8)
        sess.register("A", sess.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32)))
        p1 = sess.compile(sess.sql("SELECT A WHERE v > 0"))
        p2 = sess.compile(sess.sql("SELECT A WHERE v > 0"))
        assert p1 is p2
        assert sess.plan_cache_info()["plans"] == 1

    def test_selectblocks_predicates_key_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        sess.register("A", sess.from_numpy(a))
        diag = sess.compute(
            sess.sql("selectblocks(A, 'bi == bj', 4)")).to_numpy()
        off = sess.compute(
            sess.sql("selectblocks(A, 'bi != bj', 4)")).to_numpy()
        np.testing.assert_allclose(diag + off, a, rtol=1e-5)
        assert not np.allclose(diag, off)


class TestPlanKeyGlobalsAndPinning:
    """Code-review r3 findings: lambdas reading module globals must key
    by the global's VALUE, and id-keyed objects must stay pinned while
    their plan is cached (CPython address reuse)."""

    def test_global_value_change_keys_differently(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"thr": 0.5}
        f1 = eval("lambda v: v > thr", g)          # noqa: S307 — test fixture
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        g["thr"] = -0.5
        f2 = eval("lambda v: v > thr", g)          # noqa: S307
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)

    def test_cached_plan_pins_keyed_callable(self, mesh8, rng):
        import gc
        import weakref
        sess = MatrelSession(mesh=mesh8)
        m = sess.from_numpy(rng.standard_normal((8, 8)).astype(np.float32))

        def pred(v):
            return v > 0.25

        wr = weakref.ref(pred)
        sess.compile(m.expr().select_value(pred))
        del pred
        gc.collect()
        # while the plan is cached, the callable's id must stay valid
        assert wr() is not None
        sess._plan_cache.clear()
        gc.collect()
        assert wr() is None

    def test_rebound_array_global_keys_and_pins(self, mesh8, rng):
        # review r3: a non-scalar global (numpy array) keys by id and
        # its OLD value must stay pinned after rebinding — the recycled
        # address can otherwise falsely hit the stale plan
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"thr": np.array(0.5, np.float32)}
        f1 = eval("lambda v: v > thr", g)          # noqa: S307
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        old_thr = g["thr"]
        g["thr"] = np.array(-0.5, np.float32)      # rebind the global
        f2 = eval("lambda v: v > thr", g)          # noqa: S307
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)
        # the old value object is pinned by the cached first plan
        pinned = [p for plan in sess._plan_cache.values()
                  for p in plan._cache_pin[1]]
        assert any(p is old_thr for p in pinned)

    def test_nested_lambda_global_keys_differently(self, mesh8, rng):
        # review r3 (confirmed repro): a global read only by a NESTED
        # code object must still enter the fingerprint
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"thr": 0.5}
        make = eval("lambda: (lambda v: (lambda w: w > thr)(v))", g)  # noqa: S307
        r1 = sess.compute(m.expr().select_value(make())).to_numpy()
        g["thr"] = -3.0
        make2 = eval("lambda: (lambda v: (lambda w: w > thr)(v))", g)  # noqa: S307
        r2 = sess.compute(m.expr().select_value(make2())).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -3.0, a, 0), rtol=1e-5)

    def test_custom_repr_default_objects_key_differently(self, mesh8, rng):
        # review r3: default objects with state-independent __repr__
        # must key by identity, not repr
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)

        class Thr:
            def __init__(self, t):
                self.t = t

            def __repr__(self):
                return "<Thr>"

        f1 = lambda v, thr=Thr(0.5): v > thr.t      # noqa: E731
        f2 = lambda v, thr=Thr(-0.5): v > thr.t     # noqa: E731
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)


class TestPlanKeyBoundMethodsAndKwdefaults:
    """Advisor r3 medium: _fn_token omitted __kwdefaults__ and bound-method
    __self__ state, so behaviourally distinct callables collided in the
    plan cache (the second query silently returned the first's result)."""

    def test_bound_method_instance_state_keys_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)

        class Thresh:
            def __init__(self, t):
                self.t = t

            def pred(self, v):
                return v > self.t

        r1 = sess.compute(
            m.expr().select_value(Thresh(16.5).pred)).to_numpy()
        r2 = sess.compute(
            m.expr().select_value(Thresh(0.0).pred)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 16.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > 0.0, a, 0), rtol=1e-5)

    def test_kwonly_defaults_key_separately(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)

        def make(t):
            def pred(v, *, thr=t):
                return v > thr
            return pred

        r1 = sess.compute(m.expr().select_value(make(0.5))).to_numpy()
        r2 = sess.compute(m.expr().select_value(make(-0.5))).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)

    def test_global_list_mutated_in_place_rekeys(self, mesh8, rng):
        # advisor r3 low: a mutable global mutated IN PLACE (same id)
        # must not falsely hit the cached plan — containers key by value
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"thrs": [0.5]}
        f1 = eval("lambda v: v > thrs[0]", g)       # noqa: S307
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        g["thrs"][0] = -0.5                         # in-place, id unchanged
        f2 = eval("lambda v: v > thrs[0]", g)       # noqa: S307
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)

    def test_cyclic_global_container_terminates(self, mesh8, rng):
        # review r4: a self-referential container reachable from a
        # predicate's globals must key finitely (back-edge by pinned id)
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"cfg": {"thr": 0.5}}
        g["cfg"]["self"] = g["cfg"]             # cycle
        f1 = eval("lambda v: v > cfg['thr']", g)   # noqa: S307
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)

    def test_large_dict_mutated_in_place_rekeys(self, mesh8, rng):
        # review r4: no silent size cap — a 65+-entry global dict
        # mutated in place must still re-key by value
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {"thrs": {i: 0.0 for i in range(70)}}
        g["thrs"][0] = 0.5
        f1 = eval("lambda v: v > thrs[0]", g)      # noqa: S307
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        g["thrs"][0] = -0.5                        # in-place, id unchanged
        f2 = eval("lambda v: v > thrs[0]", g)      # noqa: S307
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0), rtol=1e-5)

    def test_recursive_global_function_terminates(self, mesh8, rng):
        # the value-keyed globals walk must terminate when a predicate's
        # global namespace reaches the predicate itself
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        g = {}
        g["pred"] = eval("lambda v: v > 0.5 if pred else v", g)  # noqa: S307
        r1 = sess.compute(m.expr().select_value(g["pred"])).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)


def test_session_explain_includes_physical_plan(mesh8, rng):
    """round-3: EXPLAIN shows the physical annotations (strategy,
    collectives) without the user reaching for compile().explain()."""
    sess = MatrelSession(mesh=mesh8)
    a = sess.from_numpy(rng.standard_normal((32, 32)).astype(np.float32))
    b = sess.from_numpy(rng.standard_normal((32, 32)).astype(np.float32))
    e = a.expr().multiply(b.expr())
    txt = sess.explain(e)
    assert "strategy=" in txt
    assert "== Logical plan ==" in txt and "== Optimized plan ==" in txt
    # logical-only mode skips compilation
    txt2 = sess.explain(e, physical=False)
    assert "strategy=" not in txt2
    # explain warmed the cache: compute() reuses the compiled plan
    assert sess.plan_cache_info()["plans"] >= 1


def test_explain_survives_compile_failure(mesh8, rng, monkeypatch):
    """review r3: when compilation (incl. the optimizer) raises, explain
    degrades to the logical plan + a note instead of crashing."""
    from matrel_tpu import executor as executor_lib
    sess = MatrelSession(mesh=mesh8)
    a = sess.from_numpy(rng.standard_normal((8, 8)).astype(np.float32))
    e = a.expr().t()

    def boom(*args, **kw):
        raise RuntimeError("optimizer exploded")

    monkeypatch.setattr(executor_lib, "compile_expr", boom)
    txt = sess.explain(e)
    assert "== Logical plan ==" in txt
    assert "Physical plan unavailable" in txt and "exploded" in txt


def test_catalog_save_and_load_roundtrip(mesh8, rng, tmp_path):
    """round-3: catalog persistence — registered tables survive a
    session restart with sharding and numerics intact."""
    sess = MatrelSession(mesh=mesh8)
    a = rng.standard_normal((16, 8)).astype(np.float32)
    b = rng.standard_normal((8, 16)).astype(np.float32)
    sess.register("A", sess.from_numpy(a))
    sess.register("B", sess.from_numpy(b))
    sess.save_catalog(str(tmp_path))

    fresh = MatrelSession(mesh=mesh8)
    names = fresh.load_catalog(str(tmp_path))
    assert names == ["A", "B"]
    np.testing.assert_allclose(fresh.table("A").to_numpy(), a, rtol=0)
    assert fresh.table("A").spec == sess.table("A").spec
    # the restored catalog answers SQL
    out = fresh.compute(fresh.sql("SELECT A * B FROM A, B")).to_numpy()
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_load_catalog_empty_dir(mesh8, tmp_path):
    sess = MatrelSession(mesh=mesh8)
    assert sess.load_catalog(str(tmp_path)) == []


def test_save_catalog_steps_are_monotonic(mesh8, rng, tmp_path):
    # review r3: default step must not collide with keep-k GC — three
    # consecutive saves all restore the LATEST catalog
    import os
    sess = MatrelSession(mesh=mesh8)
    for i in range(3):
        sess.register("T", sess.from_numpy(
            np.full((4, 4), float(i), np.float32)))
        p = sess.save_catalog(str(tmp_path))
        assert os.path.isdir(p), p        # the fresh save survives GC
    fresh = MatrelSession(mesh=mesh8)
    fresh.load_catalog(str(tmp_path))
    np.testing.assert_allclose(fresh.table("T").to_numpy(),
                               np.full((4, 4), 2.0))


def test_save_catalog_rejects_path_escaping_names(mesh8, rng, tmp_path):
    sess = MatrelSession(mesh=mesh8)
    m = sess.from_numpy(rng.standard_normal((4, 4)).astype(np.float32))
    for bad in ("a/b", "..", "x\\y", ""):
        sess.catalog = {bad: m}
        with pytest.raises(ValueError):
            sess.save_catalog(str(tmp_path))


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_plan_cache_never_aliases_predicates(seed, mesh8):
    """Cache-aliasing fuzz (round 4): across every predicate
    construction form the keying supports — closures, globals (scalar
    and container, including in-place mutation), bound methods, kw-only
    factory defaults — repeated queries must always match the numpy
    oracle. Repeating an identical threshold is allowed to HIT the
    cache; a differing one must MISS. The silent-stale-result class
    (ADVICE r2 high, r3 medium) is exactly what this net catches."""
    prng = np.random.default_rng(7000 + seed)
    sess = MatrelSession(mesh=mesh8)
    a = prng.standard_normal((8, 8)).astype(np.float32)
    m = sess.from_numpy(a)
    g = {"thr": 0.0, "thrs": [0.0]}

    class Thresh:
        def __init__(self, t):
            self.t = t

        def pred(self, v):
            return v > self.t

    def factory(t):
        def pred(v, *, thr=t):
            return v > thr
        return pred

    # small pool so thresholds REPEAT across forms and iterations —
    # exercising both cache hits and misses
    pool = [-0.5, 0.0, 0.25, 0.8]
    for _ in range(12):
        t = float(prng.choice(pool))
        form = str(prng.choice(["closure", "global", "global_list",
                                "bound", "kwdefault"]))
        if form == "closure":
            pred = lambda v, t=t: v > t          # noqa: E731
        elif form == "global":
            g["thr"] = t
            pred = eval("lambda v: v > thr", g)  # noqa: S307
        elif form == "global_list":
            g["thrs"][0] = t                     # in-place mutation
            pred = eval("lambda v: v > thrs[0]", g)  # noqa: S307
        elif form == "bound":
            pred = Thresh(t).pred
        else:
            pred = factory(t)
        got = sess.compute(m.expr().select_value(pred)).to_numpy()
        np.testing.assert_allclose(
            got, np.where(a > t, a, 0), rtol=1e-5,
            err_msg=f"form={form} t={t}")
    # the fuzz must actually exercise cache HITS: with 12 queries over
    # <=20 (form, threshold) combinations and per-query-text keys,
    # always-miss keying (the conservative inverse regression) would
    # show up as 12 distinct plans
    assert sess.plan_cache_info()["plans"] < 12


class TestOversizedContainerCap:
    """advisor r4 low: containers above _VALUE_KEY_MAX_ELEMS key by
    pinned identity + length instead of by value, so a predicate
    referencing a big module-level list doesn't re-walk it on every
    plan-cache lookup. Growth/shrink still re-keys (length is in the
    token); same-length in-place mutation requires rebinding (documented
    caveat, same as id-keyed objects)."""

    def test_token_forms(self):
        from matrel_tpu import session as S
        big = list(range(S._VALUE_KEY_MAX_ELEMS + 1))
        pins = []
        t = S._attr_token(big, pins)
        assert t.startswith("bigcont:list:") and t.endswith(
            f"len{len(big)}")
        assert any(p is big for p in pins)
        # growth re-keys even at the same id
        big.append(-1)
        assert S._attr_token(big, []) != t
        # small containers still key by value (no pin, no id)
        small = [1, 2, 3]
        pins2 = []
        assert S._attr_token(small, pins2) == S._attr_token(
            [1, 2, 3], [])
        assert not pins2

    def test_distinct_oversized_globals_never_collide(self, mesh8, rng):
        sess = MatrelSession(mesh=mesh8)
        a = rng.standard_normal((8, 8)).astype(np.float32)
        m = sess.from_numpy(a)
        from matrel_tpu import session as S
        n = S._VALUE_KEY_MAX_ELEMS + 10
        g1 = {"thrs": [0.5] * n}
        g2 = {"thrs": [-0.5] * n}   # same length, different values/id
        f1 = eval("lambda v: v > thrs[0]", g1)      # noqa: S307
        f2 = eval("lambda v: v > thrs[0]", g2)      # noqa: S307
        r1 = sess.compute(m.expr().select_value(f1)).to_numpy()
        r2 = sess.compute(m.expr().select_value(f2)).to_numpy()
        np.testing.assert_allclose(r1, np.where(a > 0.5, a, 0), rtol=1e-5)
        np.testing.assert_allclose(r2, np.where(a > -0.5, a, 0),
                                   rtol=1e-5)
