"""Workload acceptance tests (SURVEY.md §7.5, BASELINE.md rows 2/5):
chain reorder, PageRank, the analytics workloads — numerics vs host
oracles on the 8-device mesh. The regression is a query:
tests/test_linreg_query.py."""

import numpy as np
import pytest

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.workloads import chain_bench, pagerank


class TestChain:
    def test_skewed_chain_picks_cheap_order(self, mesh8):
        mats = chain_bench.skewed_abc(mesh8, n=256, mid=8)
        plan, paren, cost = chain_bench.compile_chain(mats)
        assert paren == "((A·B)·C)" or paren == "(A·(B·C))"
        # for n >> mid, (A·B)·C costs n*mid*n + n*n*mid vs A·(B·C): both
        # orders share no term; optimal is A·(B·C): mid·n·mid twice
        assert paren == "(A·(B·C))"

    def test_chain_numerics(self, mesh8, rng):
        a = rng.standard_normal((24, 4)).astype(np.float32)
        b = rng.standard_normal((4, 24)).astype(np.float32)
        c = rng.standard_normal((24, 4)).astype(np.float32)
        mats = [BlockMatrix.from_numpy(m, mesh=mesh8) for m in (a, b, c)]
        plan, _, _ = chain_bench.compile_chain(mats)
        out = plan.run()
        np.testing.assert_allclose(out.to_numpy(), a @ b @ c,
                                   rtol=1e-4, atol=1e-4)


class TestPageRank:
    def test_matches_oracle(self, mesh8, rng):
        n = 50
        a = (rng.random((n, n)) < 0.1).astype(np.float32)
        np.fill_diagonal(a, 0)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        r = np.asarray(pagerank.pagerank(A, rounds=30))
        oracle = pagerank.pagerank_numpy_oracle(a, rounds=30)
        np.testing.assert_allclose(r, oracle, rtol=1e-3, atol=1e-6)
        assert r.sum() == pytest.approx(1.0, rel=1e-3)

    def test_dangling_nodes_conserve_mass(self, mesh8):
        # node 2 has no out-edges
        a = np.array([[0, 1, 1], [1, 0, 0], [0, 0, 0]], dtype=np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        r = np.asarray(pagerank.pagerank(A, rounds=50))
        assert r.sum() == pytest.approx(1.0, rel=1e-4)
        oracle = pagerank.pagerank_numpy_oracle(a, rounds=50)
        np.testing.assert_allclose(r, oracle, rtol=1e-3, atol=1e-6)


def test_symmetric_gram_term_equivalence():
    # ops/gram.py's 2-pass identity: HiHi + HiLo + HiLo^T equals the
    # generic 3-term split HiHi + HiLo + LoHi exactly
    import jax.numpy as jnp
    from matrel_tpu.ops.gram import hi_lo_split, symmetric_gram
    x = jnp.asarray(np.random.default_rng(5).standard_normal((64, 8)),
                    jnp.float32)
    hi, lo = hi_lo_split(x)
    d = lambda a, b: jnp.einsum("nk,nj->kj", a, b,
                                preferred_element_type=jnp.float32)
    generic = d(hi, hi) + d(hi, lo) + d(lo, hi)
    np.testing.assert_allclose(np.asarray(symmetric_gram(x, d)),
                               np.asarray(generic), rtol=0, atol=0)


class TestEdgePageRank:
    def test_edges_matches_dense_oracle(self, mesh8, rng):
        from matrel_tpu.workloads.pagerank import pagerank_edges
        n = 60
        a = (rng.random((n, n)) < 0.08).astype(np.float32)
        np.fill_diagonal(a, 0)
        src, dst = np.nonzero(a)
        r = np.asarray(pagerank_edges(src, dst, n, rounds=30))
        oracle = pagerank.pagerank_numpy_oracle(a, rounds=30).ravel()
        np.testing.assert_allclose(r, oracle, rtol=1e-3, atol=1e-7)
        assert r.sum() == pytest.approx(1.0, rel=1e-3)

    @pytest.mark.parametrize("impl", ["segment", "onehot", "auto", "dense"])
    def test_out_mass_below_one_is_not_clamped(self, impl, mesh8, rng):
        # a weighted graph whose out-masses lie below 1: the floor of the
        # inverse out-mass must be an epsilon, not 1.0, or ranks skew
        # silently (regression); every executor a CPU runs, and the dense
        # adjacency on the mesh
        n = 32
        a = np.zeros((n, n), dtype=np.float32)
        a[0:8, 8:16] = 0.1 * (rng.random((8, 8)) < 0.6)
        a[8:16, 16:24] = 0.1 * (rng.random((8, 8)) < 0.6)
        a[16:24, 0:8] = 0.1 * (rng.random((8, 8)) < 0.6)
        np.fill_diagonal(a, 0)
        assert 0 < a.sum(1).max() < 1
        if impl == "dense":
            r = np.asarray(pagerank.pagerank(
                BlockMatrix.from_numpy(a, mesh=mesh8), rounds=20)).ravel()
        else:
            src, dst = np.nonzero(a)
            r = np.asarray(pagerank.pagerank_edges(
                src, dst, n, rounds=20, impl=impl, weights=a[src, dst]))
        oracle = pagerank.pagerank_numpy_oracle(a, rounds=20).ravel()
        np.testing.assert_allclose(r, oracle, rtol=1e-3, atol=1e-6)

    @pytest.mark.parametrize("impl", ["segment", "onehot"])
    @pytest.mark.parametrize("graph", ["hub", "near_regular"])
    def test_edges_match_the_plain_reference(self, graph, impl, rng):
        from matrel_tpu.workloads import pagerank as pr
        if graph == "hub":
            # every node points at node 0: in-degree 49 beside a mean of 1
            n = 50
            src = np.arange(1, n, dtype=np.int32)
            dst = np.zeros(n - 1, dtype=np.int32)
        else:
            n = 80
            a = (rng.random((n, n)) < 0.1).astype(np.float32)
            np.fill_diagonal(a, 0)
            src, dst = np.nonzero(a)
        r = np.asarray(pr.pagerank_edges(src, dst, n, rounds=20, impl=impl))
        want = pr.pagerank_reference_edges(src, dst, n, rounds=20)
        np.testing.assert_allclose(r, want, rtol=1e-4, atol=1e-8)
        assert r.shape == (n,) and abs(r.sum() - 1.0) < 1e-3


def _recognition_cases():
    """name -> (weighted first call, what the second call is handed,
    how the plan cache must know it, the call's key of
    ``recognition_counts()``). ``g`` is the first call's
    (src, dst, weights-or-None); an ``edit`` mutates it IN PLACE."""
    import jax.numpy as jnp

    def edit(which, where):
        def second(g):
            a = g[which]
            i = {"first": 0, "middle": len(a) // 2, "last": len(a) - 1}[where]
            a[i] = (a[i] + 1) % 100     # another node; another weight
            return g
        return second

    def as_jax(g):       # jnp.asarray would hand the same objects back
        return tuple(None if a is None else jnp.array(a) for a in g)

    return {
        "same_arrays": (False, lambda g: g, "compare", "confirmed"),
        "equal_in_new_arrays": (
            False, lambda g: (g[0].copy(), g[1].copy(), None), "compare",
            "confirmed"),
        "equal_as_int64": (
            False, lambda g: (g[0].astype(np.int64),
                              g[1].astype(np.int64), None), "compare",
            "confirmed"),
        "equal_strided_and_list": (
            False, lambda g: (np.repeat(g[0], 2)[::2], g[1].tolist(), None),
            "compare", "confirmed"),
        # the probe reads the first chunk: nothing is launched for it
        "edit_src_first_chunk": (False, edit(0, "first"), "new",
                                 "compared_first"),
        # past the probe: launched on the cached plan, then found out
        "edit_src_middle_chunk": (False, edit(0, "middle"), "new",
                                  "discarded"),
        "edit_dst_last_chunk": (False, edit(1, "last"), "new", "discarded"),
        "weights_equal_as_float64": (
            True, lambda g: (g[0], g[1], g[2].astype(np.float64)),
            "compare", "confirmed"),
        "weights_added": (
            False, lambda g: (g[0], g[1],
                              (1 + np.arange(len(g[0])) % 3)
                              .astype(np.float32)), "new",
            "compared_first"),
        "weights_dropped": (True, lambda g: (g[0], g[1], None), "new",
                            "compared_first"),
        "weights_edited_last_chunk": (True, edit(2, "last"), "new",
                                      "discarded"),
        "jax_same_objects": (True, lambda g: g, "identity", "identity"),
        "jax_equal_in_new_arrays": (True, as_jax, "compare", "confirmed"),
        "jax_then_numpy": (
            False, lambda g: (np.asarray(g[0]), np.asarray(g[1]), None),
            "compare", "confirmed"),
    }


class TestPlanRecognition:
    """The prepared-plan cache knows its graph by comparison with the
    copy it kept (a jax.Array by identity): never by a digest, never
    stale after an in-place edit."""

    N, M, ROUNDS = 100, 500, 3

    @pytest.fixture(autouse=True)
    def _empty_cache(self, monkeypatch):
        from matrel_tpu.workloads import pagerank as pr
        monkeypatch.setattr(pr, "_PLAN_CACHE", [])
        # eight comparison steps over the 500 edges
        monkeypatch.setattr(pr, "_PROBE_CHUNK", 64)

    def _call(self, g):
        """(ranks, attrs of the call's spans by name: of the newest
        where a name comes twice; the order of the fingerprint, plan
        and dispatch records)."""
        from matrel_tpu.obs import trace as trace_lib
        from matrel_tpu.workloads import pagerank as pr
        recs = []
        tracer = trace_lib.Tracer(lambda kind, rec: recs.append(rec))
        with trace_lib.entry("test", tracer):
            r = np.asarray(pr.pagerank_edges(
                g[0], g[1], self.N, rounds=self.ROUNDS, impl="onehot",
                weights=g[2]))
        recs.sort(key=lambda rec: rec["span_id"])
        return (r, {rec["name"]: rec.get("attrs", {}) for rec in recs},
                [rec["name"] for rec in recs if rec["name"] in (
                    "pagerank.fingerprint", "pagerank.plan",
                    "pagerank.dispatch")])

    @pytest.mark.parametrize("case", sorted(_recognition_cases()))
    def test_second_call(self, case, rng):
        import jax.numpy as jnp
        from matrel_tpu.workloads import pagerank as pr
        weighted, second, how, outcome = _recognition_cases()[case]
        # five out-edges a node, so that one weight moves the ranks
        g = (rng.permutation(np.arange(self.M, dtype=np.int32) % self.N),
             rng.integers(0, self.N, self.M).astype(np.int32),
             (1 + rng.integers(0, 9, self.M)).astype(np.float32)
             if weighted else None)
        if case.startswith("jax_"):
            g = tuple(None if a is None else jnp.asarray(a) for a in g)
        r1, spans, order = self._call(g)
        assert spans["pagerank.fingerprint"] == {
            "bytes": 0, "how": "new", "under_launch": False}
        assert spans["pagerank.plan"]["hit"] is False
        assert order == ["pagerank.fingerprint", "pagerank.plan",
                         "pagerank.dispatch"]
        built = spans["pagerank.plan"]
        counts = pr.recognition_counts()
        g2 = second(g)
        r2, spans, order = self._call(g2)
        assert pr.recognition_counts() == {
            **counts, outcome: counts[outcome] + 1}
        assert pr.last_plan()["recognised"] == outcome
        said = spans["pagerank.fingerprint"]
        assert said["how"] == how
        # the comparison ran behind a launch, and whether it bore it out
        under = outcome in ("confirmed", "discarded")
        assert said["under_launch"] is under
        assert said.get("confirmed") == (
            (outcome == "confirmed") if under else None)
        assert order == {
            "confirmed": ["pagerank.plan", "pagerank.dispatch",
                          "pagerank.fingerprint"],
            "discarded": ["pagerank.plan", "pagerank.dispatch",
                          "pagerank.fingerprint", "pagerank.plan",
                          "pagerank.dispatch"],
        }.get(outcome, ["pagerank.fingerprint", "pagerank.plan",
                        "pagerank.dispatch"])
        assert spans["pagerank.plan"]["hit"] is (how != "new")
        if how != "new":    # a hit says of the plan what its build said
            assert spans["pagerank.plan"] == {**built, "hit": True}
            assert built["layout"] == "blocks" and built["edges"] == self.M
            assert pr.last_plan() == {**spans["pagerank.plan"],
                                      "impl": "onehot",
                                      "recognised": outcome}
        examined = said["bytes"]
        if how == "identity":
            assert examined == 0
        elif how == "compare":
            assert examined == 4 * self.M * sum(a is not None for a in g2)
        else:
            assert examined <= 4 * self.M * 3
        if how != "new":
            assert len(pr._PLAN_CACHE) == 1
            np.testing.assert_array_equal(r2, r1)
            return
        # answered with the ranks of the graph as it now is
        assert len(pr._PLAN_CACHE) == 2
        assert not np.array_equal(r2, r1)
        pr._PLAN_CACHE.clear()
        fresh, _, _ = self._call(tuple(
            None if a is None else np.array(a) for a in g2))
        np.testing.assert_array_equal(r2, fresh)

    @pytest.mark.parametrize("handed", ["int32", "int64", "strided"])
    def test_warm_probe_allocates_less_than_an_edge_array(
            self, handed, rng, monkeypatch):
        import tracemalloc
        from matrel_tpu.workloads import pagerank as pr
        monkeypatch.setattr(pr, "_PROBE_CHUNK", 1 << 12)
        m = 1 << 16
        src = rng.integers(0, self.N, m).astype(np.int32)
        dst = rng.integers(0, self.N, m).astype(np.int32)
        key = (self.N, (m, m), False)
        pr._PLAN_CACHE.append(pr._CachedPlan(
            key, (src.copy(), dst.copy()), (None, None), (), 0))
        if handed == "int64":
            src, dst = src.astype(np.int64), dst.astype(np.int64)
        elif handed == "strided":
            src, dst = np.repeat(src, 2)[::2], np.repeat(dst, 2)[::2]
        tracemalloc.start()
        try:
            found, how, seen = pr._recognise((src, dst),
                                             iter(pr._PLAN_CACHE))
            assert found is pr._PLAN_CACHE[0] and how == "compare"
            assert seen == 2 * 4 * m
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert peak < 4 * m // 2


_EXECUTORS = ("onehot", "compact", "onehot_sharded", "compact_sharded")


class TestLaunchThenConfirm:
    """A cached plan that has to be known by content is launched after
    the probe and confirmed under the launch (PR 53), in all four
    executors of the prepared-plan cache: the order, what a refuted
    launch leaves behind (nothing but a count), and that the programs
    launched are the ones launched before."""

    N, M, ROUNDS, CHUNK = 100, 500, 3, 64

    @pytest.fixture(params=_EXECUTORS)
    def call(self, request, monkeypatch, mesh8):
        """``call(g)`` -> (ranks, what happened, in order): ``launch``
        for a jitted loop's call, ``build`` for a plan build and
        (``read``, array length, start, bytes) for a comparison of
        elements from ``start`` on; the executor under ``call.path``."""
        from matrel_tpu import config as config_lib
        from matrel_tpu.workloads import pagerank as pr
        path = request.param
        if path.startswith("compact"):
            monkeypatch.setattr(
                config_lib, "_default_config",
                config_lib.MatrelConfig(pallas_interpret=True))
        monkeypatch.setattr(pr, "_PLAN_CACHE", [])
        monkeypatch.setattr(pr, "_PROBE_CHUNK", self.CHUNK)
        events = []
        dispatch, compare = pr._dispatch, pr._same_contents
        prepare = pr.prepare_pagerank_onehot

        def spy_dispatch(run, *args):
            events.append("launch")
            return dispatch(run, *args)

        def spy_compare(a, kept, start=0, stop=None):
            same, seen = compare(a, kept, start, stop)
            events.append(("read", kept.shape[0], start, seen))
            return same, seen

        def spy_prepare(*args, **kw):
            events.append("build")
            return prepare(*args, **kw)

        monkeypatch.setattr(pr, "_dispatch", spy_dispatch)
        monkeypatch.setattr(pr, "_same_contents", spy_compare)
        monkeypatch.setattr(pr, "prepare_pagerank_onehot", spy_prepare)
        mesh = mesh8 if path.endswith("_sharded") else None

        def call(g):
            del events[:]
            r = np.asarray(pr.pagerank_edges(
                g[0], g[1], self.N, rounds=self.ROUNDS, impl="onehot",
                mesh=mesh))
            assert pr.last_plan()["impl"] == path
            return r, list(events)

        call.path = path
        return call

    def _graph(self, rng):
        return (rng.permutation(np.arange(self.M, dtype=np.int32) % self.N),
                rng.integers(0, self.N, self.M).astype(np.int32))

    @staticmethod
    def _edited(g, where):
        """A copy of ``g`` with one destination moved to another node."""
        src, dst = g[0].copy(), g[1].copy()
        dst[where] = (dst[where] + 1) % 100
        return src, dst

    def test_an_equal_graph_is_launched_then_compared(self, call, rng):
        from matrel_tpu.workloads import pagerank as pr
        g = self._graph(rng)
        first, events = call(g)
        assert events == ["build", "launch"]
        counts, paths = pr.recognition_counts(), pr.path_counts()
        again, events = call((g[0].copy(), g[1].copy()))
        at = events.index("launch")
        # at most one chunk an array before the launch, the rest behind
        assert events[:at] == [("read", self.M, 0, 4 * self.CHUNK)] * 2
        assert events[at + 1:] == [
            ("read", self.M, self.CHUNK, 4 * (self.M - self.CHUNK))] * 2
        said = pr.last_plan()
        assert said["hit"] is True and said["recognised"] == "confirmed"
        assert pr.recognition_counts() == {
            **counts, "confirmed": counts["confirmed"] + 1}
        assert pr.path_counts() == {**paths,
                                    call.path: paths[call.path] + 1}
        np.testing.assert_array_equal(again, first)

    def test_an_edit_in_the_last_chunk_drops_the_launched_ranks(self, call,
                                                                rng):
        from matrel_tpu.workloads import pagerank as pr
        g = self._graph(rng)
        first, _ = call(g)
        counts, paths = pr.recognition_counts(), pr.path_counts()
        g[1][-1] = (g[1][-1] + 1) % 100     # in place, as a caller would
        got, events = call(g)
        assert [e for e in events if isinstance(e, str)] \
            == ["launch", "build", "launch"]
        said = pr.last_plan()
        assert said["hit"] is False and said["recognised"] == "discarded"
        assert pr.recognition_counts() == {
            **counts, "discarded": counts["discarded"] + 1}
        # the dropped run is no call of anything
        assert pr.path_counts() == {**paths,
                                    call.path: paths[call.path] + 1}
        assert len(pr._PLAN_CACHE) == 2
        assert not np.array_equal(got, first)
        pr._PLAN_CACHE.clear()
        fresh, _ = call((g[0].copy(), g[1].copy()))
        np.testing.assert_array_equal(got, fresh)

    def test_an_edit_in_the_first_chunk_launches_nothing_first(self, call,
                                                               rng):
        from matrel_tpu.workloads import pagerank as pr
        g = self._graph(rng)
        call(g)
        counts = pr.recognition_counts()
        g[0][3] = (g[0][3] + 1) % 100
        _, events = call(g)
        assert events == [("read", self.M, 0, 4 * self.CHUNK),
                          "build", "launch"]
        assert pr.last_plan()["recognised"] == "compared_first"
        assert pr.recognition_counts() == {
            **counts, "compared_first": counts["compared_first"] + 1}

    def test_the_same_jax_arrays_are_launched_uncompared(self, call, rng):
        import jax.numpy as jnp
        from matrel_tpu.workloads import pagerank as pr
        g = tuple(jnp.asarray(a) for a in self._graph(rng))
        first, _ = call(g)
        counts = pr.recognition_counts()
        again, events = call(g)
        assert events == ["launch"]
        assert pr.last_plan()["recognised"] == "identity"
        assert pr.recognition_counts() == {
            **counts, "identity": counts["identity"] + 1}
        np.testing.assert_array_equal(again, first)

    @pytest.mark.parametrize("differ", ["in_the_first_chunk",
                                        "in_the_last_chunk"])
    def test_the_second_of_two_cached_graphs(self, call, rng, differ):
        """Two plans under one key, the call's graph the younger one's:
        the elder fails the probe, or passes it and is launched and
        dropped — and then nothing more is launched on a guess."""
        from matrel_tpu.workloads import pagerank as pr
        elder = self._graph(rng)
        younger = self._edited(elder,
                               3 if differ == "in_the_first_chunk" else -1)
        call(elder)
        want, _ = call(younger)
        assert len(pr._PLAN_CACHE) == 2
        counts = pr.recognition_counts()
        got, events = call((younger[0].copy(), younger[1].copy()))
        outcome = ("confirmed" if differ == "in_the_first_chunk"
                   else "discarded")
        launches = [i for i, e in enumerate(events) if e == "launch"]
        assert len(launches) == (1 if outcome == "confirmed" else 2)
        if outcome == "discarded":
            # the younger plan: both arrays in full, then its launch
            assert events[-3:] == [("read", self.M, 0, 4 * self.M)] * 2 \
                + ["launch"]
        assert "build" not in events and len(pr._PLAN_CACHE) == 2
        said = pr.last_plan()
        assert said["hit"] is True and said["recognised"] == outcome
        assert pr.recognition_counts() == {**counts,
                                           outcome: counts[outcome] + 1}
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("runner", _EXECUTORS)
def test_the_runners_lower_to_the_parents_programs(runner, mesh8):
    """PR 53 moved WHEN a cached plan's loop is called, not what is
    called: each executor's jitted loop lowers for the chip to the text
    the parent commit (0c5b601) lowers it to, by SHA-256 recorded there
    in this container's jax."""
    import jax
    import jax.numpy as jnp
    from test_semiring import _lowered_hash
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib
    from matrel_tpu.workloads import pagerank as pr
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    fixed = np.random.default_rng(7)
    n, m = 1024, 6000
    src, dst = fixed.integers(0, n, m), fixed.integers(0, n, m)
    plan, dangling = pr.prepare_pagerank_onehot(src, dst, n)
    static = (plan.n_rows, plan.n_cols, plan.block)
    if runner == "onehot":
        traced = pr._onehot_runner(
            n, 10, 0.85, static, len(plan.arrays())).trace(
            plan.arrays(), dangling)
    elif runner == "compact":
        traced = pr._compact_runner_loop(
            n, 10, 0.85, static + (spmv_lib.LO,), len(plan.overflow), 3,
            False).trace(pc.compact_tables(plan), plan.overflow, dangling)
    elif runner == "onehot_sharded":
        plan = spmv_lib.shard_plan(plan, mesh8)
        traced = pr._onehot_sharded_runner(
            n, 10, 0.85, static, len(plan.arrays()), mesh8).trace(
            *plan.arrays(), dangling)
    else:
        traced = pr._compact_sharded_loop(
            n, 10, 0.85, static + (spmv_lib.LO,), len(plan.overflow), 3,
            False, mesh8).trace(
            *pc.shard_compact_tables(plan, mesh8), jnp.asarray(dangling),
            *plan.overflow)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    kernels = text.count('\\22body\\22')
    assert kernels == (1 if runner.startswith("compact") else 0)
    assert _lowered_hash(text, kernels) == {
        "onehot": "9cc252b40947e510001dc9064841ed15"
                  "fcefa965ca8868f668f4102107ec4a54",
        "compact": "ff30e3e87e0000d831681797edb15bd8"
                   "0dffedf7e5f9c0afeca63a7ea06396a1",
        "onehot_sharded": "fc9a9a4abb882b1ad19b90824869abe9"
                          "2ec2a60b41b6a145f6a5ff145294a89d",
        "compact_sharded": "8b302649cf1e4959a9252105a20141f2"
                           "e4c032edfa0323581eb17a0c97821b61",
    }[runner]


class TestTriangleCount:
    def test_matches_numpy_oracle(self, mesh8, rng):
        from matrel_tpu.workloads import triangles as T
        n = 40
        a = (rng.random((n, n)) < 0.2).astype(np.float32)
        a = np.triu(a, 1)
        a = a + a.T                       # symmetric, zero diagonal
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        got = T.triangle_count(A)
        assert got == pytest.approx(T.triangles_numpy_oracle(a), rel=1e-4)

    def test_known_small_graph(self, mesh8):
        from matrel_tpu.workloads import triangles as T
        # K4 has C(4,3) = 4 triangles
        a = (np.ones((4, 4)) - np.eye(4)).astype(np.float32)
        assert T.triangle_count(
            BlockMatrix.from_numpy(a, mesh=mesh8)) == pytest.approx(4.0)

    def test_via_sql(self, mesh8, rng):
        from matrel_tpu.session import MatrelSession
        from matrel_tpu.workloads import triangles as T
        n = 24
        a = (rng.random((n, n)) < 0.3).astype(np.float32)
        a = np.triu(a, 1); a = a + a.T
        s = MatrelSession(mesh=mesh8)
        s.register("A", s.from_numpy(a))
        got = s.compute(s.sql("trace(A * A * A)")).to_numpy()[0, 0] / 6.0
        assert got == pytest.approx(T.triangles_numpy_oracle(a), rel=1e-4)

    def test_rejects_nonsquare(self, mesh8, rng):
        from matrel_tpu.workloads import triangles as T
        A = BlockMatrix.from_numpy(
            rng.standard_normal((4, 6)).astype(np.float32), mesh=mesh8)
        with pytest.raises(ValueError):
            T.triangle_count_expr(A)


class TestCosineSimilarity:
    def test_matches_numpy_oracle(self, mesh8, rng):
        from matrel_tpu.workloads import similarity as S
        x = rng.standard_normal((20, 12)).astype(np.float32) + 0.1
        X = BlockMatrix.from_numpy(x, mesh=mesh8)
        got = S.cosine_similarity(X)
        np.testing.assert_allclose(
            got, S.cosine_similarity_numpy_oracle(x), rtol=2e-3, atol=2e-3)

    def test_diagonal_is_one(self, mesh8, rng):
        from matrel_tpu.workloads import similarity as S
        x = rng.standard_normal((16, 8)).astype(np.float32) + 0.2
        got = S.cosine_similarity(BlockMatrix.from_numpy(x, mesh=mesh8))
        np.testing.assert_allclose(np.diagonal(got), 1.0, atol=1e-3)

    def test_gram_path_engaged_under_high_precision(self, mesh8, rng,
                                                    monkeypatch):
        # the X·Xᵀ core must route through the symmetric 2-pass split
        import jax.numpy as jnp
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.executor import execute
        from matrel_tpu.parallel import strategies
        from matrel_tpu.workloads import similarity as S
        calls = []
        real = strategies.run_matmul

        def spy(strategy, p, q, mesh, config=None, **kw):
            calls.append((p.dtype, q.dtype))
            return real(strategy, p, q, mesh, config, **kw)

        monkeypatch.setattr(strategies, "run_matmul", spy)
        x = rng.standard_normal((24, 12)).astype(np.float32) + 0.1
        X = BlockMatrix.from_numpy(x, mesh=mesh8)
        out = execute(S.cosine_similarity_expr(X), mesh8,
                      MatrelConfig(matmul_precision="high")).to_numpy()
        assert [c for c in calls
                if c == (jnp.bfloat16, jnp.bfloat16)], calls
        np.testing.assert_allclose(
            out, S.cosine_similarity_numpy_oracle(x), rtol=5e-3, atol=5e-3)


class TestPowerIteration:
    def test_dominant_eigenpair_symmetric(self, mesh8, rng):
        from matrel_tpu.workloads import eigen
        n = 24
        q = rng.standard_normal((n, n)).astype(np.float32)
        a = (q + q.T) / 2                       # symmetric: real spectrum
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        lam, v = eigen.power_iteration(A, rounds=200)
        assert abs(abs(lam) - eigen.eig_numpy_oracle(a)) < 1e-2
        # v is an eigenvector: A v ≈ λ v
        resid = np.linalg.norm(a @ np.asarray(v) - lam * np.asarray(v))
        assert resid < 1e-2 * abs(lam)

    def test_spectral_norm_matches_svd(self, mesh8, rng):
        from matrel_tpu.workloads import eigen
        a = rng.standard_normal((20, 12)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        got = eigen.spectral_norm(A, rounds=200)
        want = float(np.linalg.svd(a, compute_uv=False)[0])
        assert got == pytest.approx(want, rel=1e-3)

    def test_rejects_nonsquare(self, mesh8, rng):
        from matrel_tpu.workloads import eigen
        A = BlockMatrix.from_numpy(
            rng.standard_normal((4, 6)).astype(np.float32), mesh=mesh8)
        with pytest.raises(ValueError):
            eigen.power_iteration(A)

    def test_accepts_expression(self, mesh8, rng):
        from matrel_tpu.workloads import eigen
        a = rng.standard_normal((12, 12)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        # spectral norm of a lazy expression (2·A): compiles then iterates
        got = eigen.spectral_norm(A.expr().multiply_scalar(2.0),
                                  rounds=200)
        want = 2 * float(np.linalg.svd(a, compute_uv=False)[0])
        assert got == pytest.approx(want, rel=1e-3)

    def test_coo_power_iteration_matches_dense(self, mesh8, rng):
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.workloads import eigen
        n = 64
        a = (rng.random((n, n)) < 0.12).astype(np.float32)
        a = np.maximum(a, a.T)                 # symmetric 0/1 adjacency
        np.fill_diagonal(a, 0)
        r, c = np.nonzero(a)
        coo = COOMatrix.from_edges(r, c, a[r, c], shape=(n, n))
        lam, v = eigen.power_iteration_coo(coo, rounds=300)
        assert abs(lam) == pytest.approx(eigen.eig_numpy_oracle(a),
                                         rel=1e-2)
        resid = np.linalg.norm(a @ np.asarray(v) - lam * np.asarray(v))
        assert resid < 2e-2 * abs(lam)


class TestConjugateGradient:
    def test_spd_solve_matches_numpy(self, mesh8, rng):
        from matrel_tpu.workloads import cg
        n = 24
        q = rng.standard_normal((n, n)).astype(np.float32)
        a = q @ q.T + n * np.eye(n, dtype=np.float32)   # SPD
        b = rng.standard_normal(n).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        x, it = cg.cg_solve(A, b, tol=1e-6)
        assert 0 < it < 1000
        np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                                   rtol=1e-3, atol=1e-3)

    def test_least_squares_matches_lstsq(self, mesh8, rng):
        from matrel_tpu.workloads import cg
        x_np = rng.standard_normal((96, 8)).astype(np.float32)
        tt = np.linspace(-1, 1, 8).astype(np.float32)
        y = x_np @ tt
        X = BlockMatrix.from_numpy(x_np, mesh=mesh8)
        theta, it = cg.cg_least_squares(X, y, tol=1e-7)
        np.testing.assert_allclose(np.asarray(theta), tt, rtol=1e-2,
                                   atol=1e-2)

    def test_linop_form_with_planned_spmv(self, mesh8, rng):
        # SPD operator from a sparse graph Laplacian via the SpMV plan
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.ops import spmv as spmv_lib
        from matrel_tpu.workloads import cg
        n = 48
        adj = (rng.random((n, n)) < 0.15).astype(np.float32)
        adj = np.maximum(adj, adj.T); np.fill_diagonal(adj, 0)
        lap = np.diag(adj.sum(1)) - adj + np.eye(n, dtype=np.float32)
        r, c = np.nonzero(lap)
        coo = COOMatrix.from_edges(r, c, lap[r, c], shape=(n, n))
        plan = coo._get_plan()
        static = (plan.n_rows, plan.n_cols, plan.block)
        arrays = plan.arrays()
        b = rng.standard_normal(plan.n_cols).astype(np.float32)
        b[n:] = 0.0
        x, it = cg.cg_solve_linop(
            lambda v: spmv_lib.spmv_apply(static, arrays, v),
            b, tol=1e-6)
        np.testing.assert_allclose(
            np.asarray(x)[:n], np.linalg.solve(lap, b[:n]), rtol=1e-3,
            atol=1e-3)

    def test_rejects_nonsquare(self, mesh8, rng):
        from matrel_tpu.workloads import cg
        A = BlockMatrix.from_numpy(
            rng.standard_normal((4, 6)).astype(np.float32), mesh=mesh8)
        with pytest.raises(ValueError):
            cg.cg_solve(A, np.zeros(4))
