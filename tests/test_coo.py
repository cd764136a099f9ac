"""core/coo.py — element-sparse COOMatrix over the one-hot SpMV plans."""

import numpy as np
import pytest
import scipy.sparse as sp


from matrel_tpu import COOMatrix


@pytest.fixture
def rng():
    return np.random.default_rng(17)


def random_coo(rng, n_r, n_c, m):
    return (rng.integers(0, n_r, m), rng.integers(0, n_c, m),
            rng.standard_normal(m).astype(np.float32))


class TestConstruction:
    def test_from_edges_and_scipy_agree(self, rng):
        r, c, v = random_coo(rng, 500, 300, 4000)
        a = COOMatrix.from_edges(r, c, v, shape=(500, 300))
        b = COOMatrix.from_scipy(
            sp.coo_matrix((v, (r, c)), shape=(500, 300)))
        np.testing.assert_allclose(a.to_dense(), b.to_dense())
        assert a.shape == b.shape == (500, 300)
        assert a.nnz == 4000

    def test_default_values_and_shape_inference(self):
        a = COOMatrix.from_edges([0, 2], [1, 3])
        assert a.shape == (3, 4)
        assert a.to_dense()[2, 3] == 1.0

    def test_bounds_and_length_validation(self):
        with pytest.raises(ValueError, match="out of bounds"):
            COOMatrix.from_edges([5], [0], shape=(3, 3))
        with pytest.raises(ValueError, match="mismatch"):
            COOMatrix.from_edges([1, 2], [0])
        with pytest.raises(ValueError, match="vals"):
            COOMatrix.from_edges([1], [0], vals=[1.0, 2.0])


class TestOps:
    def test_matvec_vs_scipy(self, rng):
        r, c, v = random_coo(rng, 2000, 1500, 30_000)
        A = COOMatrix.from_edges(r, c, v, shape=(2000, 1500))
        S = sp.coo_matrix((v, (r, c)), shape=(2000, 1500)).tocsr()
        x = rng.standard_normal(1500).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matvec(x)), S @ x,
                                   rtol=3e-4, atol=3e-4)

    def test_rmatvec_and_T_vs_scipy(self, rng):
        r, c, v = random_coo(rng, 800, 1200, 10_000)
        A = COOMatrix.from_edges(r, c, v, shape=(800, 1200))
        S = sp.coo_matrix((v, (r, c)), shape=(800, 1200)).tocsr()
        y = rng.standard_normal(800).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.rmatvec(y)), S.T @ y,
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(A.T.matvec(y)), S.T @ y,
                                   rtol=3e-4, atol=3e-4)

    def test_matmat_vs_scipy(self, rng):
        r, c, v = random_coo(rng, 600, 400, 5_000)
        A = COOMatrix.from_edges(r, c, v, shape=(600, 400))
        S = sp.coo_matrix((v, (r, c)), shape=(600, 400)).tocsr()
        X = rng.standard_normal((400, 5)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matmat(X)), S @ X,
                                   rtol=3e-4, atol=3e-4)

    def test_matvec_shape_errors(self, rng):
        A = COOMatrix.from_edges([0], [0], shape=(4, 6))
        with pytest.raises(ValueError, match="columns"):
            A.matvec(np.ones(4))
        with pytest.raises(ValueError, match="rows"):
            A.rmatvec(np.ones(6))
        with pytest.raises(ValueError, match="k"):
            A.matmat(np.ones((4, 2)))

    def test_duplicate_coordinates_accumulate(self):
        A = COOMatrix.from_edges([1, 1, 1], [2, 2, 0],
                                 vals=[1.0, 2.0, 5.0], shape=(3, 3))
        x = np.array([1.0, 0.0, 10.0], np.float32)
        got = np.asarray(A.matvec(x))
        np.testing.assert_allclose(got, [0.0, 35.0, 0.0])

    def test_segment_fallback_on_refused_plan(self):
        # one edge per 512-block over a huge row space -> plan refused;
        # matvec must still be correct through the segment path
        n_r = 512 * 20_000
        rows = np.arange(20_000, dtype=np.int64) * 512
        cols = np.arange(20_000, dtype=np.int64) % 64
        A = COOMatrix.from_edges(rows, cols, shape=(n_r, 64))
        assert A._get_plan() is None
        x = np.ones(64, np.float32)
        got = np.asarray(A.matvec(x))
        assert got.shape == (n_r,)
        assert got[rows].sum() == pytest.approx(20_000)
        assert got.sum() == pytest.approx(20_000)

    def test_empty_matrix(self):
        A = COOMatrix.from_edges([], [], shape=(10, 10))
        np.testing.assert_array_equal(np.asarray(A.matvec(np.ones(10))),
                                      np.zeros(10))


class TestShardedCOO:
    def test_sharded_matvec_matches_single(self, mesh8, rng):
        r, c, v = random_coo(rng, 6000, 4000, 50_000)
        A = COOMatrix.from_edges(r, c, v, shape=(6000, 4000))
        x = rng.standard_normal(4000).astype(np.float32)
        want = np.asarray(A.matvec(x))
        As = A.shard(mesh8)
        got = np.asarray(As.matvec(x))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)

    def test_sharded_matmat_uses_sharded_plan(self, mesh8, rng):
        r, c, v = random_coo(rng, 3000, 2000, 20_000)
        A = COOMatrix.from_edges(r, c, v, shape=(3000, 2000))
        As = A.shard(mesh8)
        X = rng.standard_normal((2000, 3)).astype(np.float32)
        got = np.asarray(As.matmat(X))
        np.testing.assert_allclose(got, np.asarray(A.matmat(X)),
                                   rtol=2e-5, atol=1e-5)
        # the sharded matrix must not have grown an unsharded plan
        assert As._plan is None and not As._plan_tried

    def test_dsl_then_eager_no_tracer_poisoning(self, rng):
        # arrays()/spmm_extra() first invoked INSIDE the executor's
        # trace must not cache tracers (regression: UnexpectedTracerError
        # on any later eager use of the same matrix)
        from matrel_tpu import execute
        r, c, v = random_coo(rng, 500, 400, 4000)
        A = COOMatrix.from_edges(r, c, v, shape=(500, 400))
        X = rng.standard_normal((400, 3)).astype(np.float32)
        from matrel_tpu.core.blockmatrix import BlockMatrix
        out = execute(A.multiply(BlockMatrix.from_numpy(X).expr()))
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ X,
                                   rtol=3e-4, atol=3e-4)
        # eager uses after the traced one must work and agree
        np.testing.assert_allclose(np.asarray(A.matmat(X)),
                                   A.to_dense() @ X, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(A.matvec(X[:, 0])),
                                   A.to_dense() @ X[:, 0],
                                   rtol=3e-4, atol=3e-4)

    def test_shard_refused_graph_raises(self, mesh8):
        rows = np.arange(20_000, dtype=np.int64) * 512
        A = COOMatrix.from_edges(rows, np.zeros(20_000, np.int64),
                                 shape=(512 * 20_000, 1))
        with pytest.raises(ValueError, match="heavy-tailed"):
            A.shard(mesh8)


class TestDSLIntegration:
    """coo_leaf in the IR: SpMV lowering for matmuls, densify elsewhere."""

    def test_left_multiply_via_dsl(self, rng):
        from matrel_tpu import execute
        r, c, v = random_coo(rng, 700, 500, 6000)
        A = COOMatrix.from_edges(r, c, v, shape=(700, 500))
        x = rng.standard_normal((500, 3)).astype(np.float32)
        from matrel_tpu.core.blockmatrix import BlockMatrix
        X = BlockMatrix.from_numpy(x)
        out = execute(A.multiply(X.expr()))
        want = A.to_dense() @ x
        np.testing.assert_allclose(out.to_numpy(), want, rtol=3e-4,
                                   atol=3e-4)

    def test_expanded_path_partially_sharded_vectors(self, rng, mesh8):
        """Regression: the expanded XLA SpMV path must REPLICATE its
        input vectors first (executor._coo_spmv_stack). A vector sliced
        from a 2D-sharded operand arrives partially sharded (P('y',) on
        the (2, 4) mesh) and jax 0.4.37's GSPMD partitioner miscompiles
        the one-hot contraction over such inputs — every entry scaled
        by exactly gx (the round-6 root cause of the 'COO DSL 2x-scale'
        pair and fuzz[49])."""
        from matrel_tpu import executor
        from matrel_tpu.config import default_config
        from matrel_tpu.core.blockmatrix import BlockMatrix
        r, c, v = random_coo(rng, 400, 600, 5000)
        S = COOMatrix.from_edges(r, c, v, shape=(400, 600))
        a = rng.standard_normal((5, 400)).astype(np.float32)
        padded = BlockMatrix.from_numpy(a, mesh=mesh8).data  # P(x, y)
        lo = executor.Lowerer(mesh8, default_config())
        plan = S._get_plan_t()
        assert plan is not None
        out = np.asarray(
            lo._coo_spmv_stack(plan, [padded[i, :400] for i in range(5)]))
        want = (a @ S.to_dense()).T
        np.testing.assert_allclose(out[:600], want, rtol=3e-4, atol=3e-4)

    def test_right_multiply_via_dsl(self, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as E
        r, c, v = random_coo(rng, 400, 600, 5000)
        S = COOMatrix.from_edges(r, c, v, shape=(400, 600))
        a = rng.standard_normal((5, 400)).astype(np.float32)
        A = BlockMatrix.from_numpy(a)
        out = execute(E.matmul(A.expr(), S.expr()))
        want = a @ S.to_dense()
        np.testing.assert_allclose(out.to_numpy(), want, rtol=3e-4,
                                   atol=3e-4)

    def test_wide_rhs_takes_densify_fallback(self, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        r, c, v = random_coo(rng, 200, 150, 2000)
        A = COOMatrix.from_edges(r, c, v, shape=(200, 150))
        x = rng.standard_normal((150, 200)).astype(np.float32)  # k > 128
        out = execute(A.multiply(BlockMatrix.from_numpy(x).expr()))
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ x,
                                   rtol=2e-3, atol=2e-3)

    def test_non_matmul_use_densifies(self, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as E
        r, c, v = random_coo(rng, 64, 64, 500)
        A = COOMatrix.from_edges(r, c, v, shape=(64, 64))
        b = rng.standard_normal((64, 64)).astype(np.float32)
        B = BlockMatrix.from_numpy(b)
        out = execute(E.elemwise("add", A.expr(), B.expr()))
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() + b,
                                   rtol=1e-5, atol=1e-5)

    def test_chained_with_aggregation(self, rng):
        # rowSum(S·x) exercises rewrite rules over a coo_leaf tree
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as E
        r, c, v = random_coo(rng, 300, 250, 3000)
        S = COOMatrix.from_edges(r, c, v, shape=(300, 250))
        x = rng.standard_normal((250, 4)).astype(np.float32)
        expr = E.agg(S.multiply(BlockMatrix.from_numpy(x).expr()),
                     "sum", "row")
        out = execute(expr)
        want = (S.to_dense() @ x).sum(axis=1, keepdims=True)
        np.testing.assert_allclose(out.to_numpy(), want, rtol=3e-4,
                                   atol=3e-4)


class TestCompactShardedExecutor:
    """DSL coo_leaf matmuls must run the compact-table Pallas path
    (13 B/slot; row-decomposed per device on a mesh) — the expanded
    ~224 B/slot XLA tables must never be built. Single-device compact
    branches are covered here too (interpret mode in CI)."""

    def _cfg(self):
        from matrel_tpu.config import MatrelConfig
        return MatrelConfig(pallas_interpret=True)

    @staticmethod
    def _forbid_expanded(plan):
        """Spy: the expanded-table path goes through plan.arrays()."""
        def _boom(*a, **k):
            raise AssertionError("expanded tables built")
        object.__setattr__(plan, "arrays", _boom)

    def test_single_device_compact_interpret(self, rng):
        # mesh.size == 1 takes the UNSHARDED compact branch
        # (compact_apply / compact_matmat_apply); regression cover for
        # the cached-tracer bug (compact_tables memoised tracers when
        # first called inside an executor trace)
        import jax
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core import mesh as mesh_lib
        mesh1 = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        r, c, v = random_coo(rng, 600, 500, 5000)
        A = COOMatrix.from_edges(r, c, v, shape=(600, 500))
        x = rng.standard_normal((500, 3)).astype(np.float32)
        self._forbid_expanded(A._get_plan())
        out = execute(A.multiply(BlockMatrix.from_numpy(
            x, mesh=mesh1).expr()), mesh=mesh1, config=self._cfg())
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ x,
                                   rtol=3e-4, atol=3e-4)
        # memo must hold committed arrays, not trace leftovers
        assert not isinstance(A._plan._compact_dev[0], jax.core.Tracer)
        # single vector → matvec kernel branch; plan reused across
        # compiles (the sequence the cached-tracer bug broke)
        x1 = rng.standard_normal((500, 1)).astype(np.float32)
        out1 = execute(A.multiply(BlockMatrix.from_numpy(
            x1, mesh=mesh1).expr()), mesh=mesh1, config=self._cfg())
        np.testing.assert_allclose(out1.to_numpy(), A.to_dense() @ x1,
                                   rtol=3e-4, atol=3e-4)

    def test_left_multiply_compact_on_mesh(self, mesh8, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        r, c, v = random_coo(rng, 700, 500, 6000)
        A = COOMatrix.from_edges(r, c, v, shape=(700, 500))
        x = rng.standard_normal((500, 3)).astype(np.float32)
        X = BlockMatrix.from_numpy(x, mesh=mesh8)
        # spy: the expanded-table path goes through plan.arrays(); the
        # compact path must never touch it (in-trace staging returns
        # uncached tracers, so _tables stays None on BOTH paths — state
        # alone can't discriminate)
        plan = A._get_plan()
        self._forbid_expanded(plan)
        out = execute(A.multiply(X.expr()), mesh=mesh8,
                      config=self._cfg())
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ x,
                                   rtol=3e-4, atol=3e-4)
        # compact sharded tables were built for THIS mesh, committed
        # (not tracers), block axis spread over all 8 devices
        tabs = plan._compact_sharded[mesh8]
        assert len(tabs[0].sharding.device_set) == 8
        assert plan._tables is None
        assert plan._spmm_tables is None

    def test_single_vector_compact_on_mesh(self, mesh8, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        r, c, v = random_coo(rng, 900, 400, 7000)
        A = COOMatrix.from_edges(r, c, v, shape=(900, 400))
        x = rng.standard_normal((400, 1)).astype(np.float32)
        out = execute(A.multiply(BlockMatrix.from_numpy(
            x, mesh=mesh8).expr()), mesh=mesh8, config=self._cfg())
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ x,
                                   rtol=3e-4, atol=3e-4)
        assert A._plan._tables is None

    def test_right_multiply_compact_on_mesh(self, mesh8, rng):
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as E
        r, c, v = random_coo(rng, 400, 600, 5000)
        S = COOMatrix.from_edges(r, c, v, shape=(400, 600))
        a = rng.standard_normal((5, 400)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        out = execute(E.matmul(A.expr(), S.expr()), mesh=mesh8,
                      config=self._cfg())
        np.testing.assert_allclose(out.to_numpy(), a @ S.to_dense(),
                                   rtol=3e-4, atol=3e-4)
        # the transpose plan drove it; expanded tables never built
        assert S._plan_t is not None
        assert S._plan_t._tables is None

    def test_compact_with_overflow_rows_on_mesh(self, mesh8, rng):
        # heavy row → plan carries overflow COO; sharded path must add
        # it after the gather
        from matrel_tpu import execute
        from matrel_tpu.core.blockmatrix import BlockMatrix
        m = 20_000
        r = np.where(rng.random(m) < 0.3, 7,
                     rng.integers(0, 2048, m)).astype(np.int64)
        c = rng.integers(0, 512, m).astype(np.int64)
        v = rng.standard_normal(m).astype(np.float32)
        A = COOMatrix.from_edges(r, c, v, shape=(2048, 512))
        assert A._get_plan().ov_rows is not None
        x = rng.standard_normal((512, 2)).astype(np.float32)
        out = execute(A.multiply(BlockMatrix.from_numpy(
            x, mesh=mesh8).expr()), mesh=mesh8, config=self._cfg())
        np.testing.assert_allclose(out.to_numpy(), A.to_dense() @ x,
                                   rtol=3e-4, atol=3e-4)


class TestCOORelational:
    """Edge-list-native σ/γ/⋈ — results must match the dense masked
    semantics (and hence the IR lowerings) exactly."""

    def _mat(self, rng, n=40, m=30, nnz=200):
        from matrel_tpu.core.coo import COOMatrix
        r = rng.integers(0, n, nnz)
        c = rng.integers(0, m, nnz)
        v = rng.standard_normal(nnz).astype(np.float32)
        return COOMatrix.from_edges(r, c, v, shape=(n, m))

    def test_select_value(self, rng):
        A = self._mat(rng)
        d = A.to_dense()
        got = A.select_value(lambda v: v > 0.3).to_dense()
        np.testing.assert_allclose(got, np.where(d > 0.3, d, 0.0),
                                   rtol=1e-6)
        with pytest.raises(ValueError, match="fill"):
            A.select_value(lambda v: v > 0, fill=1.0)

    def test_select_index(self, rng):
        A = self._mat(rng)
        d = A.to_dense()
        got = A.select_index(rows=lambda i: i % 3 == 0,
                             cols=lambda j: j < 10).to_dense()
        want = d.copy()
        want[np.arange(40) % 3 != 0, :] = 0
        want[:, 10:] = 0
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_axis_aggregates(self, rng):
        A = self._mat(rng)
        d = A.to_dense().astype(np.float64)
        np.testing.assert_allclose(A.row_sum()[:, 0], d.sum(1), rtol=1e-5)
        np.testing.assert_allclose(A.col_sum()[0], d.sum(0), rtol=1e-5)
        nz = d != 0
        np.testing.assert_allclose(A.row_count()[:, 0], nz.sum(1))
        np.testing.assert_allclose(A.col_count()[0], nz.sum(0))
        # avg/max/min over NONZERO entries (relational γ semantics)
        cnt = np.maximum(nz.sum(1), 1)
        np.testing.assert_allclose(A.row_avg()[:, 0],
                                   np.where(nz.any(1), d.sum(1) / cnt, 0),
                                   rtol=1e-5)
        # dense-lowering parity: implicit zeros participate in max/min
        np.testing.assert_allclose(A.row_max()[:, 0], d.max(1), rtol=1e-5)
        assert A.sum() == pytest.approx(d.sum(), rel=1e-5)

    def test_trace(self, rng):
        from matrel_tpu.core.coo import COOMatrix
        A = COOMatrix.from_edges([0, 1, 2, 1], [0, 1, 0, 1],
                                 [1.0, 2.0, 3.0, 4.0], shape=(3, 3))
        assert A.trace() == pytest.approx(7.0)   # dups additive on diag

    def test_join_on_index_union_semantics(self, rng):
        from matrel_tpu.core.coo import COOMatrix
        A = self._mat(rng, nnz=100)
        B = self._mat(rng, nnz=120)
        da, db = A.to_dense(), B.to_dense()
        # merge where absence reads 0 — union coordinates matter
        got = A.join_on_index(B, lambda x, y: x * y + x).to_dense()
        np.testing.assert_allclose(got, da * db + da, rtol=1e-5,
                                   atol=1e-6)
        with pytest.raises(ValueError, match="mismatch"):
            A.join_on_index(self._mat(rng, n=10, m=10), lambda x, y: x)
        # densifying merges must be rejected, not silently wrong
        with pytest.raises(ValueError, match="dense"):
            A.join_on_index(B, lambda x, y: x + y + 1.0)

    def test_all_negative_row_max_matches_dense(self, rng):
        from matrel_tpu.core.coo import COOMatrix
        A = COOMatrix.from_edges([0, 0], [1, 2], [-3.0, -5.0],
                                 shape=(2, 4))
        d = A.to_dense()
        np.testing.assert_allclose(A.row_max()[:, 0], d.max(1))   # [0, 0]
        np.testing.assert_allclose(A.row_min()[:, 0], d.min(1))   # [-5, 0]
        # a FULLY populated row keeps its true (negative) max
        B = COOMatrix.from_edges([0, 0], [0, 1], [-3.0, -5.0],
                                 shape=(1, 2))
        np.testing.assert_allclose(B.row_max()[:, 0], [-3.0])

    def test_scale_smoke_no_densify(self, rng):
        # 200k x 200k with 50k edges: any densify would be 160 GB
        from matrel_tpu.core.coo import COOMatrix
        n, nnz = 200_000, 50_000
        r = rng.integers(0, n, nnz); c = rng.integers(0, n, nnz)
        v = rng.standard_normal(nnz).astype(np.float32)
        A = COOMatrix.from_edges(r, c, v, shape=(n, n))
        pos = A.select_value(lambda x: x > 0)
        assert 0 < pos.nnz < nnz
        rs = A.row_sum()
        want = np.zeros(n); np.add.at(want, r, v)
        np.testing.assert_allclose(rs[:, 0], want, rtol=1e-4, atol=1e-5)
        j = A.join_on_index(pos, lambda x, y: x - y)   # A - positives
        neg = A.select_value(lambda x: x < 0)
        np.testing.assert_allclose(np.sort(j.vals), np.sort(neg.vals),
                                   rtol=1e-6)

    def test_norms(self, rng):
        from matrel_tpu.core.coo import COOMatrix
        A = COOMatrix.from_edges([0, 0, 1], [1, 1, 2],
                                 [3.0, -1.0, -4.0], shape=(3, 3))
        d = A.to_dense()          # dup at (0,1) sums to 2.0
        assert A.norm() == pytest.approx(np.linalg.norm(d))
        assert A.norm("l1") == pytest.approx(np.abs(d).sum())
        assert A.norm("max") == pytest.approx(np.abs(d).max())


class TestCOOValueJoin:
    """Edge-list-native ⋈ on values: nonzero entry tuples matched by
    structured (sorted) or callable (capped brute) predicates."""

    def _oracle(self, A, B, merge_np, pred_np):
        sa = A.to_dense()
        sb = B.to_dense()
        ia, ja = np.nonzero(sa)
        ib, jb = np.nonzero(sb)
        pairs = []
        for x, (i, j) in zip(sa[ia, ja], zip(ia, ja)):
            for y, (k, l) in zip(sb[ib, jb], zip(ib, jb)):
                if pred_np(x, y):
                    pairs.append((i, j, k, l, merge_np(x, y)))
        return sorted(pairs)

    def _got(self, res):
        return sorted(zip(*(a.tolist() for a in res[:4]),
                          res[4].tolist()))

    @pytest.mark.parametrize("pred", ["eq", "lt", "le", "gt", "ge"])
    def test_structured_matches_bruteforce(self, rng, pred):
        import operator
        pool = np.array([-2.0, -1.0, 1.0, 1.0, 2.0], np.float32)
        r, c = rng.integers(0, 20, 60), rng.integers(0, 15, 60)
        A = COOMatrix.from_edges(r, c, rng.choice(pool, 60),
                                 shape=(20, 15))
        r2, c2 = rng.integers(0, 10, 40), rng.integers(0, 12, 40)
        B = COOMatrix.from_edges(r2, c2, rng.choice(pool, 40),
                                 shape=(10, 12))
        ops = {"eq": operator.eq, "lt": operator.lt, "le": operator.le,
               "gt": operator.gt, "ge": operator.ge}
        got = self._got(A.join_on_value(B, merge="mul", predicate=pred))
        want = self._oracle(A, B, operator.mul, ops[pred])
        assert [g[:4] for g in got] == [w[:4] for w in want]
        np.testing.assert_allclose([g[4] for g in got],
                                   [w[4] for w in want], rtol=1e-6)

    def test_callable_pred_and_merges(self, rng):
        r, c = rng.integers(0, 8, 20), rng.integers(0, 8, 20)
        A = COOMatrix.from_edges(r, c, rng.standard_normal(20),
                                 shape=(8, 8))
        B = COOMatrix.from_edges(c, r, rng.standard_normal(20),
                                 shape=(8, 8))
        got = self._got(A.join_on_value(
            B, merge=lambda x, y: x - y,
            predicate=lambda x, y: x + y > 0.5))
        want = self._oracle(A, B, lambda x, y: x - y,
                            lambda x, y: x + y > 0.5)
        assert [g[:4] for g in got] == [w[:4] for w in want]
        # structured merges
        ia, ja, ib, jb, v = A.join_on_value(B, merge="left",
                                            predicate="ge")
        dense_a = A.to_dense()
        np.testing.assert_allclose(v, dense_a[ia, ja], rtol=1e-6)

    def test_pair_cap_refusal(self, rng):
        r = rng.integers(0, 100, 3000)
        c = rng.integers(0, 100, 3000)
        A = COOMatrix.from_edges(r, c, np.ones(3000), shape=(100, 100))
        with pytest.raises(ValueError, match="max_pairs"):
            A.join_on_value(A, merge="mul", predicate="eq",
                            max_pairs=10)
        with pytest.raises(ValueError, match="max_pairs"):
            A.join_on_value(A, merge="mul",
                            predicate=lambda x, y: x == y,
                            max_pairs=10)

    def test_zero_entries_never_join(self):
        # duplicate cancellation produces an explicit zero entry; it
        # must be absent from the join
        A = COOMatrix.from_edges([0, 0, 1], [0, 0, 1], [1.0, -1.0, 2.0],
                                 shape=(2, 2))
        B = COOMatrix.from_edges([0], [0], [0.5], shape=(1, 1))
        ia, ja, ib, jb, v = A.join_on_value(B, merge="mul",
                                            predicate="gt")
        assert list(zip(ia, ja)) == [(1, 1)]
        np.testing.assert_allclose(v, [1.0])

    def test_nan_entries_match_nothing_structured(self):
        # IEEE: NaN compares False — structured and callable paths agree
        A = COOMatrix.from_edges([0, 1], [0, 1], [1.0, np.nan],
                                 shape=(2, 2))
        B = COOMatrix.from_edges([0, 1], [0, 1], [np.nan, 2.0],
                                 shape=(2, 2))
        for pred_s, pred_f in [("lt", lambda x, y: x < y),
                               ("eq", lambda x, y: x == y),
                               ("ge", lambda x, y: x >= y)]:
            got_s = A.join_on_value(B, merge="left", predicate=pred_s)
            got_f = A.join_on_value(B, merge="left", predicate=pred_f)
            assert got_s[0].tolist() == got_f[0].tolist(), pred_s
            assert got_s[3].tolist() == got_f[3].tolist(), pred_s
        # only the (1.0, 2.0) pair can ever match 'lt'
        ia, ja, ib, jb, v = A.join_on_value(B, merge="right",
                                            predicate="lt")
        assert list(zip(ia, ja, ib, jb)) == [(0, 0, 1, 1)]
        np.testing.assert_allclose(v, [2.0])

    def test_coo_join_totals_match_dense_streaming(self, mesh8, rng):
        # cross-surface metamorphic check: for merge='mul' (zero
        # operands annihilate), the sum over COO matched PAIRS equals
        # the dense pair-matrix aggregate of the same logical matrices
        from matrel_tpu import execute
        from matrel_tpu.relational import ops as R
        from matrel_tpu.core.blockmatrix import BlockMatrix
        r, c, v = random_coo(rng, 40, 30, 200)
        r2, c2, v2 = random_coo(rng, 20, 25, 150)
        A = COOMatrix.from_edges(r, c, v, shape=(40, 30))
        B = COOMatrix.from_edges(r2, c2, v2, shape=(20, 25))
        for pred in ("lt", "gt", "eq"):
            pairs = A.join_on_value(B, merge="mul", predicate=pred)
            coo_total = float(pairs[4].astype(np.float64).sum())
            j = R.join_on_values(
                BlockMatrix.from_numpy(A.to_dense(), mesh=mesh8),
                BlockMatrix.from_numpy(B.to_dense(), mesh=mesh8),
                merge="mul", predicate=pred)
            dense_total = float(R.aggregate(j, "sum", "all")
                                .compute().to_numpy()[0, 0])
            assert abs(coo_total - dense_total) <= 1e-3 * max(
                1.0, abs(dense_total)), (pred, coo_total, dense_total)


def test_infer_dtype_asserts_coo_payload_f32(mesh8):
    # VERDICT r4 "what's weak" #4: a dtype-bearing COOMatrix must fail
    # loudly at the infer_dtype boundary instead of silently keying the
    # wrong autotune table row
    import numpy as np
    import pytest
    from matrel_tpu.core.coo import COOMatrix
    from matrel_tpu.parallel.planner import infer_dtype
    rng = np.random.default_rng(0)
    A = COOMatrix.from_edges(rng.integers(0, 32, 50),
                             rng.integers(0, 32, 50), shape=(32, 32))
    x = np.random.default_rng(1).standard_normal((32, 2)).astype(
        np.float32)
    from matrel_tpu.core.blockmatrix import BlockMatrix
    e = A.multiply(BlockMatrix.from_numpy(x, mesh=mesh8).expr())
    assert infer_dtype(e) == np.dtype("float32")
    A.vals = A.vals.astype(np.float64)          # forge a future dtype
    with pytest.raises(TypeError, match="float32"):
        infer_dtype(A.multiply(
            BlockMatrix.from_numpy(x, mesh=mesh8).expr()))


class TestWidePlans:
    """The plans a COOMatrix keeps for the k-wide product (PR 37): the
    layout by who will run them, the transposed orientation in source
    panels where the dense side is no one gather table, one build an
    orientation."""

    @pytest.fixture
    def on_one_chip(self, monkeypatch):
        from matrel_tpu import config as config_lib
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import spmv as spmv_lib
        was = config_lib._default_config
        config_lib.set_default_config(MatrelConfig(pallas_interpret=True))
        monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
        monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
        # no room for a slab: at this length a line pays from 10 entries
        # on (TestDenseLines gives the dense part room)
        monkeypatch.setattr(coo_lib, "_DENSE_SHARE", 0.0)
        # and a hub table's row pays from 10,000 entries on: at this
        # scale 128 flat sources hold 4,000, and the rule's own price
        # (PR 51: an orientation's own plan asks it) would make each a hub
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 10_000)
        yield
        config_lib._default_config = was

    def _skewed(self, rng, shape=(2600, 900), m=30_000):
        rows = rng.integers(0, shape[0], m)
        rows[:m // 2] = rng.integers(0, 300, m // 2)       # a hub block
        cols = rng.integers(0, shape[1], m)
        return COOMatrix.from_edges(
            rows, cols, rng.standard_normal(m).astype(np.float32),
            shape=shape)

    def test_layout_follows_the_executor(self, rng, on_one_chip,
                                         monkeypatch):
        from matrel_tpu.core import coo as coo_lib
        A = self._skewed(rng)
        assert A._get_plan().chunk_block is not None       # auto: chunks
        assert A._get_plan().hubs is None
        monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "blocks")
        B = self._skewed(rng)
        assert B._get_plan().chunk_block is None
        # the real choice: off a one-device Pallas backend, blocks
        monkeypatch.undo()
        assert coo_lib._plan_layout() == "blocks"

    @pytest.mark.parametrize("first", ["wide", "own"])
    def test_skewed_sources_hub_chunks_stay_with_the_own_plan(self, rng,
                                                              on_one_chip,
                                                              first):
        """An orientation's own plan — the matvec's, the (max | min)
        reduction's — has hub chunks where the sources are skewed (PR
        51: the plan PageRank would build); the k-wide product, which
        gains nothing from them, runs one of its own without, whichever
        is asked for first: two builds, and neither twice. Its plan's
        slots lie by destination row (PR 38), five tables go to the
        device, and it lowers to its one kernel."""
        import jax
        import jax.numpy as jnp
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import pallas_spmv as pc
        from matrel_tpu.ops import spmv as spmv_lib
        m, shape = 60_000, (2600, 900)
        rows = rng.integers(0, shape[0], m)
        cols = np.where(rng.random(m) < 0.7, rng.integers(0, 40, m),
                        rng.integers(0, shape[1], m))   # 40 hot columns
        vals = rng.standard_normal(m).astype(np.float32)
        A = COOMatrix.from_edges(rows, cols, vals, shape=shape)
        builds = coo_lib.plan_builds()
        if first == "wide":
            plan = A._get_wide_plan()
            assert coo_lib.plan_builds() == builds + 1
            own = A._get_plan()
        else:
            own = A._get_plan()
            assert coo_lib.plan_builds() == builds + 1
            plan = A._get_wide_plan()
        assert coo_lib.plan_builds() == builds + 2
        assert A._get_wide_plan() is plan and A._get_plan() is own
        assert coo_lib.plan_builds() == builds + 2
        assert own.hubs is not None and own.hubs.ids.size == 128
        assert len(pc.compact_tables(own)) == 11
        assert spmv_lib.rows_in_order(own)
        assert plan.hubs is None and plan.chunk_block is not None
        assert len(pc.compact_tables(plan)) == 5
        real = plan.val != 0
        for b in np.unique(plan.chunk_block):
            mine = plan.chunk_block == b
            assert (np.diff(plan.off[mine][real[mine]]) >= 0).all()
        _, tall = pc.wide_windows(plan)
        assert sum(tall.values()) == plan.src8.shape[0]
        static, part_statics, part_arrays = pc.plan_operands(plan)
        text = jax.jit(lambda pa, x: pc.compact_matmat_parts(
            static, part_statics, pa, x, 3, False)).trace(
            part_arrays, jax.ShapeDtypeStruct((shape[1], 16), jnp.float32)
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert "matrel_spmm_scatter_chunks" in text
        assert "matrel_spmv_scatter_hubs" not in text
        # both answer: the matvec off the hub table, the product without
        x = rng.standard_normal((shape[1], 3)).astype(np.float32)
        want = A.to_dense() @ x
        got = np.asarray(A.matmat(jnp.asarray(x)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(np.asarray(A.matvec(x[:, 0])),
                                   want[:, 0], rtol=2e-5, atol=2e-4)

    @pytest.mark.parametrize("first", ["wide", "own"])
    def test_one_table_is_one_plan_shared_with_the_matvec(self, rng,
                                                          on_one_chip,
                                                          first):
        """Flat sources: the rule takes no hub, and one build serves
        both, whichever asks first."""
        from matrel_tpu.core import coo as coo_lib
        A = self._skewed(rng)
        builds = coo_lib.plan_builds()
        if first == "own":
            A._get_plan(), A._get_plan_t()
        assert A._get_wide_plan() is A._get_plan()
        assert A._get_wide_plan(transposed=True) is A._get_plan_t()
        assert coo_lib.plan_builds() == builds + 2

    def test_source_panels_where_the_table_is_too_tall(self, rng,
                                                       on_one_chip,
                                                       monkeypatch):
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import spmv as spmv_lib
        monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 1000 * 512)
        A = self._skewed(rng)
        builds = coo_lib.plan_builds()
        fwd = A._get_wide_plan()                 # 900 sources: one table
        bwd = A._get_wide_plan(transposed=True)  # 2,600: three
        assert fwd is A._get_plan()
        assert isinstance(bwd, coo_lib.PanelledPlan)
        assert [c0 for c0, _ in bwd.parts] == [0, 872, 1744]
        assert [p.n_cols for _, p in bwd.parts] == [872, 872, 856]
        assert all(p.n_rows == 900 for _, p in bwd.parts)
        assert sum(int((np.asarray(p.val) != 0).sum())
                   for _, p in bwd.parts) == A.nnz
        assert coo_lib.plan_builds() == builds + 2
        # kept with the matrix, and with its transpose view
        assert A._get_wide_plan(transposed=True) is bwd
        assert A.T._get_wide_plan() is bwd
        assert coo_lib.plan_builds() == builds + 2
        facts = coo_lib.plan_facts(bwd, A.nnz)
        assert facts["source_panels"] == 3 and facts["table"] == "panelled"
        assert facts["slots"] == sum(p.src8.size for _, p in bwd.parts)
        # the product over the parts is the product
        X = rng.standard_normal((2600, 7)).astype(np.float32)
        got = np.asarray(A.T.matmat(X))
        np.testing.assert_allclose(got, A.to_dense().T @ X, rtol=2e-5,
                                   atol=2e-4)

    @pytest.mark.parametrize("which", ["forward", "transposed_in_panels",
                                       "blocks_layout"])
    def test_plan_facts_count_the_windowed_chunks(self, rng, on_one_chip,
                                                  monkeypatch, which):
        """``windowed_chunks`` (PR 38): of the chunks the k-wide scatter
        walks, those whose rows lie in a window shorter than the block,
        and ``window_rows`` (PR 49), the same chunks by the rung of the
        ladder they take — over every source panel, read off the plans'
        own tables."""
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import pallas_spmv as pc
        from matrel_tpu.ops import spmv as spmv_lib
        monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 1000 * 512)
        if which == "blocks_layout":
            monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "blocks")
        A = self._skewed(rng)
        plan = A._get_wide_plan(transposed=which == "transposed_in_panels")
        facts = coo_lib.plan_facts(plan, A.nnz)
        parts = [p for _, p in coo_lib.plan_parts(plan)]
        assert len(parts) == (3 if which == "transposed_in_panels" else 1)
        want = {128: 0, 256: 0}
        for p in parts:
            src = np.asarray(p.src8).astype(np.int64) * 8 + np.asarray(p.lane)
            off = np.asarray(p.off)
            if p.chunk_block is None:           # a row walked as chunks
                width = pc._walk(off.shape[1] // 128) * 128
                off, src = off.reshape(-1, width), src.reshape(-1, width)
            for o, real in zip(off, src != p.n_cols):
                rows = o[real]
                for tall in want:           # the shortest rung that holds
                    lo = (min(rows.min() // 8 * 8, 512 - tall)
                          if rows.size else 0)
                    if rows.size == 0 or rows.max() - lo < tall:
                        want[tall] += 1
                        break
        assert facts["window_rows"] == {str(h): n for h, n in want.items()}
        assert facts["windowed_chunks"] == sum(want.values()) > 0
        if which == "blocks_layout":
            assert facts["layout"] == "blocks"
        else:           # the hub block's chunks at the least
            assert facts["layout"] == "chunks"
        # said again from the memo, and by the product's own record
        assert coo_lib.plan_facts(plan, A.nnz) == facts

    @pytest.mark.parametrize("transposed", [False, True],
                             ids=["forward", "transposed"])
    def test_a_window_as_tall_as_the_chunk_needs(self, rng, on_one_chip,
                                                 ladder_cells, transposed):
        """The ladder (PR 49): over a matrix whose chunks take a 128-row
        window, a 256-row one and the whole block, ``window_rows`` says
        how many take which, and the k-wide product is the product with
        the windows withheld (every chunk the whole block's one-hot),
        bit for bit: the same slots into the same rows through the same
        parts, only the one-hot's all-zero rows not multiplied."""
        import jax
        import jax.numpy as jnp
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import pallas_spmv as pc
        from matrel_tpu.ops import spmv as spmv_lib
        rows, cols = ladder_cells
        A = COOMatrix.from_edges(
            rows, cols, rng.standard_normal(rows.size).astype(np.float32),
            shape=(2048, 2048))
        plan = A._get_wide_plan(transposed=transposed)
        facts = coo_lib.plan_facts(plan, A.nnz)
        assert facts["layout"] == "chunks" and facts["source_panels"] == 1
        tall = facts["window_rows"]
        assert sorted(tall) == ["128", "256"]
        assert tall["256"] == 1 and tall["128"] > 8
        assert facts["windowed_chunks"] == sum(tall.values()) \
            == facts["chunks"] - 1
        height = spmv_lib.window_of(pc.wide_windows(plan)[0][0])[1]
        assert height[plan.chunk_block == 1][:3].tolist() == [0, 256, 128]
        X = rng.standard_normal((2048, 128)).astype(np.float32)
        got = np.asarray((A.T if transposed else A).matmat(X))
        static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
        withheld = np.asarray(jax.jit(
            lambda t, x: pc.compact_matmat_apply(static, t, (), x, 3, True)
        )(pc.compact_tables(plan), jnp.asarray(X)))
        assert plan.overflow == ()
        np.testing.assert_array_equal(got, withheld)
        dense = (A.to_dense().T if transposed else A.to_dense())
        want = dense.astype(np.float64) @ X
        assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-7

    def test_chunked_plans_serve_every_op(self, rng, on_one_chip):
        """matvec, rmatvec and matmat of a matrix whose plans lie in
        chunks all take the compact executors; shard() builds the
        blocks layout its mesh needs."""
        A = self._skewed(rng)
        dense = A.to_dense()
        x = rng.standard_normal(900).astype(np.float32)
        y = rng.standard_normal(2600).astype(np.float32)
        assert A._get_plan().chunk_block is not None
        np.testing.assert_allclose(np.asarray(A.matvec(x)), dense @ x,
                                   rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(np.asarray(A.rmatvec(y)), dense.T @ y,
                                   rtol=2e-5, atol=2e-4)
        assert A._get_plan_t().chunk_block is not None
        X = rng.standard_normal((900, 3)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matmat(X)), dense @ X,
                                   rtol=2e-5, atol=2e-4)


def _tables_hash(plan):
    """sha256 of a wide plan's statics and compact host tables."""
    import hashlib
    from matrel_tpu.core import coo as coo_lib
    h = hashlib.sha256()
    for col0, p in coo_lib.plan_parts(plan):
        h.update(str((col0, p.n_rows, p.n_cols, p.block)).encode())
        for a in (p.src8, p.lane, p.off, p.val, p.chunk_block):
            h.update(b"-" if a is None
                     else np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _program_hash(plan, n_in):
    """sha256 of the k-wide product's program lowered for the chip, the
    Mosaic kernels' serialized bodies and the source locations (file
    paths, line numbers) taken out."""
    import hashlib
    import re
    import jax
    import jax.numpy as jnp
    from matrel_tpu.ops import pallas_spmv as pc
    static, statics, arrays = pc.plan_operands(plan)
    text = jax.jit(lambda pa, x: pc.compact_matmat_parts(
        static, statics, pa, x, 3, False)).trace(
        arrays, jax.ShapeDtypeStruct((n_in, 128), jnp.float32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    text = re.sub(r'backend_config = "[^"]*"', "", text)
    return hashlib.sha256(re.sub(r"loc\([^)]*\)", "", text).encode()
                          ).hexdigest(), text


class TestDenseLines:
    """The dense part of a COOMatrix's k-wide plans (PR 43): the lines of
    one axis that hold more entries than a dense line costs lie in ONE
    float32 slab, shared by both orientations and the transpose view and
    multiplied on the MXU; the compact plans hold the rest; a matrix no
    line of which pays keeps the plans it had."""

    SHAPE, HOT = (2600, 4000), 128

    @pytest.fixture
    def on_one_chip(self, monkeypatch):
        """As TestWidePlans', with room for a slab, and a gather table
        of 3,000 rows at the most (the 4,000 columns are two source
        panels, the 2,600 rows one)."""
        from matrel_tpu import config as config_lib
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import spmv as spmv_lib
        was = config_lib._default_config
        config_lib.set_default_config(MatrelConfig(pallas_interpret=True))
        monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
        monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
        monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 3000 * 512)
        yield
        config_lib._default_config = was

    def _hot_columns(self, rng, repeats=0):
        """128 hot columns of ~200 entries (a column of 2,600 cells pays
        from 10 on) over a tail of 3,000 entries, under one a column (a
        row of 4,000 cells pays from 15 on; a row holds 11, and the few
        groups of rows that do pay hold a tenth of what the columns do);
        values that no bfloat16 holds; ``repeats`` cells listed twice."""
        n, m = self.SHAPE
        hot = rng.choice(m, self.HOT, replace=False)
        rows = rng.integers(0, n, 28_600)
        cols = np.concatenate([hot[rng.integers(0, self.HOT, 25_600)],
                               rng.integers(0, m, 3_000)])
        if repeats:
            again = rng.integers(0, rows.size, repeats)
            rows = np.concatenate([rows, rows[again]])
            cols = np.concatenate([cols, cols[again]])
        return hot, COOMatrix.from_edges(
            rows, cols, rng.standard_normal(rows.size).astype(np.float32),
            shape=self.SHAPE)

    def test_one_slab_for_both_orientations_and_the_view(self, rng,
                                                         on_one_chip):
        from matrel_tpu.core import coo as coo_lib
        hot, A = self._hot_columns(rng)
        builds = coo_lib.plan_builds()
        fwd = A._get_wide_plan()
        bwd = A._get_wide_plan(transposed=True)
        assert coo_lib.plan_builds() == builds + 2   # one an orientation
        assert fwd.dense is bwd.dense is A._dense_lines()
        assert (fwd.dense_role, bwd.dense_role) == ("sources",
                                                    "destinations")
        dense = fwd.dense
        assert dense.axis == 1 and set(dense.lines) == set(hot)
        assert dense.slab.shape == (2600, 128)
        assert dense.slab.dtype == np.float32
        # the view shares plans and slab, and reads the axis flipped
        assert A.T._get_wide_plan() is bwd
        assert A.T._get_wide_plan(transposed=True) is fwd
        assert A.T._dense_lines() is dense
        assert coo_lib.plan_builds() == builds + 2
        held = int(np.isin(A.cols, hot).sum())
        for plan, panels in ((fwd, 2), (bwd, 1)):
            facts = coo_lib.plan_facts(plan, A.nnz)
            assert facts["entries"] + facts["dense_entries"] == A.nnz
            assert facts["dense_entries"] == held > 0.85 * A.nnz
            assert facts["dense_lines"] == 128
            assert facts["dense_axis"] == "columns"
            assert facts["dense_bytes"] == 4 * 2600 * 128
            assert facts["dense_dtype"] == "float32"
            assert facts["source_panels"] == panels
            # the slots hold the residual: a padding, not a share
            assert facts["entries"] <= facts["slots"]
            assert sum(int((np.asarray(p.val) != 0).sum())
                       for _, p in plan.parts) == A.nnz - held
            # tables, the largest panel, the slab once
            assert facts["plan_bytes"] >= facts["dense_bytes"] + 13 * \
                facts["slots"]
            assert facts["plan_bytes"] < 2 * facts["dense_bytes"] + \
                (13 + 536) * facts["slots"]
        # the slab is the hot columns, summed in float32
        want = A.to_dense()[:, dense.lines]
        np.testing.assert_allclose(np.asarray(dense.slab), want, atol=1e-6)

    @pytest.mark.parametrize("k", [8, 128])
    @pytest.mark.parametrize("how", ["forward", "transposed", "view"])
    def test_products_match_float64(self, rng, on_one_chip, k, how):
        _, A = self._hot_columns(rng)
        D = A.to_dense().astype(np.float64)
        if how == "forward":
            X = rng.standard_normal((4000, k)).astype(np.float32)
            got, want = A.matmat(X), D @ X
        else:
            X = rng.standard_normal((2600, k)).astype(np.float32)
            got = (A.T.matmat(X) if how == "view" else
                   self._transposed_product(A, X))
            want = D.T @ X
        assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() \
            < 1e-6

    @staticmethod
    def _transposed_product(A, X):
        from matrel_tpu.ops import pallas_spmv as pc
        return pc.spmm_compact(A._get_wide_plan(transposed=True), X)

    def test_repeated_cells_add(self, rng, on_one_chip):
        """A coordinate list may repeat a cell: its values add, in the
        slab as in the tables."""
        from matrel_tpu.core import coo as coo_lib
        _, A = self._hot_columns(rng, repeats=4_000)
        keys = A.rows * 4000 + A.cols
        assert np.unique(keys).size < keys.size - 3_000
        D = A.to_dense().astype(np.float64)
        X = rng.standard_normal((4000, 8)).astype(np.float32)
        Z = rng.standard_normal((2600, 8)).astype(np.float32)
        for got, want in ((A.matmat(X), D @ X), (A.T.matmat(Z), D.T @ Z)):
            assert np.abs(np.asarray(got) - want).max() / \
                np.abs(want).max() < 1e-6
        facts = coo_lib.plan_facts(A._get_wide_plan(), A.nnz)
        assert facts["entries"] + facts["dense_entries"] == A.nnz

    def test_passes_govern_the_compact_part_of_the_whole_product(
            self, rng, on_one_chip):
        """``spmm_compact(plan, X, passes=2)`` on a plan with a dense
        part is still the whole product (the benchmark's program
        controls call it so): the dense part at ``highest``, the compact
        part at the passes asked for."""
        from matrel_tpu.ops import pallas_spmv as pc
        _, A = self._hot_columns(rng)
        D = A.to_dense().astype(np.float64)
        X = rng.standard_normal((2600, 16)).astype(np.float32)
        plan = A._get_wide_plan(transposed=True)
        want = D.T @ X
        err = {p: np.abs(np.asarray(pc.spmm_compact(plan, X, passes=p))
                         - want).max() / np.abs(want).max()
               for p in (3, 2, 1)}
        assert err[3] < 1e-6 < err[2] < 1e-4 < err[1] < 2e-2

    def test_a_long_contraction_adds_in_panels(self, rng, on_one_chip,
                                               monkeypatch):
        """A matrix taller than ``LONG_CONTRACTION``: where the dense
        lines are the product's destinations the slab's 140,000 rows are
        contracted in panels of 8,192 (one dot over them drifts on the
        MXU, PR 31) and the answer is float64's to 1e-6."""
        import jax
        import jax.numpy as jnp
        from matrel_tpu.ops import pallas_spmv as pc
        from matrel_tpu.ops import spmv as spmv_lib
        from matrel_tpu.parallel import strategies
        monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 64 << 20)
        n, m = 140_000, 2_000
        assert n > strategies.LONG_CONTRACTION
        hot = rng.choice(m, 128, replace=False)
        rows = rng.integers(0, n, 135_000)
        cols = np.concatenate([hot[rng.integers(0, 128, 128_000)],
                               rng.integers(0, m, 7_000)])
        A = COOMatrix.from_edges(
            rows, cols, rng.integers(1, 6, rows.size).astype(np.float32),
            shape=(n, m))
        plan = A._get_wide_plan(transposed=True)
        assert plan.dense_role == "destinations"
        assert plan.dense.slab.shape == (n, 128)
        X = rng.random((n, 8), dtype=np.float32)
        want = np.zeros((m, 8))
        np.add.at(want, A.cols, A.vals[:, None].astype(np.float64) * X[A.rows])
        got = np.asarray(pc.spmm_compact(plan, X))
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-6
        static, statics, arrays = pc.plan_operands(plan)
        text = jax.jit(lambda pa, x: pc.compact_matmat_parts(
            static, statics, pa, x, 3, False)).trace(
            arrays, jax.ShapeDtypeStruct((n, 8), jnp.float32)
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert f"{strategies.ACC_PANEL_ROWS}x128xf32" in text   # a panel
        assert "stablehlo.while" in text

    def _starred(self, rng, repeats=0, zeros=0):
        """``_hot_columns`` with distinct cells and values 1 to 5, every
        one a bfloat16's; then ``repeats`` of the cells listed twice, or
        ``zeros`` of the values zero."""
        n, m = self.SHAPE
        _, A = self._hot_columns(rng)
        keys = np.unique(A.rows * m + A.cols)
        vals = rng.integers(1, 6, keys.size).astype(np.float32)
        vals[:zeros] = 0
        keys = np.concatenate([keys, keys[:repeats]])
        vals = np.concatenate([vals, vals[:repeats]])
        return COOMatrix.from_edges(keys // m, keys % m, vals,
                                    shape=self.SHAPE)

    @pytest.mark.parametrize("k", [8, 128])
    def test_values_a_bfloat16_holds_lie_in_a_bfloat16_slab(self, rng,
                                                            on_one_chip, k):
        """Ratings 1 to 5 in distinct cells: the slab is bfloat16, exact,
        a line half the bytes (and the MXU's passes) of a float32 one;
        times the dense side's three bfloat16 parts it gives float64's
        product to 1e-6 in both orientations."""
        from matrel_tpu.core import coo as coo_lib
        A = self._starred(rng)
        fwd = A._get_wide_plan()
        bwd = A._get_wide_plan(transposed=True)
        dense = fwd.dense
        assert dense is bwd.dense and dense.dtype == "bfloat16"
        assert str(dense.slab.dtype) == "bfloat16"
        facts = coo_lib.plan_facts(fwd, A.nnz)
        assert facts["dense_dtype"] == "bfloat16"
        assert facts["dense_lines"] == 128
        assert facts["dense_axis"] == "columns"
        assert facts["dense_bytes"] == 2 * 2600 * 128    # half a float32's
        assert facts["entries"] + facts["dense_entries"] == A.nnz
        D = A.to_dense()
        np.testing.assert_array_equal(
            np.asarray(dense.slab[:, :dense.lines.size], np.float32),
            D[:, dense.lines])
        D = D.astype(np.float64)
        X = rng.standard_normal((4000, k)).astype(np.float32)
        Z = rng.standard_normal((2600, k)).astype(np.float32)
        for got, want in ((A.matmat(X), D @ X), (A.T.matmat(Z), D.T @ Z)):
            assert np.abs(np.asarray(got) - want).max() / \
                np.abs(want).max() < 1e-6

    @pytest.mark.parametrize("how", ["repeats", "zeros"])
    def test_a_bfloat16_slab_only_behind_the_check(self, rng, on_one_chip,
                                                   how):
        """A cell listed twice may sum to no bfloat16 (and a zero would
        hide one from the count that finds it): the fill says no and the
        lines are chosen again for a float32 slab; the products are
        float64's either way, and an orientation is still one build."""
        from matrel_tpu.core import coo as coo_lib
        A = self._starred(rng, **{how: 300})
        builds = coo_lib.plan_builds()
        fwd = A._get_wide_plan()
        bwd = A._get_wide_plan(transposed=True)
        assert coo_lib.plan_builds() == builds + 2
        assert fwd.dense is bwd.dense and fwd.dense.dtype == "float32"
        assert fwd.dense.slab.dtype == np.float32
        facts = coo_lib.plan_facts(bwd, A.nnz)
        assert facts["dense_lines"] == 128
        assert facts["entries"] + facts["dense_entries"] == A.nnz
        D = A.to_dense().astype(np.float64)
        X = rng.standard_normal((4000, 8)).astype(np.float32)
        Z = rng.standard_normal((2600, 8)).astype(np.float32)
        for got, want in ((A.matmat(X), D @ X), (A.T.matmat(Z), D.T @ Z)):
            assert np.abs(np.asarray(got) - want).max() / \
                np.abs(want).max() < 1e-6

    def test_a_uniform_matrix_keeps_its_plans_and_its_program(
            self, rng, on_one_chip, monkeypatch):
        """No line of a uniform matrix pays: no slab, the orientation's
        own plan where the dense side is one table and the source panels
        it had where it is not, table for table and, lowered for the
        chip, instruction for instruction what the recipe before PR 43
        gives (by hash; the same hashes came from the parent's tree,
        CHANGES.md)."""
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import spmv as spmv_lib
        monkeypatch.setattr(spmv_lib, "_FAST_TABLE_BYTES", 8000 * 512)
        r = np.random.default_rng(43)
        shape, m = (20_000, 6_000), 20_000
        A = COOMatrix.from_edges(
            r.integers(0, shape[0], m), r.integers(0, shape[1], m),
            r.standard_normal(m).astype(np.float32), shape=shape)
        fwd = A._get_wide_plan()
        bwd = A._get_wide_plan(transposed=True)
        assert A._dense_lines() is None
        assert fwd is A._get_plan()
        assert isinstance(bwd, coo_lib.PanelledPlan) and bwd.dense is None
        assert "dense_lines" not in coo_lib.plan_facts(bwd, A.nnz)
        assert coo_lib.plan_facts(bwd, A.nnz)["entries"] == A.nnz
        # the recipe as it was: the transposed orientation a plan a range
        # of 6,672 sources, built from the entries whose source is there
        parts = []
        for col0 in range(0, shape[0], 6_672):
            n_part = min(6_672, shape[0] - col0)
            sel = np.flatnonzero((A.rows >= col0) & (A.rows < col0 + n_part))
            parts.append((col0, spmv_lib.build_spmv_plan(
                A.cols[sel], A.rows[sel] - col0, A.vals[sel],
                n_rows=shape[1], n_cols=n_part, layout="auto", hubs=False)))
        was = coo_lib.PanelledPlan(n_rows=shape[1], n_cols=shape[0],
                                   block=parts[0][1].block,
                                   parts=tuple(parts))
        assert _tables_hash(bwd) == _tables_hash(was)
        got, text = _program_hash(bwd, shape[0])
        assert got == _program_hash(was, shape[0])[0]
        assert "dot_general" not in text

    def test_a_small_budget_keeps_the_densest_lines_that_fit(
            self, rng, on_one_chip, monkeypatch):
        """256 columns pay; where the device has room for one group of
        128 the slab holds the 128 densest, and the plan's reckoned bytes
        stay inside what the device hands out."""
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.ops import pallas_spmv as pc
        n, m = self.SHAPE
        hot = rng.choice(m, 256, replace=False)
        deg = np.r_[np.full(128, 150), np.full(128, 60)]   # two groups
        cols = np.concatenate([np.repeat(hot, deg),
                               rng.integers(0, m, 2_000)])
        rows = rng.integers(0, n, cols.size)
        vals = rng.standard_normal(cols.size).astype(np.float32)

        def lines(limit):
            monkeypatch.setattr(pc, "_hbm_limit", lambda: limit)
            A = COOMatrix.from_edges(rows, cols, vals, shape=self.SHAPE)
            return A, A._dense_lines()

        _, roomy = lines(1 << 30)
        assert roomy.lines.size == 256
        # a slab of 128 lines is 1.33 MB; of 16 MB the tables, a panel
        # and the product's dense sides leave 1.7
        limit = 16 << 20
        A, tight = lines(limit)
        assert tight.lines.size == 128
        degrees = np.bincount(cols, minlength=m)
        assert degrees[tight.lines].min() >= np.sort(degrees)[-128]
        facts = coo_lib.plan_facts(A._get_wide_plan(), A.nnz)
        assert facts["dense_bytes"] <= coo_lib._DENSE_SHARE * limit
        assert facts["plan_bytes"] < limit
        X = rng.standard_normal((m, 8)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matmat(X)),
                                   A.to_dense() @ X, rtol=2e-5, atol=2e-4)
        _, none = lines(4 << 20)        # no room for a group: no slab
        assert none is None

    def test_the_rule_reads_rows_as_well_as_columns(self, rng, on_one_chip):
        """The dense lines are those of the axis whose paying lines hold
        more: a matrix with hot ROWS gets a slab of rows, its forward
        product's destinations."""
        from matrel_tpu.core import coo as coo_lib
        _, At = self._hot_columns(rng)
        A = COOMatrix.from_edges(At.cols, At.rows, At.vals,
                                 shape=self.SHAPE[::-1])
        plan = A._get_wide_plan()
        assert plan.dense.axis == 0 and plan.dense_role == "destinations"
        assert coo_lib.plan_facts(plan, A.nnz)["dense_axis"] == "rows"
        X = rng.standard_normal((2600, 8)).astype(np.float32)
        want = A.to_dense().astype(np.float64) @ X
        assert np.abs(np.asarray(A.matmat(X)) - want).max() / \
            np.abs(want).max() < 1e-6
        # the first-built matrix's columns are its view's rows
        assert At.T._get_wide_plan().dense_axis == "rows"

    def test_a_matrix_on_a_mesh_and_a_matvec_get_what_they_had(self, rng):
        """Off the one-device compact executor (``_plan_layout`` says
        ``blocks``) no slab is chosen, and a k = 1 product never asks."""
        from matrel_tpu.core import coo as coo_lib
        _, A = self._hot_columns(rng)
        assert coo_lib._plan_layout() == "blocks"
        assert A._get_wide_plan() is A._get_plan()
        assert A._get_wide_plan(transposed=True) is A._get_plan_t()
        assert A._dense == []
