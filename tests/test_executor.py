"""End-to-end execution tests — the MatrixOperatorSuite analogue
(SURVEY.md §4): DSL queries on the simulated 8-device mesh, numerics vs
numpy oracles, including ragged (padded) shapes."""

import numpy as np
import pytest

from matrel_tpu.core.blockmatrix import BlockMatrix


def bm(arr, mesh, **kw):
    return BlockMatrix.from_numpy(np.asarray(arr, dtype=np.float32), mesh=mesh, **kw)


@pytest.fixture()
def mats(mesh8, rng):
    a = rng.standard_normal((24, 16)).astype(np.float32)
    b = rng.standard_normal((16, 24)).astype(np.float32)
    return a, b, bm(a, mesh8), bm(b, mesh8)


class TestDenseOps:
    def test_matmul(self, mats):
        a, b, A, B = mats
        out = A.multiply(B).compute().to_numpy()
        np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-5)

    def test_matmul_ragged(self, mesh8, rng):
        a = rng.standard_normal((13, 9)).astype(np.float32)
        b = rng.standard_normal((9, 11)).astype(np.float32)
        out = bm(a, mesh8).multiply(bm(b, mesh8)).compute().to_numpy()
        assert out.shape == (13, 11)
        np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-5)

    def test_transpose(self, mats):
        a, _, A, _ = mats
        np.testing.assert_allclose(A.t().compute().to_numpy(), a.T, rtol=1e-6)

    def test_add_sub_elemwise(self, mesh8, rng):
        a = rng.standard_normal((10, 10)).astype(np.float32)
        b = rng.standard_normal((10, 10)).astype(np.float32)
        A, B = bm(a, mesh8), bm(b, mesh8)
        np.testing.assert_allclose(A.add(B).compute().to_numpy(), a + b, rtol=1e-5)
        np.testing.assert_allclose(A.subtract(B).compute().to_numpy(), a - b, rtol=1e-5)
        np.testing.assert_allclose(
            A.elem_multiply(B).compute().to_numpy(), a * b, rtol=1e-5)

    def test_divide_safe(self, mesh8):
        a = np.array([[1.0, 2.0], [3.0, 4.0]], dtype=np.float32)
        b = np.array([[2.0, 0.0], [1.0, 4.0]], dtype=np.float32)
        out = bm(a, mesh8).divide(bm(b, mesh8)).compute().to_numpy()
        # division by zero yields 0 (sparse-relational semantics: missing)
        np.testing.assert_allclose(out, [[0.5, 0.0], [3.0, 1.0]], rtol=1e-6)

    def test_scalar_ops_mask_padding(self, mesh8, rng):
        a = rng.standard_normal((5, 5)).astype(np.float32)  # heavily padded
        A = bm(a, mesh8)
        out = A.add_scalar(3.0).compute()
        np.testing.assert_allclose(out.to_numpy(), a + 3.0, rtol=1e-5)
        # padding must remain zero after scalar add (invariant)
        full = np.asarray(out.data)
        assert np.all(full[5:, :] == 0)

    def test_power(self, mesh8):
        a = np.array([[1.0, 2.0], [3.0, 4.0]], dtype=np.float32)
        out = bm(a, mesh8).power(2.0).compute().to_numpy()
        np.testing.assert_allclose(out, a ** 2, rtol=1e-5)

    def test_chained_expression(self, mats):
        a, b, A, B = mats
        # (A·B)ᵀ + (A·B)ᵀ computed via DSL; exercises rewrite + CSE by memo
        e = A.multiply(B).t().add(A.multiply(B).t())
        np.testing.assert_allclose(
            e.compute().to_numpy(), 2 * (a @ b).T, rtol=1e-4, atol=1e-5)


class TestAggregates:
    def test_row_col_sums(self, mesh8, rng):
        a = rng.standard_normal((9, 7)).astype(np.float32)
        A = bm(a, mesh8)
        np.testing.assert_allclose(
            A.row_sum().compute().to_numpy(), a.sum(1, keepdims=True),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            A.col_sum().compute().to_numpy(), a.sum(0, keepdims=True),
            rtol=1e-4, atol=1e-5)

    def test_sum_trace(self, mesh8, rng):
        a = rng.standard_normal((8, 8)).astype(np.float32)
        A = bm(a, mesh8)
        assert A.sum().compute().to_numpy()[0, 0] == pytest.approx(a.sum(), rel=1e-4)
        assert A.trace().compute().to_numpy()[0, 0] == pytest.approx(
            np.trace(a), rel=1e-4)

    def test_max_min_with_negative_entries(self, mesh8):
        # all-negative matrix, ragged: padding zeros must NOT win the max
        a = -np.abs(np.random.default_rng(0).standard_normal((5, 3))).astype(np.float32) - 1
        A = bm(a, mesh8)
        out = A.expr().row_max().compute().to_numpy()
        np.testing.assert_allclose(out, a.max(1, keepdims=True), rtol=1e-5)
        out = A.expr().col_min().compute().to_numpy()
        np.testing.assert_allclose(out, a.min(0, keepdims=True), rtol=1e-5)

    def test_count_avg(self, mesh8):
        a = np.array([[1.0, 0.0, 2.0], [0.0, 0.0, 3.0]], dtype=np.float32)
        A = bm(a, mesh8)
        np.testing.assert_allclose(
            A.expr().row_count().compute().to_numpy(), [[2.0], [1.0]])
        np.testing.assert_allclose(
            A.expr().row_avg().compute().to_numpy(), [[1.5], [3.0]])

    def test_rowsum_pushdown_numerics(self, mesh8, rng):
        # optimized plan (A·rowSum(B)) must equal unoptimized rowSum(A·B)
        a = rng.standard_normal((12, 20)).astype(np.float32)
        b = rng.standard_normal((20, 12)).astype(np.float32)
        A, B = bm(a, mesh8), bm(b, mesh8)
        out = A.multiply(B).row_sum().compute().to_numpy()
        np.testing.assert_allclose(out, (a @ b).sum(1, keepdims=True),
                                   rtol=1e-4, atol=1e-4)


class TestVecRank1:
    def test_vec_column_major(self, mesh8):
        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        out = bm(a, mesh8).vec().compute().to_numpy()
        np.testing.assert_allclose(out, a.T.reshape(-1, 1))

    def test_rank_one_update(self, mesh8, rng):
        a = rng.standard_normal((6, 4)).astype(np.float32)
        u = rng.standard_normal((6, 1)).astype(np.float32)
        v = rng.standard_normal((4, 1)).astype(np.float32)
        out = bm(a, mesh8).rank_one_update(bm(u, mesh8), bm(v, mesh8))
        np.testing.assert_allclose(out.compute().to_numpy(), a + u @ v.T,
                                   rtol=1e-4, atol=1e-5)


class TestNormalEquations:
    def test_linreg_normal_equations(self, mesh8, rng):
        # the reference's flagship workload: (XᵀX)⁻¹Xᵀy pieces via the IR
        x = rng.standard_normal((64, 8)).astype(np.float32)
        y = rng.standard_normal((64, 1)).astype(np.float32)
        X, Y = bm(x, mesh8), bm(y, mesh8)
        xtx = X.t().multiply(X).compute().to_numpy()
        xty = X.t().multiply(Y).compute().to_numpy()
        np.testing.assert_allclose(xtx, x.T @ x, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(xty, x.T @ y, rtol=1e-4, atol=1e-4)
        theta = np.linalg.solve(xtx, xty)
        oracle = np.linalg.lstsq(x, y, rcond=None)[0]
        np.testing.assert_allclose(theta, oracle, rtol=1e-2, atol=1e-3)


class TestBf16Pipeline:
    def test_bf16_end_to_end_keeps_dtype(self, mesh8, rng):
        import jax.numpy as jnp
        a = rng.standard_normal((16, 16)).astype(np.float32)
        b = rng.standard_normal((16, 16)).astype(np.float32)
        A = bm(a, mesh8, dtype="bfloat16")
        B = bm(b, mesh8, dtype="bfloat16")
        out = A.multiply(B).compute()
        assert out.dtype == jnp.bfloat16  # f32 accumulate, bf16 storage
        np.testing.assert_allclose(out.to_numpy().astype(np.float32),
                                   a @ b, rtol=3e-2, atol=3e-1)

    def test_mixed_mesh_leaves_rejected(self, mesh8, mesh_square, rng):
        from matrel_tpu.executor import compile_expr
        a = bm(rng.standard_normal((8, 8)).astype(np.float32), mesh8)
        b = bm(rng.standard_normal((8, 8)).astype(np.float32), mesh_square)
        with pytest.raises(ValueError, match="mesh"):
            compile_expr(a.expr().multiply(b.expr()))


class TestBoundRunner:
    def test_matches_run_and_rebinds(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        a = rng.standard_normal((24, 24)).astype(np.float32)
        b = rng.standard_normal((24, 24)).astype(np.float32)
        A, B = bm(a, mesh8), bm(b, mesh8)
        plan = compile_expr(A.expr().multiply(B.expr()), mesh8)
        a_leaf = plan.leaf_order[0]
        step = plan.bound_runner(rebind_uids=(a_leaf.uid,))
        cur = step(A.data)                    # A·B
        np.testing.assert_allclose(np.asarray(cur)[:24, :24], a @ b,
                                   rtol=1e-4, atol=1e-4)
        cur = step(cur)                       # (A·B)·B
        np.testing.assert_allclose(np.asarray(cur)[:24, :24], a @ b @ b,
                                   rtol=1e-4, atol=1e-3)
        # parity with the general run() path
        got = plan.run(bindings={a_leaf.uid: plan.run()}).to_numpy()
        np.testing.assert_allclose(np.asarray(cur)[:24, :24], got,
                                   rtol=1e-5, atol=1e-5)

    def test_no_rebind_closure(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        a = rng.standard_normal((16, 16)).astype(np.float32)
        A = bm(a, mesh8)
        plan = compile_expr(A.expr().multiply(A.expr().t()), mesh8)
        fixed = plan.bound_runner()
        np.testing.assert_allclose(np.asarray(fixed())[:16, :16], a @ a.T,
                                   rtol=1e-4, atol=1e-4)

    def test_unknown_uid_raises(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        A = bm(rng.standard_normal((8, 8)).astype(np.float32), mesh8)
        plan = compile_expr(A.expr().multiply(A.expr()), mesh8)
        with pytest.raises(KeyError):
            plan.bound_runner(rebind_uids=(999999,))

    def test_donate_chain(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        a = rng.standard_normal((16, 16)).astype(np.float32)
        b = rng.standard_normal((16, 16)).astype(np.float32)
        A, B = bm(a, mesh8), bm(b, mesh8)
        plan = compile_expr(A.expr().multiply(B.expr()), mesh8)
        leaf = plan.leaf_order[0]
        step = plan.bound_runner(rebind_uids=(leaf.uid,), donate=True)
        cur = step(A.data + 0)        # fresh buffer (A.data stays live)
        cur = step(cur)
        cur = step(cur)
        np.testing.assert_allclose(np.asarray(cur)[:16, :16], a @ b @ b @ b,
                                   rtol=1e-3, atol=1e-2)

    def test_wrong_arity_raises(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        a = rng.standard_normal((8, 8)).astype(np.float32)
        A, B = bm(a, mesh8), bm(a, mesh8)
        plan = compile_expr(A.expr().multiply(B.expr()), mesh8)
        step = plan.bound_runner(
            rebind_uids=tuple(l.uid for l in plan.leaf_order))
        with pytest.raises(ValueError, match="rebound"):
            step(A.data)


class TestSolveInverse:
    """inverse/solve nodes — the normal-equations building blocks."""

    def _spd(self, rng, n):
        m = rng.standard_normal((n, n)).astype(np.float32)
        return m @ m.T + n * np.eye(n, dtype=np.float32)

    def test_inverse_matches_numpy(self, mesh8, rng):
        a = self._spd(rng, 12)
        out = bm(a, mesh8).inverse().compute().to_numpy()
        np.testing.assert_allclose(out, np.linalg.inv(a), rtol=1e-3,
                                   atol=1e-4)

    def test_solve_matches_numpy(self, mesh8, rng):
        a = self._spd(rng, 12)
        b = rng.standard_normal((12, 5)).astype(np.float32)
        out = bm(a, mesh8).solve(bm(b, mesh8)).compute().to_numpy()
        np.testing.assert_allclose(out, np.linalg.solve(a, b), rtol=1e-3,
                                   atol=1e-4)

    def test_ragged_padding_not_singular(self, mesh8, rng):
        # 13x13 pads to a larger grid: the zero padding must be sliced
        # off before the LU factorisation or the system is singular
        a = self._spd(rng, 13)
        b = rng.standard_normal((13, 3)).astype(np.float32)
        out = bm(a, mesh8).solve(bm(b, mesh8)).compute().to_numpy()
        np.testing.assert_allclose(out, np.linalg.solve(a, b), rtol=1e-3,
                                   atol=1e-4)
        assert np.isfinite(out).all()

    def test_normal_equations_end_to_end(self, mesh8, rng):
        # the reference's flagship expression, straight from the DSL:
        # theta = (XᵀX)⁻¹ · (Xᵀy)
        x = rng.standard_normal((40, 6)).astype(np.float32)
        y = (x @ np.arange(1, 7, dtype=np.float32)[:, None]
             + 0.01 * rng.standard_normal((40, 1)).astype(np.float32))
        X, Y = bm(x, mesh8), bm(y, mesh8)
        theta = (X.t().matmul(X)).inverse().matmul(
            X.t().matmul(Y)).compute().to_numpy()
        oracle = np.linalg.solve(x.T @ x, x.T @ y)
        np.testing.assert_allclose(theta, oracle, rtol=1e-2, atol=1e-3)

    def test_shape_validation(self, mesh8, rng):
        import matrel_tpu.ir.expr as E
        A = bm(rng.standard_normal((4, 6)), mesh8)
        with pytest.raises(ValueError, match="square"):
            A.inverse()
        B = bm(rng.standard_normal((6, 6)), mesh8)
        with pytest.raises(ValueError, match="mismatch"):
            E.solve(B.expr(), bm(rng.standard_normal((4, 2)), mesh8).expr())


class TestLargeConstHoisting:
    """compile_expr hoists big sparse payloads into call-time args — an
    embedded multi-GB constant bloats the executable past the compile
    cache's entry limit and doubles its HBM (the 10M-edge COO plan
    measured ~GBs of one-hot tables)."""

    def test_sparse_payload_hoisted_and_correct(self, mesh8, rng):
        from matrel_tpu.core.sparse import BlockSparseMatrix
        from matrel_tpu.executor import compile_expr
        from matrel_tpu.config import MatrelConfig
        # tile stack > 1 MB: 64 tiles of 64x64 f32 = 1.05 MB
        n = 512
        a = np.zeros((n, n), np.float32)
        for bi in range(8):
            for bj in range(8):
                a[bi*64:(bi+1)*64, bj*64:(bj+1)*64] = \
                    rng.standard_normal((64, 64))
        d = rng.standard_normal((n, 16)).astype(np.float32)
        S = BlockSparseMatrix.from_numpy(a, block_size=64, mesh=mesh8)
        D = bm(d, mesh8)
        plan = compile_expr(S.multiply(D), mesh8, MatrelConfig())
        assert len(plan.extra_args) >= 1        # payload rides as an arg
        assert sum(c.nbytes for c in plan.extra_args) >= 1 << 20
        np.testing.assert_allclose(plan.run().to_numpy(), a @ d,
                                   rtol=1e-4, atol=1e-4)
        # repeated runs and the iteration path both append the extras
        np.testing.assert_allclose(plan.run().to_numpy(), a @ d,
                                   rtol=1e-4, atol=1e-4)
        out = np.asarray(plan.bound_runner()())
        np.testing.assert_allclose(out[:n, :16], a @ d, rtol=1e-4,
                                   atol=1e-4)
        # donation paths must append the extras too (C <- f(C) loops)
        D2 = bm(d, plan.mesh)
        leaf_uid = plan.leaf_order[0].uid
        out2 = plan.run(bindings={leaf_uid: D2}, donate=True).to_numpy()
        np.testing.assert_allclose(out2, a @ d, rtol=1e-4, atol=1e-4)
        run3 = plan.bound_runner(rebind_uids=(leaf_uid,), donate=True)
        out3 = np.asarray(run3(bm(d, plan.mesh).data))
        np.testing.assert_allclose(out3[:n, :16], a @ d, rtol=1e-4,
                                   atol=1e-4)

    def test_small_consts_stay_embedded(self, mesh8, rng):
        from matrel_tpu.executor import compile_expr
        from matrel_tpu.config import MatrelConfig
        A = bm(rng.standard_normal((16, 16)), mesh8)
        plan = compile_expr(A.expr().row_sum(), mesh8, MatrelConfig())
        assert plan.extra_args == []            # nothing above 1 MB

def test_cholesky_solve_option(mesh8, rng):
    m = rng.standard_normal((12, 12)).astype(np.float32)
    a = m @ m.T + 12 * np.eye(12, dtype=np.float32)
    b = rng.standard_normal((12, 5)).astype(np.float32)
    out = bm(a, mesh8).solve(bm(b, mesh8), assume="pos"
                             ).compute().to_numpy()
    np.testing.assert_allclose(out, np.linalg.solve(a, b), rtol=1e-3,
                               atol=1e-4)
    import matrel_tpu.ir.expr as E
    with pytest.raises(ValueError, match="assume"):
        E.solve(bm(a, mesh8).expr(), bm(b, mesh8).expr(),
                assume="banded")


def test_multiplan_hoists_and_appends_extras(mesh8, rng):
    # compile_exprs (multi-output) shares the hoisting path: sparse
    # payloads ride as args there too
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.executor import compile_exprs
    from matrel_tpu.config import MatrelConfig
    n = 1024
    a = np.zeros((n, n), np.float32)
    for bi in range(16):                 # 80 tiles of 64^2 f32 = 1.25 MB
        for bj in range(5):
            a[bi*64:(bi+1)*64, bj*64:(bj+1)*64] = \
            rng.standard_normal((64, 64))
    d = rng.standard_normal((n, 8)).astype(np.float32)
    S = BlockSparseMatrix.from_numpy(a, block_size=64, mesh=mesh8)
    D = bm(d, mesh8)
    e1 = S.multiply(D)
    e2 = e1.row_sum()
    plan = compile_exprs([e1, e2], mesh8, MatrelConfig())
    o1, o2 = plan.run()
    np.testing.assert_allclose(o1.to_numpy(), a @ d, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(o2.to_numpy(), (a @ d).sum(1, keepdims=True),
                               rtol=1e-4, atol=1e-4)
    if sum(c.nbytes for c in plan.extra_args) == 0:
        # tile stack below threshold would make this vacuous
        raise AssertionError("expected hoisted sparse payload")


def test_norms(mesh8, rng):
    a = rng.standard_normal((9, 13)).astype(np.float32)
    A = bm(a, mesh8)
    assert A.norm().compute().to_numpy()[0, 0] == pytest.approx(
        np.linalg.norm(a), rel=1e-4)
    assert A.norm("l1").compute().to_numpy()[0, 0] == pytest.approx(
        np.abs(a).sum(), rel=1e-4)
    assert A.norm("max").compute().to_numpy()[0, 0] == pytest.approx(
        np.abs(a).max(), rel=1e-4)
    with pytest.raises(ValueError, match="norm kind"):
        A.norm("spectral")
    # |a| via max(a, -a): tiny magnitudes must not underflow to 0
    tiny = bm(np.full((4, 4), -1e-30, np.float32), mesh8)
    assert tiny.norm("max").compute().to_numpy()[0, 0] == pytest.approx(
        1e-30, rel=1e-4)


class TestSymmetricGramLowering:
    """matmul(Aᵀ, A) / matmul(A, Aᵀ) under precision="high" lowers to
    the symmetric 2-pass bf16 split (round-3: 33% fewer MXU FLOPs at
    bf16x3-identical accuracy; docs/ROUND3.md)."""

    def _cfg(self):
        from matrel_tpu.config import MatrelConfig
        return MatrelConfig(matmul_precision="high")

    def test_ata_matches_oracle(self, mesh8, rng):
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.executor import execute
        a = rng.standard_normal((48, 24)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        out = execute(A.expr().t().multiply(A.expr()), mesh8,
                      self._cfg()).to_numpy()
        np.testing.assert_allclose(out, a.T @ a, rtol=2e-3, atol=2e-3)

    def test_aat_matches_oracle(self, mesh8, rng):
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.executor import execute
        a = rng.standard_normal((24, 48)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        out = execute(A.expr().multiply(A.expr().t()), mesh8,
                      self._cfg()).to_numpy()
        np.testing.assert_allclose(out, a @ a.T, rtol=2e-3, atol=2e-3)

    def test_two_bf16_passes_not_one_f32(self, mesh8, rng, monkeypatch):
        # spy: the gram path must call run_matmul TWICE with bf16
        # operands (hi·hi, hi·lo) instead of once with f32
        import jax.numpy as jnp
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.executor import execute
        from matrel_tpu.parallel import strategies
        calls = []
        real = strategies.run_matmul

        def spy(strategy, x, y, mesh, config=None, **kw):
            calls.append((x.dtype, y.dtype))
            return real(strategy, x, y, mesh, config, **kw)

        monkeypatch.setattr(strategies, "run_matmul", spy)
        a = rng.standard_normal((32, 16)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        execute(A.expr().t().multiply(A.expr()), mesh8, self._cfg())
        gram_calls = [c for c in calls if c == (jnp.bfloat16, jnp.bfloat16)]
        assert len(gram_calls) == 2, calls

    def test_highest_precision_keeps_generic_path(self, mesh8, rng):
        # default "highest" must NOT take the 2-pass split (it would
        # silently downgrade accuracy): result ≈ f32-exact
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.executor import execute
        a = rng.standard_normal((32, 16)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        out = execute(A.expr().t().multiply(A.expr()), mesh8,
                      MatrelConfig(matmul_precision="highest")).to_numpy()
        np.testing.assert_allclose(out, a.T @ a, rtol=1e-5, atol=1e-5)

    def test_distinct_matrices_not_treated_as_gram(self, mesh8, rng):
        # Bᵀ·A with B ≠ A must stay on the generic path and be correct
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.executor import execute
        a = rng.standard_normal((48, 24)).astype(np.float32)
        b = rng.standard_normal((48, 24)).astype(np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        B = BlockMatrix.from_numpy(b, mesh=mesh8)
        out = execute(B.expr().t().multiply(A.expr()), mesh8,
                      self._cfg()).to_numpy()
        np.testing.assert_allclose(out, b.T @ a, rtol=2e-3, atol=2e-3)


def test_rebound_leaf_with_different_layout_stays_correct(mesh8, rng):
    # round-5 net: a compiled plan is OPTIMIZED for the layouts its
    # leaves had at compile time; rebinding a matrix with a different
    # PartitionSpec may make the cached strategy suboptimal but must
    # never change the numbers (jit re-specializes on the new input
    # sharding; the strategy recipes are layout-correct for any input)
    from jax.sharding import PartitionSpec as P
    from matrel_tpu import executor
    from matrel_tpu.ir.expr import leaf, matmul
    a = rng.standard_normal((64, 32)).astype(np.float32)
    b = rng.standard_normal((32, 16)).astype(np.float32)
    a2 = rng.standard_normal((64, 32)).astype(np.float32)
    A_row = bm(a, mesh8, spec=P(("x", "y"), None))
    B = bm(b, mesh8)
    la = leaf(A_row)
    plan = executor.compile_expr(matmul(la, leaf(B)), mesh8)
    np.testing.assert_allclose(plan.run().to_numpy(), a @ b,
                               rtol=1e-4, atol=1e-4)
    # rebind with canonical-2D data of the same shape
    got = plan.run(bindings={la.uid: bm(a2, mesh8)}).to_numpy()
    np.testing.assert_allclose(got, a2 @ b, rtol=1e-4, atol=1e-4)
    # and with a replicated rebind
    A3_rep = bm(a2, mesh8, spec=P(None, None))
    got3 = plan.run(bindings={la.uid: A3_rep}).to_numpy()
    np.testing.assert_allclose(got3, a2 @ b, rtol=1e-4, atol=1e-4)
