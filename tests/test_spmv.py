"""ops/spmv.py — blocked one-hot SpMV and the width-row gather.

Oracle: scipy-style COO accumulation in numpy f64. The plan layouts are
data-dependent (per-graph static shapes), so the suite sweeps shapes,
duplicates, weights, skew (overflow path) and the refusal fallback.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from matrel_tpu.ops import spmv as spmv_lib


def coo_oracle(rows, cols, vals, x, n_rows):
    y = np.zeros((n_rows,), np.float64)
    np.add.at(y, rows, vals * x[cols])
    return y


class TestGather1D:
    def test_matches_plain_indexing(self):
        rng = np.random.default_rng(0)
        for n, m in [(17, 5), (1000, 2048), (8, 8), (4096, 100_000)]:
            table = rng.standard_normal(n).astype(np.float32)
            idx = rng.integers(0, n, m).astype(np.int32)
            got = np.asarray(spmv_lib.gather_1d(jnp.asarray(table),
                                                jnp.asarray(idx)))
            np.testing.assert_array_equal(got, table[idx])

    def test_sentinel_reads_zero(self):
        table = jnp.arange(1, 11, dtype=jnp.float32)
        idx = jnp.asarray([0, 10, 5], jnp.int32)   # 10 == len(table)
        got = np.asarray(spmv_lib.gather_1d(table, idx))
        np.testing.assert_array_equal(got, [1.0, 0.0, 6.0])

    def test_2d_index_shape(self):
        rng = np.random.default_rng(1)
        table = rng.standard_normal(97).astype(np.float32)
        idx = rng.integers(0, 97, (13, 29)).astype(np.int32)
        got = np.asarray(spmv_lib.gather_1d(jnp.asarray(table),
                                            jnp.asarray(idx)))
        np.testing.assert_array_equal(got, table[idx])


def random_coo(rng, n_rows, n_cols, m, weighted=True):
    rows = rng.integers(0, n_rows, m).astype(np.int64)
    cols = rng.integers(0, n_cols, m).astype(np.int64)
    vals = (rng.standard_normal(m).astype(np.float32) if weighted
            else np.ones(m, np.float32))
    return rows, cols, vals


class TestSpMVPlan:
    @pytest.mark.parametrize("n_rows,n_cols,m", [
        (1000, 1000, 10_000),     # square, multi-block
        (300, 700, 5_000),        # rectangular, n_rows not /512
        (512, 512, 512),          # exactly one block
        (100, 100, 3),            # nearly empty
        (2000, 50, 20_000),       # many duplicate cols
    ])
    def test_matches_oracle(self, n_rows, n_cols, m):
        rng = np.random.default_rng(n_rows + m)
        rows, cols, vals = random_coo(rng, n_rows, n_cols, m)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_rows, n_cols=n_cols)
        assert plan is not None
        x = rng.standard_normal(n_cols).astype(np.float32)
        got = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))
        want = coo_oracle(rows, cols, vals, x, n_rows)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)

    def test_unsorted_input_and_duplicate_edges(self):
        rng = np.random.default_rng(7)
        rows = np.array([5, 5, 5, 0, 999, 0, 5], np.int64)
        cols = np.array([1, 1, 2, 3, 4, 3, 1], np.int64)
        vals = rng.standard_normal(7).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=1000, n_cols=10)
        x = rng.standard_normal(10).astype(np.float32)
        got = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))
        want = coo_oracle(rows, cols, vals, x, 1000)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_empty_edge_list(self):
        plan = spmv_lib.build_spmv_plan(np.zeros(0), np.zeros(0),
                                        n_rows=100, n_cols=100)
        got = np.asarray(spmv_lib.spmv(plan, jnp.ones((100,), jnp.float32)))
        np.testing.assert_array_equal(got, np.zeros(100))

    def test_skewed_degrees_use_overflow(self):
        # one hub row receives most edges -> quantile capacity forces an
        # overflow COO; numerics must still match
        rng = np.random.default_rng(3)
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        assert plan is not None and plan.ov_rows is not None
        x = rng.standard_normal(512).astype(np.float32)
        got = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))
        want = coo_oracle(rows, cols, vals, x, 4096)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_refuses_pathological_padding(self):
        # edges spread one-per-block over a huge row space: capacity 128
        # per block pads >4x the edge count (and >1M slots absolute)
        # -> build returns None
        n_rows = 512 * 20_000
        rows = (np.arange(20_000, dtype=np.int64) * 512)
        cols = np.zeros(20_000, np.int64)
        plan = spmv_lib.build_spmv_plan(rows, cols, n_rows=n_rows,
                                        n_cols=1, max_padding=4.0)
        assert plan is None

    def test_out_of_bounds_indices_raise(self):
        # both fill paths must fail loudly — a C++ truncating-division
        # guard once let rows in (-block, 0) through silently (regression)
        with pytest.raises(ValueError, match="out of bounds"):
            spmv_lib.build_spmv_plan(np.array([-1, 3]), np.array([0, 1]),
                                     n_rows=16, n_cols=4)
        with pytest.raises(ValueError, match="out of bounds"):
            spmv_lib.build_spmv_plan(np.array([1, 3]), np.array([0, -2]),
                                     n_rows=16, n_cols=4)
        with pytest.raises(ValueError, match="out of bounds"):
            spmv_lib.build_spmv_plan(np.array([16]), np.array([0]),
                                     n_rows=16, n_cols=4)

    def test_padding_ratio_reported(self):
        rng = np.random.default_rng(11)
        rows, cols, vals = random_coo(rng, 1024, 1024, 50_000)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=1024, n_cols=1024)
        assert 1.0 <= plan.padding_ratio < 2.0


class TestShardedSpMV:
    def test_spmv_sharded_matches_single(self, mesh8):
        import jax.numpy as jnp
        rng = np.random.default_rng(8)
        n_r, n_c, m = 8192, 4000, 60_000
        rows = rng.integers(0, n_r, m)
        cols = rng.integers(0, n_c, m)
        vals = rng.standard_normal(m).astype(np.float32)
        x = rng.standard_normal(n_c).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_r, n_cols=n_c)
        want = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))
        plan_s = spmv_lib.shard_plan(
            spmv_lib.build_spmv_plan(rows, cols, vals,
                                     n_rows=n_r, n_cols=n_c), mesh8)
        got = np.asarray(spmv_lib.spmv_sharded(plan_s, x, mesh8))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)

    def test_shard_plan_shards_block_axis(self, mesh8):
        rng = np.random.default_rng(9)
        rows = rng.integers(0, 8192, 10_000)
        cols = rng.integers(0, 512, 10_000)
        plan = spmv_lib.shard_plan(
            spmv_lib.build_spmv_plan(rows, cols, n_rows=8192, n_cols=512),
            mesh8)
        assert plan.src8.shape[0] % 8 == 0
        assert len(plan.src8.sharding.device_set) == 8

    def test_shard_plan_rejects_expanded(self, mesh8):
        rng = np.random.default_rng(10)
        plan = spmv_lib.build_spmv_plan(rng.integers(0, 1024, 1000),
                                        rng.integers(0, 64, 1000),
                                        n_rows=1024, n_cols=64)
        plan.arrays()   # expand
        with pytest.raises(ValueError, match="before table expansion"):
            spmv_lib.shard_plan(plan, mesh8)

    def test_sharded_with_overflow(self, mesh8):
        import jax.numpy as jnp
        rng = np.random.default_rng(11)
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        x = rng.standard_normal(512).astype(np.float32)
        plan = spmv_lib.shard_plan(
            spmv_lib.build_spmv_plan(rows, cols, vals,
                                     n_rows=4096, n_cols=512), mesh8)
        assert plan.ov_rows is not None
        got = np.asarray(spmv_lib.spmv_sharded(plan, x, mesh8))
        want = coo_oracle(rows, cols, vals, x, 4096)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_sharded_hlo_contains_all_gather(self, mesh8):
        # plan-shape assertion (the Catalyst comparePlans analogue): the
        # sharded matvec's only collective is one tiled all-gather
        import jax
        rng = np.random.default_rng(14)
        rows = rng.integers(0, 8192, 20_000)
        cols = rng.integers(0, 1024, 20_000)
        plan = spmv_lib.shard_plan(
            spmv_lib.build_spmv_plan(rows, cols, n_rows=8192,
                                     n_cols=1024), mesh8)
        arrays = plan.arrays()
        run = spmv_lib._sharded_spmv_runner(
            (plan.n_rows, plan.n_cols, plan.block), mesh8,
            len(arrays) > 4)
        x = np.zeros(1024, np.float32)
        hlo = run.lower(*arrays[:4], x, *arrays[4:]).compile().as_text()
        assert "all-gather" in hlo
        assert "reduce-scatter" not in hlo and "all-to-all" not in hlo

    @pytest.mark.parametrize("k", [1, 3, 70])
    def test_spmm_sharded_matches_single(self, mesh8, k):
        import jax.numpy as jnp
        rng = np.random.default_rng(15 + k)
        n_r, n_c, m = 6000, 3000, 40_000
        rows = rng.integers(0, n_r, m)
        cols = rng.integers(0, n_c, m)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_r, n_cols=n_c)
        X = rng.standard_normal((n_c, k)).astype(np.float32)
        want = np.asarray(spmv_lib.spmm(plan, jnp.asarray(X)))
        plan_s = spmv_lib.shard_plan(
            spmv_lib.build_spmv_plan(rows, cols, vals,
                                     n_rows=n_r, n_cols=n_c), mesh8)
        got = np.asarray(spmv_lib.spmm_sharded(plan_s, X, mesh8))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)

    def test_pagerank_sharded_matches_single(self, mesh8):
        from matrel_tpu.workloads import pagerank as pr
        rng = np.random.default_rng(12)
        n, m = 4000, 30_000
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        # impl='onehot' + mesh = the sharded variant, on any backend
        got = np.asarray(pr.pagerank_edges(src, dst, n, rounds=10,
                                           mesh=mesh8, impl="onehot"))
        want = np.asarray(pr.pagerank_edges(src, dst, n, rounds=10,
                                            impl="onehot"))
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-10)


class TestSpMM:
    @pytest.mark.parametrize("k", [1, 2, 7, 64, 100])
    def test_matches_scipy(self, k):
        import scipy.sparse as sp
        import jax.numpy as jnp
        rng = np.random.default_rng(k)
        n_r, n_c, m = 2500, 1800, 30_000
        rows = rng.integers(0, n_r, m)
        cols = rng.integers(0, n_c, m)
        vals = rng.standard_normal(m).astype(np.float32)
        S = sp.coo_matrix((vals, (rows, cols)), shape=(n_r, n_c)).tocsr()
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_r, n_cols=n_c)
        X = rng.standard_normal((n_c, k)).astype(np.float32)
        got = np.asarray(spmv_lib.spmm(plan, jnp.asarray(X)))
        np.testing.assert_allclose(got, S @ X, rtol=3e-4, atol=3e-4)

    def test_overflow_and_column_chunking(self):
        import scipy.sparse as sp
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        S = sp.coo_matrix((vals, (rows, cols)), shape=(4096, 512)).tocsr()
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        assert plan.ov_rows is not None
        X = rng.standard_normal((512, 9)).astype(np.float32)
        got = np.asarray(spmv_lib.spmm(plan, jnp.asarray(X), col_chunk=4))
        np.testing.assert_allclose(got, S @ X, rtol=3e-4, atol=3e-4)

    def test_consistent_with_spmv_columns(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(5)
        rows = rng.integers(0, 1500, 10_000)
        cols = rng.integers(0, 1000, 10_000)
        vals = rng.standard_normal(10_000).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=1500, n_cols=1000)
        X = rng.standard_normal((1000, 3)).astype(np.float32)
        via_spmm = np.asarray(spmv_lib.spmm(plan, jnp.asarray(X)))
        via_spmv = np.stack(
            [np.asarray(spmv_lib.spmv(plan, jnp.asarray(X[:, j])))
             for j in range(3)], axis=1)
        np.testing.assert_allclose(via_spmm, via_spmv, rtol=2e-5,
                                   atol=1e-5)


class TestPlanPersistence:
    def test_save_load_roundtrip(self, tmp_path):
        import jax.numpy as jnp
        rng = np.random.default_rng(13)
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        p = str(tmp_path / "plan.npz")
        spmv_lib.save_plan(p, plan)
        loaded = spmv_lib.load_plan(p)
        assert loaded.capacity == plan.capacity
        assert loaded.padding_ratio == plan.padding_ratio
        x = rng.standard_normal(512).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(spmv_lib.spmv(loaded, jnp.asarray(x))),
            np.asarray(spmv_lib.spmv(plan, jnp.asarray(x))))

    def test_save_after_expansion_works(self, tmp_path):
        # behavior change (2026-07-30): compact tables are kept for the
        # plan's life, so saving after expanded-path use round-trips
        import jax.numpy as jnp
        plan = spmv_lib.build_spmv_plan(np.array([1, 2]), np.array([0, 1]),
                                        n_rows=8, n_cols=4)
        x = jnp.ones(4, jnp.float32)
        y1 = np.asarray(spmv_lib.spmv(plan, x))   # expands
        spmv_lib.save_plan(str(tmp_path / "x.npz"), plan)
        plan2 = spmv_lib.load_plan(str(tmp_path / "x.npz"))
        np.testing.assert_allclose(np.asarray(spmv_lib.spmv(plan2, x)),
                                   y1, rtol=1e-6)


class TestPageRankOneHot:
    def test_matches_segment_impl_and_oracle(self):
        from matrel_tpu.workloads.pagerank import (
            pagerank_edges, pagerank_numpy_oracle)
        rng = np.random.default_rng(5)
        n, m = 2000, 16_000
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        r_hot = np.asarray(pagerank_edges(src, dst, n, rounds=15,
                                          impl="onehot"))
        r_seg = np.asarray(pagerank_edges(src, dst, n, rounds=15,
                                          impl="segment"))
        np.testing.assert_allclose(r_hot, r_seg, rtol=5e-4, atol=1e-9)
        a = np.zeros((n, n), np.float32)
        a[src, dst] = 1.0   # duplicates collapse; rebuild edges to match
        s2, d2 = np.nonzero(a)
        r_hot2 = np.asarray(pagerank_edges(s2, d2, n, rounds=15,
                                           impl="onehot"))
        want = pagerank_numpy_oracle(a, rounds=15).ravel()
        np.testing.assert_allclose(r_hot2, want, rtol=1e-3, atol=1e-10)

    def test_explicit_onehot_raises_on_refused_graph(self):
        # same pathological spread as the plan-refusal test: explicit
        # impl='onehot' must raise, not silently run the segment path
        from matrel_tpu.workloads.pagerank import pagerank_edges
        n = 512 * 20_000
        src = np.zeros(20_000, np.int64)
        dst = np.arange(20_000, dtype=np.int64) * 512
        with pytest.raises(ValueError, match="heavy-tailed"):
            pagerank_edges(src, dst, n, rounds=2, impl="onehot")

    def test_weighted_edges_match_oracle(self):
        from matrel_tpu.workloads.pagerank import (
            pagerank_edges, pagerank_numpy_oracle)
        rng = np.random.default_rng(21)
        n, m = 800, 6000
        a = np.zeros((n, n), np.float32)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        w = rng.random(m).astype(np.float32) + 0.1
        np.add.at(a, (src, dst), w)
        s2, d2 = np.nonzero(a)
        w2 = a[s2, d2]
        want = pagerank_numpy_oracle(a, rounds=15).ravel()
        for impl in ("onehot", "segment"):
            got = np.asarray(pagerank_edges(s2, d2, n, rounds=15,
                                            impl=impl, weights=w2))
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-9,
                                       err_msg=impl)

    def test_dangling_nodes(self):
        # node 3 has no out-edges; its mass must redistribute
        src = np.array([0, 1, 2, 0])
        dst = np.array([1, 2, 3, 3])
        n = 4
        from matrel_tpu.workloads.pagerank import (
            pagerank_edges, pagerank_numpy_oracle)
        a = np.zeros((n, n), np.float32)
        a[src, dst] = 1.0
        got = np.asarray(pagerank_edges(src, dst, n, rounds=25,
                                        impl="onehot"))
        want = pagerank_numpy_oracle(a, rounds=25).ravel()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-8)
        assert abs(got.sum() - 1.0) < 1e-3


@pytest.mark.parametrize("passes,tol", [(1, 2.0 ** -7), (2, 1e-4),
                                        (3, 1e-6)])
def test_bf16_split_reconstructs(passes, tol):
    from matrel_tpu.ops.pallas_spmv import _bf16_split
    v = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(4096).astype(np.float32))
    # truncation-based split: one-sided error, bound ~2^(-7·passes)
    parts = _bf16_split(v, passes)
    assert len(parts) == passes
    # parts sit exactly on the bf16 grid (lossless astype)
    for p in parts:
        assert np.array_equal(
            np.asarray(p),
            np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32)))
    back = np.sum([np.asarray(p, np.float64) for p in parts], axis=0)
    rel = np.abs(back - np.asarray(v, np.float64))
    rel = rel / np.maximum(np.abs(np.asarray(v)), 1e-30)
    assert rel.max() < tol
    # truncation toward zero: no part overshoots what is left
    assert np.all(np.abs(back) <= np.abs(np.asarray(v, np.float64)))


class TestCompactSpMV:
    """ops/pallas_spmv.py — the compact-table Pallas scatter (interpret
    mode on CPU; on-chip numbers in BASELINE.md row 5)."""

    def test_matches_oracle(self, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        n_r, n_c, m = 3000, 2500, 30_000
        rows, cols, vals = random_coo(rng, n_r, n_c, m)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_r, n_cols=n_c)
        x = rng.standard_normal(n_c).astype(np.float32)
        y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x),
                                       interpret=True))
        want = coo_oracle(rows, cols, vals, x, n_r)
        scale = np.abs(want).max()
        assert np.abs(y - want).max() / scale < 1e-6   # passes=3

    def test_two_pass_split(self, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        n, m = 2000, 20_000
        rows, cols, vals = random_coo(rng, n, n, m)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows=n,
                                        n_cols=n)
        x = rng.standard_normal(n).astype(np.float32)
        y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x), passes=2,
                                       interpret=True))
        want = coo_oracle(rows, cols, vals, x, n)
        assert np.abs(y - want).max() / np.abs(want).max() < 1e-4

    def test_overflow_coo_included(self, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        # hub row forces quantile-capacity overflow
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        assert plan.ov_rows is not None
        x = rng.standard_normal(512).astype(np.float32)
        y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x),
                                       interpret=True))
        want = coo_oracle(rows, cols, vals, x, 4096)
        scale = np.abs(want).max()
        assert np.abs(y - want).max() / scale < 1e-5

    def test_works_after_expanded_path(self, rng):
        # compact hosts are kept past expansion, so the two executors
        # can be mixed on one plan in any order
        from matrel_tpu.ops import pallas_spmv as pc
        rows, cols, vals = random_coo(rng, 1000, 1000, 5_000)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=1000, n_cols=1000)
        x = rng.standard_normal(1000).astype(np.float32)
        y1 = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))  # expands
        y2 = np.asarray(pc.spmv_compact(plan, jnp.asarray(x),
                                        interpret=True))
        np.testing.assert_allclose(y2, y1, rtol=1e-5, atol=1e-6)

    def test_pagerank_compact_matches_onehot(self, rng):
        from matrel_tpu.workloads import pagerank as pr
        n, m = 3000, 30_000
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        r1 = np.asarray(pr.run_pagerank_compact(
            pr.prepare_pagerank_onehot(src, dst, n), rounds=10,
            interpret=True))
        r2 = np.asarray(pr.run_pagerank_onehot(
            pr.prepare_pagerank_onehot(src, dst, n), rounds=10))
        assert np.abs(r1 - r2).max() / np.abs(r2).max() < 5e-4
        assert abs(r1.sum() - 1.0) < 1e-3

    def test_spmm_compact_matches_oracle(self, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        for n_r, n_c, m, k in [(3000, 2500, 25_000, 16),
                               (1000, 1500, 8_000, 5)]:
            rows, cols, vals = random_coo(rng, n_r, n_c, m)
            plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                            n_rows=n_r, n_cols=n_c)
            X = rng.standard_normal((n_c, k)).astype(np.float32)
            Y = np.asarray(pc.spmm_compact(plan, jnp.asarray(X),
                                           interpret=True))
            want = np.zeros((n_r, k))
            np.add.at(want, rows, vals[:, None] * X[cols])
            scale = np.abs(want).max()
            assert np.abs(Y - want).max() / scale < 1e-4

    def test_spmm_compact_overflow_and_single_col(self, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        assert plan.ov_rows is not None
        X = rng.standard_normal((512, 3)).astype(np.float32)
        Y = np.asarray(pc.spmm_compact(plan, jnp.asarray(X),
                                       interpret=True))
        want = np.zeros((4096, 3))
        np.add.at(want, rows, vals[:, None] * X[cols])
        scale = np.abs(want).max()
        assert np.abs(Y - want).max() / scale < 1e-4
        # k == 1 takes the matvec kernel
        y1 = np.asarray(pc.spmm_compact(plan, jnp.asarray(X[:, :1]),
                                        interpret=True))
        assert np.abs(y1[:, 0] - want[:, 0]).max() / scale < 1e-5

    def test_sharded_compact_matches_oracle(self, mesh8, rng):
        # compact tables row-decomposed over the 8-device mesh; pallas
        # runs per device inside shard_map (interpret on CPU)
        from matrel_tpu.ops import pallas_spmv as pc
        n_r, n_c, m = 8192, 4000, 60_000
        rows, cols, vals = random_coo(rng, n_r, n_c, m)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=n_r, n_cols=n_c)
        x = rng.standard_normal(n_c).astype(np.float32)
        y = np.asarray(pc.spmv_compact_sharded(plan, x, mesh8,
                                               interpret=True))
        want = coo_oracle(rows, cols, vals, x, n_r)
        scale = np.abs(want).max()
        assert np.abs(y - want).max() / scale < 1e-5
        # tables are actually sharded: block axis spread over 8 devices
        tabs = plan._compact_sharded[mesh8]
        assert len(tabs[0].sharding.device_set) == 8

    def test_sharded_compact_with_overflow(self, mesh8, rng):
        from matrel_tpu.ops import pallas_spmv as pc
        m = 20_000
        rows = np.where(rng.random(m) < 0.3, 7,
                        rng.integers(0, 4096, m)).astype(np.int64)
        cols = rng.integers(0, 512, m).astype(np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=4096, n_cols=512)
        assert plan.ov_rows is not None
        x = rng.standard_normal(512).astype(np.float32)
        y = np.asarray(pc.spmv_compact_sharded(plan, x, mesh8,
                                               interpret=True))
        want = coo_oracle(rows, cols, vals, x, 4096)
        assert np.abs(y - want).max() / np.abs(want).max() < 1e-5

    def test_pagerank_compact_sharded_matches_segment(self, mesh8, rng):
        from matrel_tpu.workloads import pagerank as pr
        n, m = 3000, 30_000
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        r1 = np.asarray(pr._pagerank_compact_sharded(
            src, dst, n, 8, 0.85, mesh8, interpret=True))
        r2 = np.asarray(pr.pagerank_edges(src, dst, n, rounds=8,
                                          impl="segment"))
        assert np.abs(r1 - r2).max() / np.abs(r2).max() < 5e-4
        assert abs(r1.sum() - 1.0) < 1e-3

    def test_compact_edge_cases(self, mesh8, rng):
        # empty plans, single partial block, fewer blocks than devices,
        # zero-column X — none may crash or densify
        from matrel_tpu.ops import pallas_spmv as pc
        empty = spmv_lib.build_spmv_plan(np.zeros(0), np.zeros(0),
                                         n_rows=100, n_cols=100)
        y = np.asarray(pc.spmv_compact(empty, jnp.ones(100, jnp.float32),
                                       interpret=True))
        assert (y == 0).all()
        rows, cols, vals = random_coo(rng, 100, 80, 500)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=100, n_cols=80)
        x = rng.standard_normal(80).astype(np.float32)
        want = coo_oracle(rows, cols, vals, x, 100)
        y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x),
                                       interpret=True))
        assert np.abs(y - want).max() / np.abs(want).max() < 1e-6
        # one block over eight devices: sentinel-padded to the mesh
        y = np.asarray(pc.spmv_compact_sharded(plan, x, mesh8,
                                               interpret=True))
        assert np.abs(y - want).max() / np.abs(want).max() < 1e-6
        assert pc.spmm_compact(plan, jnp.zeros((80, 0), jnp.float32),
                               interpret=True).shape == (100, 0)

    def test_save_after_use_roundtrip(self, tmp_path, rng):
        # compact tables survive expanded-path use, so persistence works
        # at any point in a plan's life
        rows, cols, vals = random_coo(rng, 2000, 1500, 20_000)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                        n_rows=2000, n_cols=1500)
        x = rng.standard_normal(1500).astype(np.float32)
        y1 = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))  # expands
        path = str(tmp_path / "plan.npz")
        spmv_lib.save_plan(path, plan)                        # after use
        plan2 = spmv_lib.load_plan(path)
        y2 = np.asarray(spmv_lib.spmv(plan2, jnp.asarray(x)))
        np.testing.assert_allclose(y2, y1, rtol=1e-6, atol=1e-7)
        from matrel_tpu.ops import pallas_spmv as pc
        y3 = np.asarray(pc.spmv_compact(plan2, jnp.asarray(x),
                                        interpret=True))
        np.testing.assert_allclose(y3, y1, rtol=1e-5, atol=1e-6)


class TestSpmvChoiceIdentity:
    """VERDICT r4 "what's weak" #3: the forced-variant mapping is
    validated by plan identity, so a recycled id can never misroute a
    different plan onto a measured choice."""

    def test_identity_checked(self, mesh8):
        from matrel_tpu import executor as ex
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.coo import COOMatrix
        import numpy as np
        rng = np.random.default_rng(0)
        A = COOMatrix.from_edges(rng.integers(0, 64, 200),
                                 rng.integers(0, 64, 200),
                                 shape=(64, 64))
        B = COOMatrix.from_edges(rng.integers(0, 64, 200),
                                 rng.integers(0, 64, 200),
                                 shape=(64, 64))
        pa, pb = A._get_plan(), B._get_plan()
        low = ex.Lowerer(mesh8, MatrelConfig())
        low.spmv_choice = {id(pa): (pa, "expanded"),
                           # forged stale entry: pb's id mapped to pa
                           id(pb): (pa, "expanded")}
        assert low._spmv_forced(pa) == "expanded"
        assert low._spmv_forced(pb) is None     # identity mismatch


# -- x[idx], bit for bit (PR 28) ---------------------------------------------
# The gathered row travels as bytes and is reassembled with integer
# operations, so what comes back is x[idx] to the last bit, specials
# included. The form before PR 28 selected the lane by a 0/1 product
# (``sum(g * sel)``), which turns a neighbouring lane's inf into NaN
# and -0.0 into +0.0: the specials are held to x[idx], not to that.

_F32_MAX = np.finfo(np.float32).max
_NAN_PAYLOAD = np.array([0x7FC12345, 0xFFC00001], np.uint32).view(np.float32)

# name -> (table, what the compact matvec may reference). Every entry is
# read by gather_1d; the scatter kernel splits a product into bfloat16
# parts, which only a finite non-zero normal value survives, so the
# matvec references those and keeps the specials as their lane neighbours.
_BIT_CASES = {
    "negatives": np.array([-1.5, 2.25, -3e-7, 7.0, -1e30, 0.3, -0.7, 9.0,
                           -5.5, 6.5], np.float32),
    "negative_zero": np.array([1.0, -0.0, 2.0, -0.0, 3.0, 0.0, 4.0, -0.0,
                               5.0], np.float32),
    "subnormals": np.array([1.0, 1e-45, -3e-39, 2.0, 1.1754942e-38, 3.0,
                            -1e-45, 4.0, 5.0], np.float32),
    "infinities": np.array([1.0, np.inf, 2.0, -np.inf, 3.0, np.inf, 4.0,
                            5.0, -np.inf, 6.0], np.float32),
    "largest_finite": np.array([_F32_MAX, -_F32_MAX, 1.0, _F32_MAX, 2.0,
                                -_F32_MAX, 3.0, 4.0, 5.0], np.float32),
    "nan_payloads": np.concatenate([np.array([1.0, 2.0], np.float32),
                                    _NAN_PAYLOAD,
                                    np.array([3.0, 4.0, 5.0, 6.0, 7.0],
                                             np.float32)]),
    "length_not_multiple_of_8": np.arange(1, 14, dtype=np.float32) / 7,
    "length_multiple_of_8": -np.arange(1, 17, dtype=np.float32) / 3,
}


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _survives_split(v):
    """Finite, non-zero, normal: what the bfloat16 split carries exactly."""
    with np.errstate(invalid="ignore"):
        return np.isfinite(v) & (np.abs(v) >= np.finfo(np.float32).tiny)


@pytest.mark.parametrize("case", sorted(_BIT_CASES))
def test_gather_is_x_idx_bit_for_bit(case):
    from matrel_tpu.ops import pallas_spmv as pc
    table = _BIT_CASES[case]
    n = table.shape[0]
    ext = np.concatenate([table, np.zeros(1, np.float32)])
    # every entry twice, out of order, and the sentinel slot n (reads +0.0)
    idx = np.concatenate([np.arange(n), [n], np.arange(n)[::-1], [n]]
                         ).astype(np.int32)
    # the default row (2 values at this length) and the wider ones that
    # longer tables take
    for width in (None, 4, 8, 32):
        got = spmv_lib.gather_1d(jnp.asarray(table), jnp.asarray(idx),
                                 width=width)
        np.testing.assert_array_equal(_bits(got), _bits(ext[idx]))
    got2d = spmv_lib.gather_1d(jnp.asarray(table),
                               jnp.asarray(idx.reshape(2, -1)))
    np.testing.assert_array_equal(_bits(got2d),
                                  _bits(ext[idx.reshape(2, -1)]))

    # the compact matvec of a selection matrix: row i reads x[cols[i]]
    # with weight 1, so y is x[cols] once the split parts are summed;
    # empty rows and the plan's padded slots (sentinel) read +0.0
    cols = np.flatnonzero(_survives_split(table))[::-1]
    rows = np.arange(cols.size) * 2          # odd rows stay empty
    plan = spmv_lib.build_spmv_plan(rows, cols, np.ones(cols.size, np.float32),
                                    n_rows=2 * cols.size, n_cols=n)
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    y = pc.compact_apply(static, pc.compact_tables(plan), plan.overflow,
                         jnp.asarray(table), interpret=True)
    want = np.zeros(plan.n_rows, np.float32)
    want[rows] = table[cols]
    np.testing.assert_array_equal(_bits(y), _bits(want))


def _compact_apply_before_pr28(plan_static, tables, ov, x, passes, interpret):
    """``compact_apply`` as it stood before PR 28 (8 float32 a gathered
    row, the lane selected by a 0/1 product): the reference that
    positive finite ranks must still match bit for bit."""
    from matrel_tpu.ops import pallas_spmv as pc
    n_rows, n_cols, block, lo = plan_static
    src8, lane, off, val = tables
    nb, cr, _ = src8.shape
    x_ext = spmv_lib._ext_table(x.astype(jnp.float32))
    g = jnp.take(x_ext, src8, axis=0)
    sel = lane[..., None] == jnp.arange(spmv_lib.WIDTH, dtype=lane.dtype)
    w = jnp.sum(g * sel, axis=-1) * val
    y = pc._compact_runner(nb, cr * pc.LANE, block, lo, passes,
                           interpret)(off, w).reshape(-1)[:n_rows]
    if ov:
        ov_c, ov_r, ov_v = ov
        hi, lo_ = ov_c // spmv_lib.WIDTH, ov_c % spmv_lib.WIDTH
        gs = jnp.take(x_ext, hi, axis=0)
        s = (lo_[..., None] == jnp.arange(spmv_lib.WIDTH, dtype=lo_.dtype)
             ).astype(jnp.float32)
        y = y + jax.ops.segment_sum(jnp.sum(gs * s, axis=-1) * ov_v, ov_r,
                                    num_segments=n_rows,
                                    indices_are_sorted=True)
    return y


@pytest.mark.parametrize("hub", [False, True], ids=["uniform", "hub_overflow"])
def test_pagerank_auto_ranks_bit_identical_to_previous_form(monkeypatch, hub):
    """pagerank_edges(impl="auto") where the compact executor answers
    (the TPU's choice, pinned here with Pallas in interpret mode)."""
    from matrel_tpu import config as config_lib
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.workloads import pagerank as pr
    rng = np.random.default_rng(28)
    n, m, rounds = 3000, 30_000, 8
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if hub:     # one node drawing 30% of the edges: an overflow tail
        dst = np.where(rng.random(m) < 0.3, 11, dst)
    monkeypatch.setattr(config_lib, "_default_config",
                        config_lib.MatrelConfig(pallas_interpret=True))
    monkeypatch.setattr(pr, "on_tpu", lambda: True)
    before = pr.path_counts()["compact"]
    got = pr.pagerank_edges(src, dst, n, rounds=rounds, impl="auto")
    assert pr.path_counts()["compact"] == before + 1

    plan, dangling = pr.prepare_pagerank_onehot(src, dst, n)
    assert bool(plan.overflow) == hub
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    tables = pc.compact_tables(plan)
    body = pr._power_body(
        lambda r: _compact_apply_before_pr28(static, tables, plan.overflow,
                                             r, 3, True),
        n, 0.85, dangling)
    want = jax.jit(lambda: jax.lax.fori_loop(0, rounds, body, pr._r0(n)))()
    assert np.all(np.asarray(want) > 0) and np.all(np.isfinite(want))
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n,want", [
    (7, 2), (1_000_000, 2), (1_048_574, 2), (1_048_576, 4),
    (4_000_000, 8), (16_000_000, 32), (100_000_000, 32)])
def test_row_values_keep_the_byte_table_in_fast_memory(n, want):
    """128 B a padded row: the fewest values a row whose table stays
    within 64 MB, the size measured fast (PERF.md section 6, PR 28)."""
    w = spmv_lib._row_values(n)
    assert w == want
    if want < 32:
        assert (n // w + 1) * 128 <= 64 << 20



def test_gather_1d_is_its_two_halves(rng):
    """PR 33: a panelled matvec builds the byte table once and gathers
    from it a panel at a time; together the halves are gather_1d."""
    table = rng.standard_normal(1000).astype(np.float32)
    idx = rng.integers(0, 1001, (3, 256)).astype(np.int32)
    for width in (None, 2, 8):
        rows = spmv_lib.byte_table(jnp.asarray(table), width)
        w = width or spmv_lib._row_values(1000)
        assert rows.dtype == jnp.uint8 and rows.shape == (1000 // w + 1,
                                                          4 * w)
        got = spmv_lib.gather_rows(rows, jnp.asarray(idx), jnp.float32)
        want = spmv_lib.gather_1d(jnp.asarray(table), jnp.asarray(idx),
                                  width=width)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        # a panel of the index array gathers the panel of the answer
        np.testing.assert_array_equal(
            _bits(spmv_lib.gather_rows(rows, jnp.asarray(idx[1:2]),
                                       jnp.float32)), _bits(want[1:2]))


@pytest.mark.parametrize("layout", ["blocks", "auto"])
def test_default_layout_keeps_the_overflow_contract(layout):
    """A hub below the small-plan threshold: blocks and an overflow COO,
    whichever of the two layouts the caller allows; only "chunks" (or a
    large plan under "auto") lays it out without one."""
    rng = np.random.default_rng(3)
    m = 20_000
    rows = np.where(rng.random(m) < 0.3, 7, rng.integers(0, 4096, m))
    cols = rng.integers(0, 512, m)
    plan = spmv_lib.build_spmv_plan(rows, cols, n_rows=4096, n_cols=512,
                                    layout=layout)
    assert plan.chunk_block is None and plan.ov_rows is not None
    chunks = spmv_lib.build_spmv_plan(rows, cols, n_rows=4096, n_cols=512,
                                      layout="chunks")
    assert chunks.chunk_block is not None and chunks.ov_rows is None
    assert chunks.src8.shape[1] == spmv_lib.CHUNK


def test_refusals_are_named():
    """build_spmv_plan says which of its two gates refused."""
    n_rows = 512 * 20_000
    rows = np.arange(20_000, dtype=np.int64) * 512
    cols = np.zeros(20_000, np.int64)
    why = []
    assert spmv_lib.build_spmv_plan(rows, cols, n_rows=n_rows, n_cols=1,
                                    refusals=why) is None
    assert why[0].startswith("padding: the blocks layout takes 2560000 ")
    why = []
    assert spmv_lib.build_spmv_plan(rows[:10], cols[:10], n_rows=5120,
                                    n_cols=1, max_slots=100,
                                    refusals=why) is None
    assert why[0].startswith("bytes: the blocks layout takes 1280 slots")
    for layout in ("chunks", "auto"):
        assert spmv_lib.build_spmv_plan(rows, cols, n_rows=n_rows, n_cols=1,
                                        layout=layout) is None


# -- hub chunks (PR 36) --------------------------------------------------------
# In the chunks layout the edges from the sources of largest out-degree
# lie in chunks of their own, and the scatter kernel takes x for them
# from a (M, 128) table in VMEM by lane permutes: no row gather.


# the table rows of a Kronecker scale-13 graph's 6,467 sources and 13
# blocks by the rule left to itself: 32 pay, one more pays its entries of
# x[ids] in the walk step they open (the kernel holds a whole step of 64);
# at steps of 8 rows the 32 are whole steps and open none
AS_CHOSEN_ROWS_AT_SCALE_13 = {64: 33, 8: 32}
# and of the Graph500 scale-22 graph of cell pagerank_g500_22_1c (2,396,366
# sources, 4,681 blocks), from its shares of the edges (below)
G500_ROWS_CHOSEN = 2240


@pytest.fixture
def steps_of_eight(monkeypatch):
    """Walk steps of one (8, 128) tile, so that a table of a few dozen
    rows is several steps tall (the code's own step is 64 rows)."""
    monkeypatch.setattr(spmv_lib, "HUB_WALK", 8)


def _hub_rows_max(monkeypatch, rows):
    if rows is not None:
        monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", rows)


def _hub_weights_in_a_kernel(idx, table, first, rows):
    """``pallas_spmv._hub_weights`` of one chunk of slots ``idx`` (16,
    128), its two registers walking the table rows ``first[r] : first[r]
    + rows[r]``, run as the hub kernel runs it (interpreted)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from matrel_tpu.ops import pallas_spmv as pc

    def kernel(walk_ref, idx_ref, table_ref, out_ref):
        out_ref[...] = pc._hub_weights(
            idx_ref[...], table_ref,
            lambda r: (walk_ref[2 * r], walk_ref[2 * r + 1]),
            spmv_lib.HUB_WALK)

    whole = lambda shape: pl.BlockSpec(shape, lambda i, w: (0, 0))
    walk = np.stack([first, np.asarray(rows) // spmv_lib.HUB_WALK],
                    1).astype(np.int32).reshape(-1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[whole(idx.shape), whole(table.shape)],
            out_specs=whole(idx.shape)),
        out_shape=jax.ShapeDtypeStruct(idx.shape, jnp.float32),
        interpret=True)(jnp.asarray(walk), jnp.asarray(idx),
                        jnp.asarray(table))


@pytest.mark.parametrize("case", sorted(_BIT_CASES))
def test_hub_weights_are_x_idx_bit_for_bit(case, monkeypatch, steps_of_eight):
    """A permute moves 32-bit lanes and a select picks whole values: a
    hub slot's weight is the table's entry to the last bit, specials
    included, through the walk of the rows the register names (PR 42:
    two steps for the one, the second and third tile of the table; one
    for the other), and a padded slot (the rank past the table) reads
    +0.0 whatever lies in the table."""
    from matrel_tpu.ops import pallas_spmv as pc
    table = _BIT_CASES[case]
    n = table.shape[0]
    # the case's values on three of the table's 32 rows, one back to front
    rows = np.full((32, pc.LANE), np.float32(7.0))
    rows[9, 5:5 + n], rows[17, 100:100 + n] = table, table[::-1]
    rows[30, 20:20 + n] = table
    rows[9, 0] = 0.0
    idx = np.full((16, pc.LANE), 32 * pc.LANE, np.int32)      # padded slots
    idx[3, :n] = 9 * pc.LANE + 5 + np.arange(n)
    idx[6, 7:7 + n] = 17 * pc.LANE + 100 + np.arange(n)
    idx[12, 3:3 + n] = 30 * pc.LANE + 20 + np.arange(n)
    idx[5, ::2] = 9 * pc.LANE                                 # a +0.0 entry
    first, walked = spmv_lib.hub_walks(idx[None], 32 * pc.LANE)
    np.testing.assert_array_equal(first[0], [8, 24])
    np.testing.assert_array_equal(walked[0], [16, 8])
    got = _hub_weights_in_a_kernel(idx, rows, first[0], walked[0])
    want = np.concatenate([rows.reshape(-1), np.zeros(1, np.float32)])[idx]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # a row the walk leaves out is not read: the slots of row 17 weigh 0
    got = _hub_weights_in_a_kernel(idx, rows, [8, 24], [8, 8])
    want[6] = 0.0
    np.testing.assert_array_equal(_bits(got), _bits(want))

    # the compact matvec of a selection matrix whose sources are all hubs
    # (fewer than 128: the table's one row, which the kernel holds as a
    # walk step of 8 rows here, the others zeros):
    # y is x[cols], the specials its lane neighbours in the table, empty
    # rows +0.0 (a table row of so few edges pays only because the test
    # says so)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    cols = np.flatnonzero(_survives_split(table))[::-1]
    sel_rows = np.arange(cols.size) * 2
    plan = spmv_lib.build_spmv_plan(sel_rows, cols,
                                    np.ones(cols.size, np.float32),
                                    n_rows=2 * cols.size, n_cols=n,
                                    layout="chunks")
    assert plan.hubs.ids.size == pc.LANE
    assert not plan.val.any()
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    y = pc.compact_apply(static, pc.compact_tables(plan), plan.overflow,
                         jnp.asarray(table), interpret=True)
    want = np.zeros(plan.n_rows, np.float32)
    want[sel_rows] = table[cols]
    np.testing.assert_array_equal(_bits(y), _bits(want))


@pytest.fixture(scope="module")
def kronecker_13():
    """(src, dst, vertices) of a Graph500 Kronecker graph of scale 13, both
    directions of every edge: the Graph500 cell's generator."""
    import os
    from benchmarks import run as harness
    g500 = harness.load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "ldbc_graphalytics_g500_22.py"))
    lo, hi, v = g500.kronecker_graph(13, 16, (0.57, 0.19, 0.19), 1)
    return g500.directed_in_seed_order(lo, hi, 36) + (v,)


def _assert_hub_slots_in_order(hub):
    """A block's real hub slots lie by table row INTO registers of
    ``HUB_REG`` (no register names a row below one an earlier register
    of its block names), inside a register by destination row (PR 51),
    the padding after them with the last real slot's ``off``."""
    n = hub.ids.size
    for b in np.unique(hub.chunk_block):
        at = hub.chunk_block == b
        idx, off = hub.idx[at].reshape(-1), hub.off[at].reshape(-1)
        real = idx < n
        k = int(real.sum())
        assert real[:k].all(), "padding lies at the block's end"
        if k:
            np.testing.assert_array_equal(off[k:], off[k - 1])
        regs = [slice(r, min(r + spmv_lib.HUB_REG, k))
                for r in range(0, k, spmv_lib.HUB_REG)]
        for before, after in zip(regs, regs[1:]):
            assert (idx[before] >> 7).max() <= (idx[after] >> 7).min()
        for reg in regs:
            assert (np.diff(off[reg]) >= 0).all()
            # stable: a row's slots by table row
            key = off[reg].astype(np.int64) * (n >> 7) + (idx[reg] >> 7)
            assert (np.diff(key) >= 0).all()


def _hub_walks_before_pr51(plan):
    """``hub_walks`` of the layout PRs 42 to 50 built of the same plan:
    a block's hub slots by table row alone."""
    hub = plan.hubs
    idx = hub.idx.copy()
    for b in np.unique(hub.chunk_block):
        at = hub.chunk_block == b
        flat = idx[at].reshape(-1)
        k = int((flat < hub.ids.size).sum())
        flat[:k] = flat[:k][np.argsort(flat[:k] >> 7, kind="stable")]
        idx[at] = flat.reshape(-1, spmv_lib.CHUNK)
    return spmv_lib.hub_walks(idx, hub.ids.size)


def _assert_walks_cover(hub):
    """Every register's recorded run of table rows starts on a tile, is
    whole walk steps (one at the least: a register of padding walks the
    table's first), lies inside the table as the kernel holds it, and
    holds the row of every real slot of the register; and no run is a
    step longer than its slots need."""
    step, tile, n = spmv_lib.HUB_WALK, spmv_lib.HUB_TILE, hub.ids.size
    regs = spmv_lib.CHUNK // spmv_lib.HUB_REG
    assert hub.first.shape == hub.rows.shape == (hub.idx.shape[0], regs)
    assert hub.first.dtype == hub.rows.dtype == np.int32
    assert not (hub.first % tile).any() and not (hub.rows % step).any()
    assert (hub.first >= 0).all()
    assert (hub.first + hub.rows
            <= spmv_lib.hub_table_rows(n // spmv_lib.HUB_ROW)).all()
    row = hub.idx.reshape(-1, regs, spmv_lib.HUB_REG) >> 7
    real = hub.idx.reshape(row.shape) < n
    inside = (row >= hub.first[..., None]) & (
        row < (hub.first + hub.rows)[..., None])
    assert inside[real].all()
    some = real.any(axis=2)
    assert (hub.rows >= step).all()
    np.testing.assert_array_equal(hub.rows[~some], step)    # the least
    np.testing.assert_array_equal(hub.first[~some], 0)
    low = np.where(real, row, n).min(axis=2)
    high = np.where(real, row, -1).max(axis=2)
    assert (hub.rows[some] < (high - low)[some] + 1 + tile + step).all()


@pytest.mark.parametrize("step", [64, 8], ids=["steps_of_64", "steps_of_8"])
@pytest.mark.parametrize("hub_rows", [0, 1, 8, 32, None],
                         ids=["no_hubs", "128_hubs", "1024_hubs",
                              "4096_hubs", "as_chosen"])
def test_skewed_matvec_with_and_without_hubs(kronecker_13, monkeypatch, rng,
                                             hub_rows, step):
    """``step``: the rows a walk step takes, the code's own 64 (at this
    scale a table is a step tall, or less and padded) and 8, where the
    walks are several steps long and differ a register."""
    from matrel_tpu.ops import pallas_spmv as pc
    src, dst, v = kronecker_13
    monkeypatch.setattr(spmv_lib, "HUB_WALK", step)
    _hub_rows_max(monkeypatch, hub_rows)
    if hub_rows:        # a table of exactly that many rows
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    vals = rng.standard_normal(src.size).astype(np.float32)
    plan = spmv_lib.build_spmv_plan(dst, src, vals, v, v, layout="chunks")
    hub = plan.hubs
    assert plan.overflow == () and plan.ov_rows is None
    if hub_rows == 0:
        assert hub is None
        hub_edges = hub_slots = 0
    else:
        assert hub.ids.size == spmv_lib.HUB_ROW * (
            hub_rows or AS_CHOSEN_ROWS_AT_SCALE_13[step])
        hub_edges, hub_slots = int((hub.val != 0).sum()), hub.val.size
        assert np.isin(src, hub.ids).sum() == hub_edges
        _assert_walks_cover(hub)
        _assert_hub_slots_in_order(hub)
        assert spmv_lib.rows_in_order(plan)
        # and so its registers share the table out: they walk little
        # more than the table once a block, not once a register
        blocks = np.unique(hub.chunk_block).size
        assert hub.rows.sum() <= blocks * spmv_lib.hub_table_rows(
            hub.ids.size // spmv_lib.HUB_ROW) \
            + 2 * spmv_lib.HUB_WALK * hub.rows.size
    # every edge in one of the two sets, the rest of both padding
    assert int((plan.val != 0).sum()) + hub_edges == (vals != 0).sum()
    assert plan.padding_ratio == (plan.val.size + hub_slots) / src.size
    x = rng.standard_normal(v).astype(np.float32)
    y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x), interpret=True))
    want = coo_oracle(dst, src, vals, x, v)
    assert np.abs(y - want).max() / np.abs(want).max() < 2e-7


def test_a_chunk_that_names_the_whole_table_and_a_block_with_one_hub_slot(
        monkeypatch, rng, steps_of_eight):
    """Block 0 takes one edge from every one of 2,048 hubs (16 table
    rows): its one chunk is full and its two registers walk the table's
    two halves. Block 1 takes a single hub edge, from the table's last
    row: a chunk of one real slot, one walk of one step and one of
    none. Block 2 takes all hubs but the last, and the other sources."""
    from matrel_tpu.ops import pallas_spmv as pc
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 16)
    n_hubs = 16 * spmv_lib.HUB_ROW
    # every hub has two edges (among equals the smaller id ranks first,
    # so hub h lies at table row h // 128): one into block 0 and one into
    # block 2, but for the last hub, whose second goes into block 1; the
    # other 300 sources have one edge each, into block 2
    cols = np.concatenate([np.arange(n_hubs), np.arange(n_hubs),
                           n_hubs + np.arange(300)])
    rows = np.concatenate([rng.integers(0, 512, n_hubs),
                           1024 + rng.integers(0, 512, n_hubs - 1), [700],
                           1024 + rng.integers(0, 512, 300)])
    order = rng.permutation(cols.size)
    rows, cols = rows[order], cols[order]
    vals = rng.standard_normal(cols.size).astype(np.float32)
    n = n_hubs + 300
    plan = spmv_lib.build_spmv_plan(rows, cols, vals, 1536, n,
                                    layout="chunks")
    hub = plan.hubs
    assert hub.ids.size == n_hubs
    np.testing.assert_array_equal(hub.chunk_block, [0, 1, 2])
    np.testing.assert_array_equal(hub.first, [[0, 8], [8, 0], [0, 8]])
    np.testing.assert_array_equal(hub.rows, [[8, 8], [8, 8], [8, 8]])
    _assert_walks_cover(hub)
    x = rng.standard_normal(n).astype(np.float32)
    y = np.asarray(pc.spmv_compact(plan, jnp.asarray(x), interpret=True))
    want = coo_oracle(rows, cols, vals, x, 1536)
    assert np.abs(y - want).max() / np.abs(want).max() < 2e-7


def test_a_plan_in_input_order_walks_what_its_slots_name(kronecker_13,
                                                         monkeypatch, rng,
                                                         steps_of_eight):
    """The walks are reckoned from the slots, whatever their order: hub
    chunks whose slots lie in another order (a plan file of PR 36) name
    wide runs, which the kernel walks to the same answer."""
    import dataclasses
    from matrel_tpu.ops import pallas_spmv as pc
    src, dst, v = kronecker_13
    _hub_rows_max(monkeypatch, 32)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    plan = spmv_lib.build_spmv_plan(dst, src, None, v, v, layout="chunks")
    hub = plan.hubs
    # (the same permutation a chunk for the three tables)
    perm = np.argsort(rng.random(hub.idx.shape), axis=1)
    take = lambda a: np.take_along_axis(a, perm, axis=1)
    other = spmv_lib.HubChunks.of(hub.ids, take(hub.idx), take(hub.off),
                                  take(hub.val), hub.chunk_block)
    _assert_walks_cover(other)
    assert other.rows.sum() > hub.rows.sum()
    x = jnp.asarray(rng.standard_normal(v).astype(np.float32))
    a = pc.spmv_compact(plan, x, interpret=True)
    b = pc.spmv_compact(dataclasses.replace(plan, hubs=other), x,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                               atol=1e-6)


def _compact_apply_before_pr36(plan_static, tables, ov, x, passes, interpret):
    """``compact_apply`` as it stood before PR 36: what a plan without
    hub chunks must still lower to, word for word."""
    from matrel_tpu.ops import pallas_spmv as pc
    n_rows, n_cols, block, lo = plan_static
    src8, lane, off, val, *chunk_block = tables
    rows, cr, _ = src8.shape
    w = pc._slot_weights(src8, lane, val, x)
    if chunk_block:
        scatter = pc._chunk_runner(rows, cr * pc.LANE, -(-n_rows // block),
                                   block, lo, passes, interpret)
        y = scatter(chunk_block[0], off, w).reshape(-1)[:n_rows]
    else:
        scatter = pc._compact_runner(rows, cr * pc.LANE, block, lo, passes,
                                     interpret)
        y = scatter(off, w).reshape(-1)[:n_rows]
    if ov:
        y = spmv_lib._overflow_add(y, ov, x, n_rows)
    return y


@pytest.mark.parametrize("graph,layout", [
    ("uniform", "auto"), ("uniform", "blocks"), ("uniform_wide", "chunks"),
    ("skewed", "blocks"), ("skewed", "auto")])
def test_no_hubs_off_the_skewed_chunks_and_the_program_is_the_parents(
        kronecker_13, monkeypatch, rng, graph, layout):
    """Hubs exist only where the layout is chunks and the largest sources
    hold their share: a uniform graph (which "auto" lays in blocks; in
    chunks, the 32,768 largest of 1.5M sources hold 6% of the edges) and
    every blocks plan have none, and their matvec lowers to the text it
    lowered to before."""
    from matrel_tpu.ops import pallas_spmv as pc
    if graph.startswith("uniform"):
        v, m = (200_000, 2_000_000) if graph == "uniform" else (
            1_500_000, 3_000_000)
        src, dst = rng.integers(0, v, m), rng.integers(0, v, m)
    else:       # below the small-plan threshold "auto" keeps blocks
        src, dst, v = kronecker_13
    plan = spmv_lib.build_spmv_plan(dst, src, None, v, v, layout=layout)
    assert plan.hubs is None
    assert (plan.chunk_block is not None) == (layout == "chunks")
    tables = pc.compact_tables(plan)
    assert len(tables) == (5 if layout == "chunks" else 4)
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    x = jax.ShapeDtypeStruct((v,), jnp.float32)
    texts = [jax.jit(lambda t, ov, r: apply(static, t, ov, r, 3, True)
                     ).lower(tables, plan.overflow, x).as_text()
             for apply in (pc.compact_apply, _compact_apply_before_pr36)]
    assert "pallas_call" in texts[0] or "while" in texts[0]
    assert texts[0] == texts[1]


def _zipf_degrees(n, exponent, edges):
    d = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return np.maximum((d / d.sum() * edges).astype(np.int64), 1)


def _a_row_pays(blocks):
    """Edges a table row needs to pay for itself (see ``_hub_rows``)."""
    return int(np.ceil(spmv_lib._HUB_ROW_EDGES_A_BLOCK * blocks
                       + spmv_lib._HUB_ROW_EDGES))


def _a_row_fills(pays):
    """Edges of a row that pays its entries of ``x[ids]`` and no walk of
    its own: between ``_HUB_ROW_EDGES`` and what a row pays with."""
    return (spmv_lib._HUB_ROW_EDGES + pays) // 2


def _rows_of(edges_a_row, rows):
    """``rows`` table rows of 128 sources that hold ``edges_a_row``."""
    return np.full(128 * rows, -(-edges_a_row // 128), np.int64)


# the Graph500 scale-22 graph's 128.3M directed edges by sources of falling
# degree, as (table rows, mean edges a row) between marks (my desk count
# on the cell's own graph, PR 42: 52.3 / 62.0 / 74.5 / 84.7 / 85.9 / 92.3 /
# 97.5% of the edges at 256 / 512 / 1,024 / 2,048 / 2,192 / 4,096 / 8,192
# rows; the degrees step down from 9,728 to 7,156 edges a row at 2,192)
_G500_ROWS = [(256, 262_126), (256, 48_815), (512, 31_231), (1024, 12_767),
              (144, 10_675), (1904, 4_338), (4096, 1_617), (10_529, 304)]
_G500_DEG = np.concatenate([_rows_of(edges, rows)
                            for rows, edges in _G500_ROWS])
G500_BLOCKS = 4681


@pytest.mark.parametrize("name,deg,blocks,want_rows", [
    # 10 edges a source, a million sources: a row of 1,280 edges pays
    # for a walk in none of 1,954 blocks
    ("flat", np.full(1_000_000, 10), 1954, 0),
    # rows that pay, twice over, but hold a twentieth of the edges
    ("paying_rows_under_a_tenth", lambda pays: np.concatenate([
        _rows_of(2 * pays, 8), np.full(62 * pays, 10)]),
     1954, 0),
    # 70 rows pay, the 71st would not: the second walk step of 64 rows
    # is walked whole all the same, and the 58 rows that fill it hold
    # edges enough to pay their entries of x[ids]
    ("taken_while_they_pay", lambda pays: np.concatenate([
        _rows_of(2 * pays, 70), _rows_of(_a_row_fills(pays), 400)]),
     300, 128),
    # ... and here they do not: a table of 70 rows (the kernel holds it
    # as 128, the others zeros that cost no gather)
    ("the_step_is_not_filled_for_nothing", lambda pays: np.concatenate([
        _rows_of(2 * pays, 70), np.ones(128 * 400, np.int64)]), 300, 70),
    # ... and here some do: the rows of a step one by one
    ("the_step_is_filled_while_a_row_pays_its_gather",
     lambda pays: np.concatenate([
         _rows_of(2 * pays, 70), _rows_of(_a_row_fills(pays), 9),
         np.ones(128 * 400, np.int64)]), 300, 79),
    # every row pays: as many as the table may have
    ("capped", lambda pays: _rows_of(3 * pays, 2 * spmv_lib._HUB_ROWS_MAX),
     64, "cap"),
    # no more rows than there are sources for
    ("fewer_than_128_sources", lambda pays: np.full(50, pays), 4, 1),
    ("one_source", np.array([100_000]), 1, 1),
    ("one_source_of_few_edges", np.array([20]), 1, 0),
    # 128 hubs of 10,000 edges, then one edge a source
    ("the_tail_does_not_pay", np.concatenate([
        np.full(128, 10_000), np.ones(500_000, np.int64)]), 977, 1),
    ("no_edges", np.zeros(1000, np.int64), 2, 0),
    ("graph500_scale_22", _G500_DEG, G500_BLOCKS, G500_ROWS_CHOSEN),
])
def test_the_hub_table_is_chosen_from_the_degrees(name, deg, blocks,
                                                  want_rows):
    if callable(deg):
        deg = deg(_a_row_pays(blocks))
    if want_rows == "cap":
        want_rows = spmv_lib._HUB_ROWS_MAX
    deg = np.sort(np.asarray(deg, np.int64))[::-1]
    assert spmv_lib._hub_rows(deg, int(deg.sum()), blocks) == want_rows
    # through the build's own door: the ids are the largest sources, the
    # smaller id first among equals
    if 0 < deg.size <= 1000:
        cols = np.repeat(np.arange(deg.size), deg)
        ids, rank = spmv_lib._choose_hubs(cols, deg.size, blocks)
        if want_rows == 0:
            assert ids is None and rank is None
        else:
            assert ids.size == want_rows * spmv_lib.HUB_ROW
            real = min(ids.size, deg.size)
            np.testing.assert_array_equal(ids[:real], np.arange(real))
            assert not ids[real:].any()
            np.testing.assert_array_equal(rank, np.arange(deg.size))


def test_a_row_costs_a_walk_a_block_and_no_longer_every_hub_slot():
    """PR 36's rule charged a row to every hub slot; since PR 42 a row is
    walked about once a block: the table is as tall on a graph of ten
    times the edges a source, shorter where the blocks are more, and the
    hubs hold the more of the edges the steeper the degrees fall."""
    rows_of = lambda deg, blocks: spmv_lib._hub_rows(
        np.sort(deg)[::-1], int(deg.sum()), blocks)
    deg = _zipf_degrees(1_000_000, 0.8, 10**7)
    assert rows_of(10 * deg, 1954) >= rows_of(deg, 1954) > 0
    by_blocks = [rows_of(deg, b) for b in (100, 1954, 20_000, 200_000)]
    assert by_blocks == sorted(by_blocks, reverse=True)
    assert by_blocks[0] > by_blocks[-1]
    shares = []
    for exponent in (0.5, 0.7, 0.9, 1.1):
        deg = np.sort(_zipf_degrees(1_000_000, exponent, 10**7))[::-1]
        rows = spmv_lib._hub_rows(deg, int(deg.sum()), 1954)
        shares.append(deg[:128 * rows].sum() / deg.sum())
    assert shares == sorted(shares) and shares[-1] > 0.5


def test_the_hub_table_stops_where_its_chunks_would_pass_smem(monkeypatch):
    """The hub kernel's scalar-prefetched words, 8 B a chunk, lie in
    SMEM: the rule stops taking rows before the hub chunks there could
    be (their edges in whole chunks, one more a block) pass
    ``_HUB_CHUNKS_MAX`` — a narrower table and no refused compile — and
    takes none where the blocks alone would."""
    blocks, pays = 300, _a_row_pays(300)
    deg = _rows_of(10 * pays, 200)
    a_row = int(deg[:128].sum())
    edges = int(deg.sum())
    assert spmv_lib._hub_rows(deg, edges, blocks) == 200
    monkeypatch.setattr(spmv_lib, "_HUB_CHUNKS_MAX",
                        blocks + 50 * a_row // spmv_lib.CHUNK)
    rows = spmv_lib._hub_rows(deg, edges, blocks)
    assert rows == 50
    assert (-(-rows * a_row // spmv_lib.CHUNK) + blocks
            <= spmv_lib._HUB_CHUNKS_MAX + 1)
    monkeypatch.setattr(spmv_lib, "_HUB_CHUNKS_MAX", blocks)
    assert spmv_lib._hub_rows(deg, edges, blocks) == 0


def test_a_chunks_walks_ride_in_one_word():
    """Both registers' walks of a hub chunk in one int32 (first row in
    tiles of 8, steps of ``HUB_WALK``), up to the tallest table the rule
    may choose, the sign bit included; what does not fit is refused."""
    from matrel_tpu.ops import pallas_spmv as pc
    tall = spmv_lib.hub_table_rows(spmv_lib._HUB_ROWS_MAX)
    assert tall <= 4096
    step = spmv_lib.HUB_WALK
    first = np.array([[0, tall - step], [tall - step, 0], [8, 4032],
                      [0, 0]], np.int32)
    rows = np.array([[tall, step], [step, tall], [2 * step, step],
                     [step, step]], np.int32)
    word = pc._pack_walks(first, rows)
    assert word.dtype == np.int32 and word.shape == (4,)
    field = (word[:, None] >> np.array([0, 16])) & 0xFFFF   # as the kernel
    np.testing.assert_array_equal((field >> pc._WALK_STEP_BITS) * 8, first)
    np.testing.assert_array_equal(
        (field & ((1 << pc._WALK_STEP_BITS) - 1)) * step, rows)
    with pytest.raises(ValueError, match="packed word"):
        pc._pack_walks(np.array([[4096, 0]], np.int32), rows[3:])
    with pytest.raises(ValueError, match="packed word"):
        pc._pack_walks(first[3:], np.array([[step + 8, step]], np.int32))
    assert pc._pack_walks(first[:0], rows[:0]).shape == (0,)


# -- the k-wide compact product (PR 37) --------------------------------------------


def _skewed_entries(rng, n_rows, n_cols, m):
    """A hub block (a third of the entries land in rows 512..1023, many
    chunks), an empty block (rows 1536..2047 hold none), values not
    exact in bfloat16."""
    rows = rng.integers(0, n_rows, m)
    rows[:m // 3] = rng.integers(512, 1024, m // 3)
    rows[rows // 512 == 3] = 0
    cols = rng.integers(0, n_cols, m)
    return rows, cols, rng.standard_normal(m).astype(np.float32)


@pytest.mark.parametrize("orientation", ["forward", "transposed"])
@pytest.mark.parametrize("layout", ["chunks", "blocks"])
def test_k_wide_compact_product_in_panels(rng, monkeypatch, layout,
                                          orientation):
    """Both orientations of a skewed matrix times a 128-wide dense side
    against the float64 dense product, each layout through the one chunk
    kernel, forced into several panels by a small byte budget (the last
    one overlaps the one before: nothing is added twice)."""
    from matrel_tpu.ops import pallas_spmv as pc
    n_rows, n_cols, m, k = 3000, 700, 60_000, 128
    rows, cols, vals = _skewed_entries(rng, n_rows, n_cols, m)
    if orientation == "transposed":
        rows, cols, n_rows, n_cols = cols, rows, n_cols, n_rows
    plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                    layout=layout, hubs=False)
    assert (plan.chunk_block is not None) == (layout == "chunks")
    table_rows, cap = plan.src8.shape
    chunk = cap if layout == "chunks" else max(
        d for d in range(1, 17) if (cap // 128) % d == 0) * 128
    walked = table_rows * (cap // chunk)
    # a quarter of the budget holds a few chunks' temporaries: the
    # first count whose even split leaves the last panel overlapping
    for most in (7, 5, 4, 3):
        monkeypatch.setattr(pc, "_hbm_limit", lambda: 4 * most * chunk
                            * pc._TEMP_BYTES_A_SLOT_WIDE)
        per = pc.wide_panel_rows(walked, chunk)
        if walked % per:
            break
    assert 1 < per < walked and walked % per, (per, walked)
    X = rng.standard_normal((n_cols, k)).astype(np.float32)
    got = np.asarray(pc.spmm_compact(plan, jnp.asarray(X), interpret=True),
                     np.float64)
    want = np.zeros((n_rows, k))
    np.add.at(want, rows, vals.astype(np.float64)[:, None]
              * X.astype(np.float64)[cols])
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6
    if orientation == "forward":
        assert not got[1536:2048].any()             # the empty block
    # the lower-precision settings are what they say
    errs = [np.max(np.abs(np.asarray(pc.spmm_compact(
        plan, jnp.asarray(X), passes=p, interpret=True)) - want))
        / np.max(np.abs(want)) for p in (2, 1)]
    assert 1e-6 < errs[0] < 1e-4 < errs[1] < 1e-2


@pytest.mark.parametrize("k", [2, 130])
def test_k_wide_compact_product_any_width_and_hub_chunks(rng, monkeypatch,
                                                        k):
    """Narrower than a lane row, wider than one (two passes of columns),
    and a plan whose skewed sources lie in hub chunks of their own (a
    PageRank plan handed to the k-wide product): the hub slots' rows are
    fetched by the hubs' ids."""
    from matrel_tpu.ops import pallas_spmv as pc
    monkeypatch.setattr(spmv_lib, "_HUB_MIN_SHARE", 0.0)
    rows, cols, vals = _skewed_entries(rng, 3000, 700, 40_000)
    cols[:20_000] = rng.integers(0, 40, 20_000)
    plan = spmv_lib.build_spmv_plan(rows, cols, vals, 3000, 700,
                                    layout="chunks")
    assert plan.hubs is not None
    X = rng.standard_normal((700, k)).astype(np.float32)
    got = np.asarray(pc.spmm_compact(plan, jnp.asarray(X), interpret=True),
                     np.float64)
    want = np.zeros((3000, k))
    np.add.at(want, rows, vals.astype(np.float64)[:, None]
              * X.astype(np.float64)[cols])
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6


def test_source_panels_follow_the_gather_tables_bytes():
    """A float32 row of up to 128 columns is 512 B in the chip's tiles:
    64 MB hold 131,064 rows and the zero row's eight."""
    assert spmv_lib.source_panels(17_770) == 1
    assert spmv_lib.source_panels(131_064) == 1
    assert spmv_lib.source_panels(131_065) == 2
    assert spmv_lib.source_panels(480_189) == 4


def test_no_hub_chunks_where_the_caller_declines_them(rng, monkeypatch):
    monkeypatch.setattr(spmv_lib, "_HUB_MIN_SHARE", 0.0)
    rows, cols, vals = _skewed_entries(rng, 3000, 700, 40_000)
    cols[:20_000] = rng.integers(0, 40, 20_000)
    with_hubs = spmv_lib.build_spmv_plan(rows, cols, vals, 3000, 700,
                                         layout="chunks")
    without = spmv_lib.build_spmv_plan(rows, cols, vals, 3000, 700,
                                       layout="chunks", hubs=False)
    assert with_hubs.hubs is not None and without.hubs is None
    assert without.src8.size < with_hubs.src8.size + with_hubs.hubs.idx.size


# -- row order and a window a chunk (PR 38) ----------------------------------------


def _native_or_skip():
    from matrel_tpu.utils import native
    if native.spmv_counts(np.zeros(1, np.int64), 512, 1) is None:
        pytest.skip("native library unavailable")
    return native


@pytest.mark.parametrize("shape", [
    (3000, 700, 60_000),        # a hub block of many chunks, an empty one
    (700, 3000, 60_000),        # two blocks, the second short
    (512, 40, 100),             # one block, one chunk, mostly padding
    (5000, 5000, 0),            # no entry at all
    (1300, 64, 2048 * 3),       # a block's entries fill its chunks whole
], ids=["hub_block", "two_blocks", "one_chunk", "empty", "no_padding"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("hub_rows", [0, 2], ids=["no_hubs", "two_hub_rows"])
def test_the_ragged_fills_agree_slot_for_slot_in_row_order(rng, monkeypatch,
                                                          shape, weighted,
                                                          hub_rows):
    """The chunks layout, without hub chunks and (PR 51) beside them: the
    library's fill and numpy's lay the same tables, a block's real main
    slots lie by row, a row's in input order, its hub slots by table row
    into registers and by row inside one; the reduction may read either
    build, the walks are what the layout of PRs 42 to 50 gave the same
    edges, and they still ride in one word a chunk."""
    from matrel_tpu.ops import pallas_spmv as pc
    native = _native_or_skip()
    n_rows, n_cols, m = shape
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", hub_rows)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES_A_BLOCK", 0.0)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
    monkeypatch.setattr(spmv_lib, "_HUB_MIN_SHARE", 0.0)
    if n_rows == 3000:
        rows, cols, vals = _skewed_entries(rng, n_rows, n_cols, m)
    else:
        rows, cols = rng.integers(0, n_rows, m), rng.integers(0, n_cols, m)
        vals = rng.standard_normal(m).astype(np.float32)
    if m == 2048 * 3:
        rows = np.repeat(np.arange(3) * 512, 2048) + rng.integers(0, 200, m)
    vals = vals if weighted else None
    nat = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                   layout="chunks")
    monkeypatch.setattr(native, "spmv_counts", lambda *a: None)
    ref = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                   layout="chunks")
    for name in ("src8", "lane", "off", "val", "chunk_block"):
        np.testing.assert_array_equal(getattr(nat, name), getattr(ref, name),
                                      err_msg=name)
    assert (nat.hubs is not None) == (ref.hubs is not None) \
        == bool(hub_rows and m)
    is_hub = np.zeros(m, bool)
    if nat.hubs is not None:
        for name in ("ids", "idx", "off", "val", "chunk_block", "first",
                     "rows"):
            np.testing.assert_array_equal(
                getattr(nat.hubs, name), getattr(ref.hubs, name),
                err_msg="hubs." + name)
        assert nat.hubs.entries == ref.hubs.entries
        _assert_hub_slots_in_order(nat.hubs)
        _assert_walks_cover(nat.hubs)
        first, walked = _hub_walks_before_pr51(nat)
        np.testing.assert_array_equal(nat.hubs.first, first)
        np.testing.assert_array_equal(nat.hubs.rows, walked)
        assert pc._pack_walks(first, walked).shape == (
            nat.hubs.idx.shape[0],)
        is_hub = np.isin(cols, nat.hubs.ids[:min(n_cols, nat.hubs.ids.size)])
        assert nat.hubs.entries == is_hub.sum()
    assert spmv_lib.rows_in_order(nat) and spmv_lib.rows_in_order(ref)
    # by row over the real slots of every block, and stable: the columns
    # of one row come in the order the entries were given; a padded
    # slot names its block's last real row (PR 51)
    src = nat.src8.astype(np.int64) * 8 + nat.lane
    real = src != n_cols
    assert real.sum() == m - is_hub.sum()
    for b in np.unique(nat.chunk_block):
        at = nat.chunk_block == b
        off, s, r = nat.off[at].ravel(), src[at].ravel(), real[at].ravel()
        assert r[:r.sum()].all(), "padding lies at the block's end"
        assert (np.diff(off[r]) >= 0).all()
        np.testing.assert_array_equal(off[~r], off[r][-1] if r.any() else 0)
        given = (rows // 512 == b) & ~is_hub
        order = np.argsort(rows[given], kind="stable")
        np.testing.assert_array_equal(s[r], cols[given][order])


_WIN_CASES = {
    # name: (real slots' rows, padded slots, block) -> (start, height) of
    # the chunk's window; (-1, 0): the whole block
    "fits": ([200, 201, 250, 327], 0, 512, (200, 128)),
    "one_row": ([77] * 9, 0, 512, (72, 128)),
    "unaligned_least_row": ([13, 14, 130], 0, 512, (8, 128)),
    "spans_128_rows_from_an_aligned_one": ([8, 135], 0, 512, (8, 128)),
    "spans_a_row_more": ([8, 136], 0, 512, (8, 256)),
    "121_rows_from_an_unaligned_one": ([15, 135], 0, 512, (8, 128)),
    "122_rows_from_an_unaligned_one": ([15, 136], 0, 512, (8, 256)),
    "spans_256_rows_from_an_aligned_one": ([8, 263], 0, 512, (8, 256)),
    "spans_257_rows": ([8, 264], 0, 512, (-1, 0)),
    "249_rows_from_an_unaligned_one": ([15, 263], 0, 512, (8, 256)),
    "250_rows_from_an_unaligned_one": ([15, 264], 0, 512, (-1, 0)),
    "the_whole_block": ([0, 511], 0, 512, (-1, 0)),
    "at_the_blocks_end_the_window_moves_back": ([500, 511], 0, 512,
                                                (384, 128)),
    "at_the_blocks_end_the_taller_window_moves_back": (
        [300, 511], 0, 512, (256, 256)),
    "padding_at_the_blocks_end_does_not_void_it": ([480, 511], 5, 512,
                                                   (384, 128)),
    "padding_does_not_void_a_window_far_from_row_0": ([300, 310], 7, 512,
                                                      (296, 128)),
    "padding_does_not_void_a_taller_window": ([200, 400], 7, 512,
                                              (200, 256)),
    "all_padding": ([], 6, 512, (384, 128)),
    "a_block_of_one_window": ([0, 127], 1, 128, (-1, 0)),
    "a_block_shorter_than_a_window": ([0, 3], 0, 64, (-1, 0)),
    "a_block_of_the_taller_window_keeps_the_shorter": (
        [104, 231], 0, 256, (104, 128)),
    "a_block_of_the_taller_window_has_none_of_it": (
        [104, 232], 0, 256, (-1, 0)),
    "all_padding_in_a_block_of_the_taller_window": ([], 3, 256,
                                                    (128, 128)),
    "a_wider_block": ([1000, 1100], 2, 2048, (1000, 128)),
    "a_wider_block_a_taller_window": ([1000, 1200], 2, 2048, (1000, 256)),
}


@pytest.mark.parametrize("case", sorted(_WIN_CASES))
def test_a_chunks_window_is_read_off_its_rows(case):
    """The ladder (PR 49): the shortest rung of ``WINDOWS`` below the
    block that holds the chunk's real slots, from the chunk's least row
    rounded down to 8 and moved back off the block's end."""
    rows, padded, block, want = _WIN_CASES[case]
    off = np.array([rows + [0] * padded], np.int32)
    real = np.array([[True] * len(rows) + [False] * padded])
    # among other chunks, which it does not look at
    off = np.concatenate([np.array([[0] * off.shape[1]], np.int32), off,
                          np.array([[block - 1] * off.shape[1]], np.int32)])
    real = np.concatenate([np.ones_like(real), real, np.ones_like(real)])
    win = spmv_lib.chunk_windows(off, real, block)
    assert win.dtype == np.int32 and win.shape == (3,)
    start, height = (a.tolist() for a in spmv_lib.window_of(win))
    assert (start[1], height[1]) == want
    assert (win[1] == -1) == (want == (-1, 0))
    shortest = spmv_lib.WINDOWS[0]
    if block > shortest:
        assert (start[0], height[0]) == (0, shortest)
        assert (start[2], height[2]) == (block - shortest, shortest)
    else:
        assert list(win) == [-1] * 3
    if want[1]:
        at, tall = want
        assert tall in spmv_lib.WINDOWS and tall < block
        assert at % 8 == 0 and at + tall <= block
        assert all(at <= r < at + tall for r in rows)
        # and no shorter rung holds them from its own start
        for less in (h for h in spmv_lib.WINDOWS if h < tall):
            low = min(min(rows) // 8 * 8, block - less)
            assert max(rows) - low >= less


def test_a_table_in_input_order_has_no_windows(rng):
    """Chunks whose slots name rows all over their block (the blocks
    fill, a PageRank plan's two sets) read "whole block" throughout."""
    off = rng.integers(0, 512, (40, 2048)).astype(np.int32)
    win = spmv_lib.chunk_windows(off, np.ones(off.shape, bool), 512)
    assert list(win) == [-1] * 40
    start, height = spmv_lib.window_of(win)
    assert not height.any() and (start == -1).all()


def _wide_scatter_before_pr38(cb, skip, off, val, g, acc, block, passes=3):
    """The k-wide chunk scatter as PR 37 wrote it: every chunk takes the
    whole block's one-hot."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops.pallas_spmv import _bf16_split
    n, cr, _ = off.shape

    def kernel(cb_ref, skip_ref, off_ref, val_ref, g_ref, acc_ref, y_ref):
        @pl.when(pc._first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = acc_ref[...]

        @pl.when(pl.program_id(0) >= skip_ref[0])
        def _():
            off, val = off_ref[0], val_ref[0]
            rows = jax.lax.broadcasted_iota(jnp.int32, (block, 128), 0)
            eye = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1))
            acc = jnp.zeros((block, 128), jnp.float32)
            for s in range(cr):
                oh = (off[s:s + 1, :] == rows).astype(jnp.bfloat16)
                col = jnp.sum(jnp.where(eye, val[s:s + 1, :], 0.0),
                              axis=1, keepdims=True)
                w = g_ref[0, s * 128:(s + 1) * 128, :] * col
                for part in _bf16_split(w, passes):
                    acc = acc + jnp.dot(oh, part.astype(jnp.bfloat16),
                                        precision=jax.lax.Precision.DEFAULT,
                                        preferred_element_type=jnp.float32)
            y_ref[0] += acc

    slots = pl.BlockSpec((1, cr, 128), lambda c, cb, skip: (c, 0, 0))
    sums = pl.BlockSpec((1, block, 128), lambda c, cb, skip: (cb[c], 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n,),
            in_specs=[slots, slots,
                      pl.BlockSpec((1, cr * 128, 128),
                                   lambda c, cb, skip: (c, 0, 0)), sums],
            out_specs=sums),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={5: 0}, interpret=True)(cb, skip, off, val, g,
                                                     acc)


def _one_block_of_both_kinds(rng):
    """Block 1 (rows 512..1023): 300 rows of 2 entries, then 11 rows of
    1,500 — its first chunk in row order spans 300 rows, its eight others
    a few; block 0 holds a sprinkle, block 2 two chunks over all its
    rows, block 3 nothing: 13 chunks."""
    rows = np.concatenate([
        512 + np.repeat(np.arange(300), 2), 512 + 300 + np.repeat(
            np.arange(11), 1500), rng.integers(0, 512, 700),
        rng.integers(1024, 1536, 2100)])
    rng.shuffle(rows)
    cols = rng.integers(0, 300, rows.size)
    return rows, cols, rng.standard_normal(rows.size).astype(np.float32)


def _one_block_of_three_heights(rng):
    """Block 1 (rows 512..1023) in row order: a chunk over 293 rows (7
    entries a row: the whole block), one over ~190 (11 a row: the
    256-row rung), eight over a few (1,500 a row: the 128-row rung);
    block 0 a sprinkle, block 2 two chunks over all its rows, block 3
    nothing."""
    rows = np.concatenate([
        512 + np.repeat(np.arange(300), 7),
        512 + 300 + np.repeat(np.arange(180), 11),
        512 + 480 + np.repeat(np.arange(11), 1500),
        rng.integers(0, 512, 700), rng.integers(1024, 1536, 2100)])
    rng.shuffle(rows)
    cols = rng.integers(0, 300, rows.size)
    return rows, cols, rng.standard_normal(rows.size).astype(np.float32)


def _wide_case(name, rng, monkeypatch):
    """(plan, rows, cols, vals, windowed chunks wanted: all / none / some)"""
    from matrel_tpu.ops import pallas_spmv as pc
    n_rows, n_cols = 2048, 300
    if name == "unsorted_blocks":
        n_rows = 2560
    if name == "sorted_chunks":
        rows, cols, vals = _skewed_entries(rng, n_rows, n_cols, 120_000)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                        layout="chunks", hubs=False)
        want = "all"
    elif name == "unsorted_blocks":
        _native_or_skip()           # numpy's blocks fill sorts by row
        # 4,224 entries in every block: a table row of 33 x 128 slots,
        # walked as three chunks of 1,408, none of them padding
        rows = rng.permutation(np.repeat(np.arange(5) * 512, 4224)
                               + rng.integers(0, 512, 5 * 4224))
        cols = rng.integers(0, n_cols, rows.size)
        vals = rng.standard_normal(rows.size).astype(np.float32)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                        layout="blocks")
        assert plan.chunk_block is None and plan.src8.shape == (5, 4224)
        want = "none"
    elif name in ("both_kinds_in_one_block", "three_heights_in_one_block"):
        rows, cols, vals = (
            _one_block_of_both_kinds if name == "both_kinds_in_one_block"
            else _one_block_of_three_heights)(rng)
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                        layout="chunks", hubs=False)
        want = "some"
    else:       # hub chunks: their slots lie by the hub table's row (PR
        # 42: the matvec's walks read that order), not by destination
        # row, so fewer of them take a window than of the main chunks
        monkeypatch.setattr(spmv_lib, "_HUB_MIN_SHARE", 0.0)
        monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)
        rows, cols, vals = _skewed_entries(rng, n_rows, n_cols, 60_000)
        cols[:30_000] = rng.integers(0, 40, 30_000)
        order = np.argsort(rows, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
        plan = spmv_lib.build_spmv_plan(rows, cols, vals, n_rows, n_cols,
                                        layout="chunks")
        assert plan.hubs is not None
        want = "some"
    return plan, rows, cols, vals, want


@pytest.mark.parametrize("panels", [False, True], ids=["whole", "panels"])
@pytest.mark.parametrize("name", ["sorted_chunks", "unsorted_blocks",
                                  "both_kinds_in_one_block",
                                  "three_heights_in_one_block",
                                  "hub_chunks"])
def test_k_wide_product_with_a_window_a_chunk(rng, monkeypatch, name,
                                              panels):
    """The k-wide product over chunks that take a 128-row window, chunks
    that take a 256-row one (PR 49), chunks that take the block, and all
    of them inside one block, whole and in panels whose last is moved
    back (``skip`` > 0 inside a block): the float64 product to 2e-7, and
    where no chunk has a window the sums PR 37's kernel gave, bit for
    bit."""
    from matrel_tpu.ops import pallas_spmv as pc
    plan, rows, cols, vals, want = _wide_case(name, rng, monkeypatch)
    wins, tall = pc.wide_windows(plan)
    assert sorted(tall) == list(spmv_lib.WINDOWS)
    windowed = sum(tall.values())
    walked = sum(int(w.shape[0]) for w in wins)
    assert len(wins) == (2 if plan.hubs is not None else 1)
    if want == "all":
        assert windowed == walked == plan.src8.shape[0] + (
            0 if plan.hubs is None else plan.hubs.idx.shape[0])
    elif want == "none":
        assert windowed == 0 and walked == 3 * plan.src8.shape[0]
    else:
        assert 0 < windowed < walked
    if name == "both_kinds_in_one_block":
        win, cb = np.asarray(wins[0]), plan.chunk_block
        assert sorted(set(np.sign(win[cb == 1]))) == [-1, 1]
    if name == "three_heights_in_one_block":
        height = spmv_lib.window_of(wins[0])[1][plan.chunk_block == 1]
        assert height.tolist() == [0, 256] + [128] * 9
        assert tall[256] == 1
    if panels:
        # a small byte budget: the largest set of chunks in several
        # panels, the last of them moved back over the one before
        chunk = plan.src8.size // int(wins[0].shape[0])
        n = max(int(w.shape[0]) for w in wins)
        for most in (7, 5, 4, 3):
            monkeypatch.setattr(pc, "_hbm_limit", lambda: 4 * most * chunk
                                * pc._TEMP_BYTES_A_SLOT_WIDE)
            per = pc.wide_panel_rows(n, chunk)
            if n % per:
                break
        assert 1 < per < n and n % per, (per, n)
        if name.endswith("in_one_block"):
            # the moved-back panel starts inside block 1
            assert plan.chunk_block[n - per] == 1 == plan.chunk_block[
                n - per - 1]
    k = 128
    X = rng.standard_normal((plan.n_cols, k)).astype(np.float32)
    got = np.asarray(pc.spmm_compact(plan, jnp.asarray(X), interpret=True))
    truth = np.zeros((plan.n_rows, k))
    np.add.at(truth, rows, vals.astype(np.float64)[:, None]
              * X.astype(np.float64)[cols])
    assert np.max(np.abs(got - truth)) / np.max(np.abs(truth)) <= 2e-7
    if want != "none":
        assert not got[1536:2048].any()             # the empty block
    # without the windows every chunk takes the block: as good
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    plain = np.asarray(jax.jit(
        lambda t, ov, x: pc.compact_matmat_apply(static, t, ov, x, 3, True)
    )(pc.compact_tables(plan), plan.overflow, jnp.asarray(X)))
    assert np.max(np.abs(plain - truth)) / np.max(np.abs(truth)) <= 2e-7
    if want == "none":
        np.testing.assert_array_equal(got, plain)


def test_k_wide_chunks_without_a_window_compute_what_pr37_did(rng):
    """A panel of chunks in input order (``win`` −1 throughout) through
    the kernel as it is and as PR 37 wrote it: the same bits; and with a
    window on the chunks that can take one, the same sums to float32's
    last digits (a row's terms meet in another order)."""
    from matrel_tpu.ops import pallas_spmv as pc
    n, cr, block, nb = 6, 4, 512, 3
    cb = jnp.asarray([0, 0, 1, 1, 1, 2], jnp.int32)
    off = rng.integers(0, block, (n, cr, 128)).astype(np.int32)
    off[2] = rng.integers(380, 500, (cr, 128))      # can take a window
    off[5] = 511
    val = rng.standard_normal((n, cr, 128)).astype(np.float32)
    g = rng.standard_normal((n, cr * 128, 128)).astype(np.float32)
    acc = rng.standard_normal((nb, block, 128)).astype(np.float32)
    skip = jnp.asarray([1], jnp.int32)
    was = np.asarray(_wide_scatter_before_pr38(
        cb, skip, jnp.asarray(off), jnp.asarray(val), jnp.asarray(g),
        jnp.asarray(acc), block))
    run = pc._wide_runner(n, cr * 128, nb, block, 3, True)
    none = jnp.full((n,), -1, jnp.int32)
    now = np.asarray(run(cb, skip, none, jnp.asarray(off), jnp.asarray(val),
                         jnp.asarray(g), jnp.asarray(acc)))
    np.testing.assert_array_equal(now, was)
    win = spmv_lib.chunk_windows(off.reshape(n, -1),
                                 np.ones((n, cr * 128), bool), block)
    assert [a.tolist() for a in spmv_lib.window_of(win)] == [
        [-1, -1, 376, -1, -1, 384], [0, 0, 128, 0, 0, 128]]
    windowed = np.asarray(run(cb, skip, jnp.asarray(win), jnp.asarray(off),
                              jnp.asarray(val), jnp.asarray(g),
                              jnp.asarray(acc)))
    np.testing.assert_array_equal(windowed[0], was[0])
    np.testing.assert_allclose(windowed, was, rtol=0, atol=2e-5)
