"""Incremental view maintenance (ir/delta.py + serve/ivm.py +
session.register_delta; docs/IVM.md): per-rule patch-vs-fresh
equivalence (int paths bit-exact), eligibility fallback to the
transitive kill, patch-vs-recompute pricing with the autotune ``ivm|``
override, generation-prefix cache isolation, steady-state patch-plan
reuse, MV113 both halves, the obs ``delta`` event + history roll-up,
and the default-config bit-identity contract (register_delta unused ⇒
zero delta-plane objects, no ``delta:`` key prefixes)."""

import json
import os

import numpy as np
import pytest

from matrel_tpu import executor as executor_lib
from matrel_tpu.analysis import delta_pass, verify_plan
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix
from matrel_tpu.core.sparse import BlockSparseMatrix
from matrel_tpu.ir import delta as delta_lib
from matrel_tpu.session import MatrelSession

RC = dict(result_cache_max_bytes=256 << 20)


def _sess(mesh, **cfg):
    return MatrelSession(mesh=mesh, config=MatrelConfig(**cfg))


def _int_adj(rng, n):
    a = (rng.random((n, n)) < 0.06).astype(np.float32)
    a = np.triu(a, 1)
    return a + a.T


def _coo_batch(rng, n, k, vals=None):
    rows = rng.integers(0, n, k)
    cols = rng.integers(0, n, k)
    v = np.ones(k, np.float32) if vals is None else vals
    return rows, cols, v


# ---------------------------------------------------------------------------
# MatrixDelta forms
# ---------------------------------------------------------------------------


class TestMatrixDelta:
    def test_coo_factors_reconstruct(self, mesh8, rng):
        old = BlockMatrix.from_numpy(_int_adj(rng, 64), mesh=mesh8,
                                     integral=True)
        rows, cols, v = _coo_batch(rng, 64, 9)
        d = delta_lib.as_delta((rows, cols, v), old, "coo")
        u, vv = d.factors(mesh8, MatrelConfig())
        got = u.to_numpy() @ vv.to_numpy().T
        np.testing.assert_array_equal(got, d.to_dense_numpy())
        assert d.rank == 9 and d.integral

    def test_lowrank_and_dense_kinds(self, mesh8, rng):
        old = BlockMatrix.from_numpy(
            rng.standard_normal((48, 32)).astype(np.float32),
            mesh=mesh8)
        U = rng.standard_normal((48, 3)).astype(np.float32)
        V = rng.standard_normal((32, 3)).astype(np.float32)
        d = delta_lib.as_delta((U, V), old, "lowrank")
        np.testing.assert_allclose(d.to_dense_numpy(), U @ V.T,
                                   rtol=1e-6)
        dd = delta_lib.as_delta(U @ V.T, old, "dense")
        assert dd.rank is None and dd.kind == "dense"

    def test_auto_disambiguation_and_validation(self, mesh8, rng):
        old = BlockMatrix.from_numpy(np.zeros((16, 16), np.float32),
                                     mesh=mesh8)
        coo = COOMatrix.from_edges([1, 2], [3, 4], shape=(16, 16))
        assert delta_lib.as_delta(coo, old).kind == "coo"
        with pytest.raises(ValueError, match="out of bounds"):
            delta_lib.as_delta(([99], [0], [1.0]), old, "coo")
        with pytest.raises(ValueError, match="shape"):
            delta_lib.as_delta(np.zeros((4, 4), np.float32), old,
                               "dense")
        with pytest.raises(ValueError, match="kind"):
            delta_lib.as_delta(np.zeros((16, 16)), old, "bogus")

    def test_apply_to_dense_and_sparse(self, mesh8, rng):
        a = _int_adj(rng, 64)
        old = BlockMatrix.from_numpy(a, mesh=mesh8, integral=True)
        rows, cols, v = _coo_batch(rng, 64, 7)
        d = delta_lib.as_delta((rows, cols, v), old, "coo")
        new = d.apply_to(old, mesh8, MatrelConfig())
        want = a.copy()
        np.add.at(want, (rows, cols), v)
        np.testing.assert_array_equal(new.to_numpy(), want)
        assert new.integral        # int + int stays provably int
        sp_old = BlockSparseMatrix.from_numpy(a, block_size=16,
                                              mesh=mesh8)
        sp_new = d.apply_to(sp_old, mesh8, MatrelConfig())
        np.testing.assert_array_equal(sp_new.to_numpy(), want)
        assert sp_new.block_size == 16

    def test_rank_above_bound_loses_factored_form(self, mesh8, rng):
        old = BlockMatrix.from_numpy(np.zeros((64, 64), np.float32),
                                     mesh=mesh8)
        rows, cols, v = _coo_batch(rng, 64, 12)
        d = delta_lib.as_delta((rows, cols, v), old, "coo")
        assert d.factors(mesh8, MatrelConfig(delta_rank_max=8)) is None
        assert d.factors(mesh8, MatrelConfig(delta_rank_max=16)) \
            is not None


# ---------------------------------------------------------------------------
# Per-rule patch-vs-fresh-execution equivalence
# ---------------------------------------------------------------------------


def _stream_check(sess, make_query, oracle_fn, name, make_delta,
                  steps, exact, tol=2e-4):
    """Run the query cold, then per step: produce one delta (the
    callable also advances the host oracle), register it, and assert
    the re-run HITS a patched entry and matches the oracle."""
    sess.run(make_query())
    for _ in range(steps):
        info0 = sess.result_cache_info()
        d_payload, kind = make_delta()
        sess.register_delta(name, d_payload, kind=kind)
        got = sess.run(make_query()).to_numpy()
        info1 = sess.result_cache_info()
        assert info1["hits"] > info0["hits"], "re-run did not hit"
        assert info1["patched"] > info0["patched"], "nothing patched"
        want = np.asarray(oracle_fn(), np.float32).reshape(got.shape)
        if exact:
            np.testing.assert_array_equal(got, want)
        else:
            scale = max(float(np.abs(want).max()), 1.0)
            np.testing.assert_allclose(got / scale, want / scale,
                                       atol=tol)


class TestRulePatchEquivalence:
    def test_matmul_left_delta(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n, k = 96, 24
        a = _int_adj(rng, n)
        f = rng.standard_normal((n, k)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("F", sess.from_numpy(f))
        state = {"a": a}

        def mk():
            return sess.table("A").expr().multiply(
                sess.table("F").expr())

        def delta():
            rows, cols, v = _coo_batch(rng, n, 5)
            np.add.at(state["a"], (rows, cols), v)
            return (rows, cols, v), "coo"

        _stream_check(sess, mk, lambda: state["a"] @ f, "A",
                      delta, 3, exact=False)

    def test_matmul_right_delta(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        g = rng.standard_normal((16, n)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("G", sess.from_numpy(g))
        state = {"a": a}

        def mk():
            return sess.table("G").expr().multiply(
                sess.table("A").expr())

        def delta():
            rows, cols, v = _coo_batch(rng, n, 4)
            np.add.at(state["a"], (rows, cols), v)
            return (rows, cols, v), "coo"

        _stream_check(sess, mk, lambda: g @ state["a"], "A",
                      delta, 2, exact=False)

    def test_gram_rank_k_correction_lowrank(self, mesh8, rng):
        # Δ(XᵀX) = ΔXᵀ·X + X'ᵀ·ΔX — the linreg panel-append case,
        # with an explicit low-rank (U, V) delta
        sess = _sess(mesh8, **RC)
        n, k = 128, 24
        x = rng.standard_normal((n, k)).astype(np.float32)
        sess.register("X", sess.from_numpy(x))
        state = {"x": x}

        def mk():
            return sess.table("X").expr().t().multiply(
                sess.table("X").expr())

        def delta():
            U = rng.standard_normal((n, 2)).astype(np.float32)
            V = rng.standard_normal((k, 2)).astype(np.float32)
            state["x"] = state["x"] + U @ V.T
            return (U, V), "lowrank"

        _stream_check(sess, mk, lambda: state["x"].T @ state["x"],
                      "X", delta, 2, exact=False, tol=1e-3)

    def test_elemwise_and_scalar_chain(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        b = rng.standard_normal((n, n)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("B", sess.from_numpy(b))
        state = {"a": a}

        def mk():
            return sess.table("A").expr().elem_multiply(
                sess.table("B").expr()).multiply_scalar(3.0) \
                .add(sess.table("B").expr())

        def delta():
            rows, cols, v = _coo_batch(rng, n, 4)
            np.add.at(state["a"], (rows, cols), v)
            return (rows, cols, v), "coo"

        _stream_check(sess, mk, lambda: state["a"] * b * 3.0 + b,
                      "A", delta, 2, exact=False)

    def test_aggregates_exact_int(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        state = {"a": a}
        for mk, oracle in (
                (lambda: sess.table("A").expr().row_sum(),
                 lambda: state["a"].sum(1, keepdims=True)),
                (lambda: sess.table("A").expr().sum(),
                 lambda: state["a"].sum().reshape(1, 1))):
            def delta():
                rows, cols, v = _coo_batch(rng, n, 3)
                np.add.at(state["a"], (rows, cols), v)
                return (rows, cols, v), "coo"

            _stream_check(sess, mk, oracle, "A", delta, 2,
                          exact=True)

    def test_triangle_trace_exact_via_known_propagation(self, mesh8,
                                                        rng):
        # the graph-count headline: trace(A³) patched EXACTLY, with
        # the cached A·A entry's delta propagating into the trace
        # patch as a leaf (the known-map DAG propagation)
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        state = {"a": a}

        def mk_aa():
            return sess.table("A").expr().multiply(
                sess.table("A").expr())

        def mk_tri():
            return sess.table("A").expr().multiply(
                sess.table("A").expr()).multiply(
                sess.table("A").expr()).trace()

        sess.run(mk_aa())
        sess.run(mk_tri())
        for _ in range(3):
            rows, cols, v = _coo_batch(rng, n, 4)
            np.add.at(state["a"], (rows, cols), v)
            s = sess.register_delta("A", (rows, cols, v), kind="coo")
            assert s["patched"] == 2 and s["killed"] == 0
            assert s["rules"].get("known", 0) >= 1
            got_aa = sess.run(mk_aa()).to_numpy()
            got_tri = sess.run(mk_tri()).to_numpy()
            np.testing.assert_array_equal(got_aa,
                                          state["a"] @ state["a"])
            np.testing.assert_array_equal(
                got_tri,
                np.float32(np.trace(state["a"] @ state["a"]
                                    @ state["a"])).reshape(1, 1))

    def test_sparse_delta_spgemm_dispatch(self, mesh8, rng):
        # sparse ΔA against a sparse leaf partner: the emitted product
        # must route the S×S SpGEMM dispatch (the PR 10 registry path)
        # force mode for the end-to-end half: at toy scale the n²
        # combine honestly outweighs the tiny SpGEMM product, and the
        # point here is the dispatch routing, not the pricing
        cfg = MatrelConfig(delta_patch_mode="force", **RC)
        sess = _sess(mesh8, delta_patch_mode="force", **RC)
        n, bs = 128, 16
        # BLOCK-sparse operands (a few occupied tiles, not uniform
        # element sparsity — uniform 1% still touches every tile and
        # the dispatch's output-block-density gate would refuse)
        def tiles(k):
            m = np.zeros((n, n), np.float32)
            for _ in range(k):
                bi = int(rng.integers(0, n // bs))
                bj = int(rng.integers(0, n // bs))
                blk = (rng.random((bs, bs)) < 0.2).astype(np.float32)
                m[bi * bs:(bi + 1) * bs, bj * bs:(bj + 1) * bs] = blk
            return m
        a = tiles(5)
        b = tiles(5)
        sp_a = BlockSparseMatrix.from_numpy(a, block_size=bs,
                                            mesh=mesh8)
        sp_b = BlockSparseMatrix.from_numpy(b, block_size=bs,
                                            mesh=mesh8)
        sess.register("SA", sp_a)
        sess.register("SB", sp_b)
        state = {"a": a}

        def mk():
            from matrel_tpu.ir import expr as E
            return E.matmul(E.as_expr(sess.table("SA")),
                            E.as_expr(sess.table("SB")))

        sess.run(mk())
        rows, cols, v = _coo_batch(rng, n, 6)
        np.add.at(state["a"], (rows, cols), v)
        old = sess.table("SA")
        d = delta_lib.as_delta((rows, cols, v), old, "coo")
        new = d.apply_to(old, mesh8, cfg)
        ent = sess._result_cache.items_snapshot()[0][1]
        spec = delta_lib.derive_patch(ent.expr, old, new, d,
                                      ent.result, mesh8, cfg)
        assert spec is not None
        assert spec.rule == "spgemm" and not spec.rebindable
        s = sess.register_delta("SA", (rows, cols, v), kind="coo")
        assert s["patched"] == 1
        got = sess.run(mk()).to_numpy()
        np.testing.assert_allclose(got, state["a"] @ b, atol=1e-4)

    def test_refine_hook_warm_restart(self, mesh8, rng):
        # the iterative family: a stamped delta_refine callable owns
        # the patch (PageRank-style warm restart from the cached value)
        sess = _sess(mesh8, **RC)
        n = 48
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        calls = []

        def refine(old_result, new_matrix, d):
            calls.append(1)
            return new_matrix.to_numpy().sum(1, keepdims=True)

        def mk():
            return delta_lib.stamp_refine(
                sess.table("A").expr().row_sum(), refine)

        sess.run(mk())
        rows, cols, v = _coo_batch(rng, n, 3)
        np.add.at(a, (rows, cols), v)
        s = sess.register_delta("A", (rows, cols, v), kind="coo")
        assert s["patched"] == 1 and s["rules"] == {"refine": 1}
        assert calls == [1]
        got = sess.run(mk()).to_numpy()
        np.testing.assert_array_equal(got, a.sum(1, keepdims=True))

    def test_pagerank_warm_restart_converges(self, rng):
        a = _int_adj(rng, 64)
        cold = delta_lib.pagerank_warm_restart(
            a.astype(np.float64), np.full(64, 1 / 64), rounds=300)
        np.add.at(a, (rng.integers(0, 64, 4),
                      rng.integers(0, 64, 4)), 1.0)
        cold2 = delta_lib.pagerank_warm_restart(
            a.astype(np.float64), np.full(64, 1 / 64), rounds=300)
        warm = delta_lib.pagerank_warm_restart(
            a.astype(np.float64), cold, rounds=40)
        assert np.abs(warm - cold2).sum() < 1e-8
        assert np.abs(warm - cold2).sum() <= np.abs(
            delta_lib.pagerank_warm_restart(
                a.astype(np.float64), np.full(64, 1 / 64),
                rounds=5) - cold2).sum()


# ---------------------------------------------------------------------------
# Eligibility fallback + pricing
# ---------------------------------------------------------------------------


class TestEligibilityAndPricing:
    def test_ineligible_falls_back_to_kill(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        q = sess.table("A").expr().select_value(lambda v: v > 0.5)
        sess.run(q)
        rows, cols, v = _coo_batch(rng, n, 3)
        s = sess.register_delta("A", (rows, cols, v), kind="coo")
        assert s["patched"] == 0 and s["killed"] == 1
        np.add.at(a, (rows, cols), v)
        got = sess.run(sess.table("A").expr().select_value(
            lambda v: v > 0.5)).to_numpy()
        np.testing.assert_array_equal(got, a * (a > 0.5))

    def test_priced_out_falls_back_to_kill(self, mesh8, rng):
        # a fat delta (rank ~ n) makes the n×n patch cost more than
        # recompute — the pricing must kill, not patch at a loss
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().multiply(
            sess.table("A").expr()))
        rows, cols, v = _coo_batch(rng, n, n)  # rank n delta
        s = sess.register_delta("A", (rows, cols, v), kind="coo")
        assert s["patched"] == 0 and s["killed"] == 1
        assert s["priced_out"] == 1

    def test_force_mode_overrides_pricing(self, mesh8, rng):
        sess = _sess(mesh8, delta_patch_mode="force", **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().multiply(
            sess.table("A").expr()))
        rows, cols, v = _coo_batch(rng, n, n)
        np.add.at(a, (rows, cols), v)
        s = sess.register_delta("A", (rows, cols, v), kind="coo")
        assert s["patched"] == 1 and s["priced_out"] == 0
        got = sess.run(sess.table("A").expr().multiply(
            sess.table("A").expr())).to_numpy()
        np.testing.assert_array_equal(got, a @ a)

    def test_off_mode_kills_everything(self, mesh8, rng):
        sess = _sess(mesh8, delta_patch_mode="off", **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        s = sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert s["patched"] == 0 and s["killed"] == 1

    def test_measured_ivm_winner_overrides_estimate(self, mesh8, rng,
                                                    tmp_path):
        # a persisted ivm| "recompute" winner must veto a patch the
        # estimate likes (the fuse| measured-override precedent)
        from matrel_tpu.parallel import autotune
        table = str(tmp_path / "tab.json")
        sess = _sess(mesh8, autotune=True, autotune_table_path=table,
                     **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        import jax
        gx, gy = 2, 4
        key = autotune._ivm_key("rank_k", n, gx, gy)
        autotune._persist(table, key, "recompute",
                          {"patch": 2.0, "recompute": 1.0})
        autotune._IVM_CACHE.clear()
        autotune._TABLE_CACHE.clear()
        s = sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert s["patched"] == 0 and s["priced_out"] == 1
        assert sess._delta_plane.stats["measured_overrides"] == 1

    def test_ivm_key_format_accepted_and_pruned(self):
        from matrel_tpu.parallel import autotune
        assert autotune._current_key_format(
            "ivm|rank_k|1024|2x4|cpu")
        assert autotune._current_key_format(
            "ivm|spgemm|512|2x4|cpu|w1x8")
        assert not autotune._current_key_format(
            "ivm|retired_rule|1024|2x4|cpu")
        assert not autotune._current_key_format("ivm|rank_k|1024|2x4")

    def test_lookup_or_measure_ivm_ties_never_persist(self, mesh8,
                                                      tmp_path):
        from matrel_tpu.parallel import autotune
        cfg = MatrelConfig(autotune=True,
                           autotune_table_path=str(tmp_path / "t.json"))
        autotune._IVM_CACHE.clear()
        got = autotune.lookup_or_measure_ivm(
            "linear", 64, mesh8, cfg,
            patch_s=lambda: 1.0, full_s=lambda: 1.0)
        assert got is None
        # lookup without runners never measures, never caches negative
        autotune._IVM_CACHE.clear()
        assert autotune.lookup_or_measure_ivm("linear", 64, mesh8,
                                              cfg) is None


# ---------------------------------------------------------------------------
# Generation isolation + steady state
# ---------------------------------------------------------------------------


class TestGenerationIsolation:
    def test_keys_carry_generation_prefix(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        b = rng.standard_normal((n, n)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("B", sess.from_numpy(b))
        sess.run(sess.table("A").expr().row_sum())
        sess.run(sess.table("B").expr().row_sum())   # independent
        keys0 = [k for k, _ in sess._result_cache.items_snapshot()]
        assert all(not k.startswith("delta:") for k in keys0)
        s = sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert s["gen"] == 1 and s["rekeyed"] == 1
        keys1 = [k for k, _ in sess._result_cache.items_snapshot()]
        assert keys1 and all(k.startswith("delta:1|") for k in keys1)
        # the independent entry was RENAMED, not killed: it still hits
        info0 = sess.result_cache_info()
        sess.run(sess.table("B").expr().row_sum())
        assert sess.result_cache_info()["hits"] == info0["hits"] + 1
        s2 = sess.register_delta("A", ([3], [4], [1.0]), kind="coo")
        assert s2["gen"] == 2
        keys2 = [k for k, _ in sess._result_cache.items_snapshot()]
        assert keys2 and all(k.startswith("delta:2|") for k in keys2)

    def test_precision_prefix_survives_patching(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        f = rng.standard_normal((n, 8)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("F", sess.from_numpy(f))

        def mk():
            return sess.table("A").expr().multiply(
                sess.table("F").expr())

        sess.run(mk(), precision="fast")
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        keys = [k for k, _ in sess._result_cache.items_snapshot()]
        assert len(keys) == 1
        assert keys[0].startswith("delta:1|prec:fast|")
        # the patched fast entry answers a fast re-run, NOT an exact
        info0 = sess.result_cache_info()
        sess.run(mk(), precision="fast")
        assert sess.result_cache_info()["hits"] == info0["hits"] + 1
        sess.run(mk(), precision="exact")
        assert sess.result_cache_info()["misses"] > info0["misses"]

    def test_patch_plan_reuse_steady_state(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        for gen in range(1, 4):
            rows, cols, v = _coo_batch(rng, n, 3)
            np.add.at(a, (rows, cols), v)
            s = sess.register_delta("A", (rows, cols, v), kind="coo")
            assert s["patched"] == 1
            assert s["reused_plans"] == (0 if gen == 1 else 1)
        assert sess._delta_plane.stats["patch_compiles"] == 1
        assert sess._delta_plane.stats["patch_reuses"] == 2
        got = sess.run(sess.table("A").expr().row_sum()).to_numpy()
        np.testing.assert_array_equal(got, a.sum(1, keepdims=True))

    def test_signature_change_recompiles(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", (*_coo_batch(rng, n, 3),), kind="coo")
        # a different-capacity delta has a different signature: the
        # cached patch plan must NOT be rebound onto mismatched shapes
        s = sess.register_delta("A", (*_coo_batch(rng, n, 5),),
                                kind="coo")
        assert s["reused_plans"] == 0 and s["patched"] == 1
        assert sess._delta_plane.stats["patch_compiles"] == 2

    def test_known_propagation_is_tier_namespaced(self, mesh8, rng):
        # review r14: the same structural query cached at "fast" AND
        # "default" — the default entry's patch must never consume the
        # fast-tier sibling's (old, new) pair (bf16 error injected
        # into a bound composed from f32 units). The int query makes
        # the contamination detectable: default must stay BIT-exact.
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))

        def mk():
            return sess.table("A").expr().multiply(
                sess.table("A").expr())

        sess.run(mk(), precision="fast")     # processed first
        sess.run(mk())                       # default tier
        rows, cols, v = _coo_batch(rng, n, 4)
        np.add.at(a, (rows, cols), v)
        s = sess.register_delta("A", (rows, cols, v), kind="coo")
        assert s["patched"] == 2
        got = sess.run(mk()).to_numpy()      # the default entry
        np.testing.assert_array_equal(got, a @ a)
        assert delta_pass.verify_patched_entries(sess) == []

    def test_patch_programs_reconciled_after_kill(self, mesh8, rng):
        # review r14: a plain register() kills the entries but used to
        # leave their PatchPrograms (and the device arrays their plans
        # pin) cached forever; the next register_delta reconciles
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert len(sess._delta_plane._programs) == 1
        sess.register("A", sess.from_numpy(a, integral=True))  # kill
        assert sess.result_cache_info()["entries"] == 0
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", ([3], [4], [1.0]), kind="coo")
        # exactly the live entry's program remains — the orphan is gone
        live = {e.ivm_id for _k, e in
                sess._result_cache.items_snapshot()}
        assert set(sess._delta_plane._programs) == live
        assert len(sess._delta_plane._programs) == 1

    def test_apply_patch_budget_failure_restores_old(self, mesh8,
                                                     rng):
        # review r14: an over-budget patched result must leave the OLD
        # entry in place so the caller's kill counts invalidation and
        # feeds the brownout graveyard — not vanish silently
        import dataclasses
        from matrel_tpu.serve.result_cache import (ResultCache,
                                                   result_nbytes)
        rc_ = ResultCache()
        bm = BlockMatrix.from_numpy(
            rng.standard_normal((32, 32)).astype(np.float32),
            mesh=mesh8)
        from matrel_tpu.serve.result_cache import CacheEntry
        ent = CacheEntry(key_hash="k", result=bm, pins=(),
                         dep_ids=frozenset({1}), layout="2d",
                         dtype="float32", nbytes=result_nbytes(bm))
        assert rc_.put("old", ent, 1 << 20)
        big = dataclasses.replace(ent, nbytes=2 << 20)
        assert not rc_.apply_patch("old", "new", big, 1 << 20)
        assert rc_.lookup("old") is ent          # restored
        assert rc_.patched == 0
        assert rc_.drop("old", keep_stale=True, stale_max=4,
                        stale_max_bytes=1 << 20)
        assert rc_.invalidated == 1
        assert rc_.info()["stale_entries"] == 1  # graveyard fed

    def test_register_delta_unbound_name_raises(self, mesh8):
        sess = _sess(mesh8, **RC)
        with pytest.raises(KeyError, match="not a bound"):
            sess.register_delta("nope", ([0], [0], [1.0]), kind="coo")

    def test_plain_register_still_invalidates(self, mesh8, rng):
        # register() keeps its historical semantics even after deltas
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert sess.result_cache_info()["entries"] == 1
        sess.register("A", sess.from_numpy(a, integral=True))
        assert sess.result_cache_info()["entries"] == 0


def test_streaming_dashboard_patched_equals_recomputed(mesh8):
    """The sliding-window graph dashboard (workloads/streaming.py) over
    one seeded edge stream, maintained both ways: ``step_delta``
    patches the cached entries in place, ``step_rebind`` kills and
    recomputes. After every tick both dashboards agree with each other
    and with the numpy oracle — the integer queries bit for bit — and
    the steady-state ticks reuse their compiled patch plans."""
    from matrel_tpu.workloads.streaming import StreamingGraph

    def dashboard():
        return StreamingGraph(_sess(mesh8, **RC), n=256, batch_edges=8,
                              window=6, feature_k=16, seed=0)

    patched, recomputed = dashboard(), dashboard()
    patched.run_all()
    recomputed.run_all()
    for tick in range(3):
        summary = patched.step_delta()
        recomputed.step_rebind()
        got, ref = patched.run_all(), recomputed.run_all()
        want = patched.oracle()
        for name, v in got.items():
            w = np.asarray(want[name], np.float32).reshape(v.shape)
            if name == "feature_product":
                np.testing.assert_allclose(v, w, rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(v, ref[name], rtol=1e-4,
                                           atol=1e-4)
            else:
                np.testing.assert_array_equal(v, w)
                np.testing.assert_array_equal(v, ref[name])
        assert summary["patched"] > 0
        assert (summary["reused_plans"] > 0) == (tick > 0)
    assert delta_pass.verify_patched_entries(patched.sess) == []


# ---------------------------------------------------------------------------
# MV113 — both halves, both directions
# ---------------------------------------------------------------------------


class TestMV113:
    def _patched_sess(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        rows, cols, v = _coo_batch(rng, n, 3)
        np.add.at(a, (rows, cols), v)
        sess.register_delta("A", (rows, cols, v), kind="coo")
        return sess

    def test_dynamic_clean_after_patch(self, mesh8, rng):
        sess = self._patched_sess(mesh8, rng)
        assert delta_pass.verify_patched_entries(sess) == []

    def test_dynamic_flags_corrupted_result(self, mesh8, rng):
        import dataclasses
        sess = self._patched_sess(mesh8, rng)
        key, ent = sess._result_cache.items_snapshot()[0]
        bad = BlockMatrix.from_numpy(
            ent.result.to_numpy() + 1.0, mesh=mesh8)
        # corrupt THROUGH the seam (a new entry object) — the dynamic
        # check must catch a wrong value whatever wrote it
        sess._result_cache.apply_patch(
            key, key, dataclasses.replace(ent, result=bad),
            RC["result_cache_max_bytes"])
        diags = delta_pass.verify_patched_entries(sess)
        assert len(diags) == 1 and diags[0].code == "MV113"
        assert "diverges" in diags[0].message

    def test_static_quiet_on_fresh_substitution(self, mesh8, rng):
        sess = self._patched_sess(mesh8, rng)
        # consume the patched entry as an interior leaf: the stamped
        # plan must verify MV113-quiet
        q = sess.table("A").expr().row_sum().multiply_scalar(2.0)
        _ent, _key, _pins, sub = sess._rc_admit(
            q, sess._rc_key_prefix("default"))
        from matrel_tpu.ir import rules
        from matrel_tpu.parallel import planner
        opt = planner.annotate_strategies(
            rules.optimize(sub, sess.config, mesh=sess.mesh),
            sess.mesh, sess.config)
        diags = [d for d in verify_plan(opt, sess.mesh, sess.config)
                 if d.code == "MV113"]
        assert diags == []
        # and the substituted leaf really carries the provenance
        stamps = []

        def walk(n):
            rc = n.attrs.get("result_cache")
            if rc and rc.get("delta"):
                stamps.append(rc["delta"])
            for c in n.children:
                walk(c)

        walk(sub)
        assert stamps and stamps[0]["gen"] == 1
        assert stamps[0]["rule"] in delta_lib.DELTA_RULES

    @pytest.mark.parametrize("tamper,needle", [
        ({"gen": 0, "rule": "rank_k", "err_bound": 0.0},
         "generation"),
        ({"gen": 1, "rule": "made_up", "err_bound": 0.0},
         "vocabulary"),
        ({"gen": 1, "rule": "rank_k", "err_bound": -1.0},
         "err_bound"),
        ("not-a-dict", "unreadable"),
    ])
    def test_static_flags_tampered_stamp(self, mesh8, rng, tamper,
                                         needle):
        from matrel_tpu.ir import expr as E
        bm = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        leaf = E.leaf(bm).with_attrs(result_cache={
            "key_hash": "x", "layout": "2d", "dtype": "float32",
            "deps": [], "delta": tamper})
        diags = [d for d in verify_plan(
            leaf.multiply_scalar(2.0), mesh8, MatrelConfig())
            if d.code == "MV113"]
        assert diags, "tampered stamp not flagged"
        assert any(needle in d.message for d in diags), diags


# ---------------------------------------------------------------------------
# Obs surfaces
# ---------------------------------------------------------------------------


class TestObsSurfaces:
    def test_delta_event_and_history_rollup(self, mesh8, rng,
                                            tmp_path):
        log = str(tmp_path / "events.jsonl")
        sess = _sess(mesh8, obs_level="on", obs_event_log=log, **RC)
        n = 64
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        events = [json.loads(l) for l in open(log)]
        dv = [e for e in events if e["kind"] == "delta"]
        assert len(dv) == 1
        rec = dv[0]
        assert rec["name"] == "A" and rec["gen"] == 1
        assert rec["patched"] == 1 and rec["delta_kind"] == "coo"
        assert "est_saved_flops" in rec and "rules" in rec
        assert rec["result_cache"]["patched"] == 1
        from matrel_tpu.obs import history
        s = history.summarize(events)
        assert s["ivm"]["registers"] == 1
        assert s["ivm"]["patched"] == 1
        text = history.render_summary(events)
        assert "ivm: 1 delta(s)" in text

    def test_no_delta_events_on_default_obs_off(self, mesh8, rng,
                                                tmp_path):
        log = str(tmp_path / "events.jsonl")
        os.environ.pop("MATREL_OBS_EVENT_LOG", None)
        sess = _sess(mesh8, obs_event_log=log, **RC)
        n = 32
        sess.register("A", sess.from_numpy(_int_adj(rng, n),
                                           integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        assert not os.path.exists(log)

    def test_matmul_decisions_carry_delta_pricing(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        n = 96
        a = _int_adj(rng, n)
        f = rng.standard_normal((n, 16)).astype(np.float32)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.register("F", sess.from_numpy(f))
        sess.run(sess.table("A").expr().multiply(
            sess.table("F").expr()))
        sess.register_delta("A", ([1], [2], [1.0]), kind="coo")
        _key, ent = sess._result_cache.items_snapshot()[0]
        prog = sess._delta_plane._programs[ent.ivm_id]
        decs = executor_lib.plan_matmul_decisions(prog.plan)
        assert decs, "patch plan has no matmul decisions"
        for d in decs:
            assert d["delta_rule"] in delta_lib.DELTA_RULES
            assert isinstance(d["delta_est_saved_flops"],
                              (int, float))
        assert prog.plan.meta["ivm"]["est_saved_flops"] > 0

    def test_history_drift_check_exit_code(self, tmp_path,
                                           monkeypatch):
        # the --check gate: rc 0 with no flags, rc 1 when a seeded
        # rank-order flag fires (obs/drift.py audit())
        import argparse
        from matrel_tpu.obs import drift, history
        events = []
        args = argparse.Namespace(
            log=None, summary=False, last=None, drift=True,
            drift_table=str(tmp_path / "d.json"), no_save=False,
            check=True)
        monkeypatch.setattr(
            "matrel_tpu.obs.events.read_events",
            lambda path: events)
        monkeypatch.setenv("MATREL_OBS_EVENT_LOG",
                           str(tmp_path / "e.jsonl"))
        text, flags = drift.audit(events, persist=False)
        assert flags == []
        assert history.main(args) == 0
        monkeypatch.setattr(drift, "audit",
                            lambda *a, **k: ("boom", [{"class": "x"}]))
        assert history.main(args) == 1


# ---------------------------------------------------------------------------
# Default-config bit-identity
# ---------------------------------------------------------------------------


class TestBitIdentity:
    def test_no_delta_objects_without_register_delta(self, mesh8, rng,
                                                     monkeypatch):
        # poisoned init: rc-on traffic + rebinds must construct ZERO
        # delta-plane objects and produce no delta: prefixes
        def boom(self, *a, **k):
            raise AssertionError("MatrixDelta constructed on the "
                                 "default path")

        monkeypatch.setattr(delta_lib.MatrixDelta, "__post_init__",
                            boom)
        sess = _sess(mesh8, **RC)
        n = 48
        a = _int_adj(rng, n)
        sess.register("A", sess.from_numpy(a, integral=True))
        sess.run(sess.table("A").expr().row_sum())
        sess.run(sess.table("A").expr().row_sum())
        sess.register("A", sess.from_numpy(a, integral=True))  # rebind
        sess.run(sess.table("A").expr().row_sum())
        assert sess._delta_plane is None and sess._delta_gen == 0
        for k, ent in sess._result_cache.items_snapshot():
            assert not k.startswith("delta:")
            assert ent.delta_gen == 0 and ent.ivm_id is None

    def test_construction_counter_quiet_on_serve_traffic(self, mesh8,
                                                         rng):
        before = delta_lib._CONSTRUCTED["count"]
        sess = _sess(mesh8, **RC)
        X = BlockMatrix.from_numpy(
            rng.standard_normal((32, 8)).astype(np.float32),
            mesh=mesh8)
        outs = sess.run_many([X.expr().t().multiply(X.expr())
                              for _ in range(3)])
        assert len(outs) == 3
        assert delta_lib._CONSTRUCTED["count"] == before

    def test_config_validation(self):
        with pytest.raises(ValueError, match="delta_patch_mode"):
            MatrelConfig(delta_patch_mode="sometimes")
        with pytest.raises(ValueError, match="delta_rank_max"):
            MatrelConfig(delta_rank_max=0)
        assert MatrelConfig(delta_patch_mode="FORCE") \
            .delta_patch_mode == "force"
