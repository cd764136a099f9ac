"""Static plan verifier (matrel_tpu/analysis/): one seeded-violation
fixture per pass proving the exact diagnostic code fires, the clean-
plan contract at verify_plans="error", the HBM-hardened admissible()
routing (VERDICT r5 Weak #3 / Next #6), and the session/executor/obs
wiring."""

import types

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from matrel_tpu import analysis
from matrel_tpu.analysis import padding_pass
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.sparse import BlockSparseMatrix
from matrel_tpu.ir import expr as E, rules
from matrel_tpu.parallel import planner


def _annotated(e, mesh, cfg=None):
    cfg = cfg or MatrelConfig()
    grid = (mesh.shape[mesh.axis_names[0]], mesh.shape[mesh.axis_names[1]])
    return planner.annotate_strategies(
        rules.optimize(e, cfg, grid=grid, mesh=mesh), mesh, cfg)


def _codes(diags):
    return sorted({d.code for d in diags})


def _dense(rng, n, m, mesh, spec=None):
    return BlockMatrix.from_numpy(
        rng.standard_normal((n, m)).astype(np.float32), mesh=mesh,
        spec=spec)


#: Planner-level stand-in for a matrix too large to materialise: the
#: planner/verifier only read shape/nnz/spec/dtype off a leaf.
def _phantom_leaf(shape, spec, dtype="float32"):
    m = types.SimpleNamespace(shape=shape, nnz=None, spec=spec,
                              dtype=np.dtype(dtype))
    return E.leaf(m)


class TestCleanPlans:
    """A healthy representative plan set produces ZERO diagnostics at
    verify_plans='error' — the all-clear half of the acceptance
    criteria (the corpus-scale version lives in tools/plan_verify.py,
    run by `make lint`)."""

    def test_dense_pipeline_clean(self, rng, mesh8):
        X = _dense(rng, 256, 64, mesh8)
        y = _dense(rng, 256, 1, mesh8)
        e = X.expr().t().multiply(X.expr()).solve(
            X.expr().t().multiply(y.expr()))
        diags = analysis.verify_plan(_annotated(e, mesh8), mesh8)
        assert diags == []

    def test_spgemm_and_masking_ops_clean(self, rng, mesh8):
        S1 = BlockSparseMatrix.random((256, 256), block_density=0.05,
                                      block_size=64, mesh=mesh8, seed=0)
        S2 = BlockSparseMatrix.random((256, 256), block_density=0.05,
                                      block_size=64, mesh=mesh8, seed=1)
        e = S1.multiply(S2).add_scalar(1.0).power(-1.0).row_sum()
        diags = analysis.verify_plan(_annotated(e, mesh8), mesh8)
        assert diags == []

    def test_compile_under_error_mode(self, rng, mesh8):
        from matrel_tpu import executor
        cfg = MatrelConfig(verify_plans="error")
        A = _dense(rng, 64, 32, mesh8)
        B = _dense(rng, 32, 48, mesh8)
        plan = executor.compile_expr(A.expr().multiply(B.expr()), mesh8,
                                     cfg)
        assert plan.meta["diagnostics"] == []
        got = plan.run().to_numpy()
        np.testing.assert_allclose(got, A.to_numpy() @ B.to_numpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_off_mode_pays_nothing(self, rng, mesh8):
        from matrel_tpu import executor
        A = _dense(rng, 64, 32, mesh8)
        plan = executor.compile_expr(A.expr().t().multiply(A.expr()),
                                     mesh8, MatrelConfig())
        assert "diagnostics" not in plan.meta


class TestStrategyPass:
    def test_mv101_inadmissible_stamp(self, rng, mesh8):
        # summa needs a square grid; mesh8 is (2, 4) — a summa stamp
        # can only come from a plan annotated for a different mesh
        A = _dense(rng, 64, 64, mesh8)
        B = _dense(rng, 64, 64, mesh8)
        bad = E.matmul(A.expr(), B.expr()).with_attrs(
            strategy="summa", strategy_source="model")
        diags = analysis.verify_plan(bad, mesh8)
        assert _codes(diags) == ["MV101"]
        assert diags[0].severity == "error"

    def test_mv101_unknown_strategy(self, rng, mesh8):
        A = _dense(rng, 64, 64, mesh8)
        bad = E.matmul(A.expr(), A.expr()).with_attrs(strategy="zmm")
        diags = analysis.verify_plan(bad, mesh8)
        assert _codes(diags) == ["MV101"]
        assert "vocabulary" in diags[0].message


class TestSpgemmPass:
    def _pair(self, mesh):
        S1 = BlockSparseMatrix.random((256, 256), block_density=0.02,
                                      block_size=64, mesh=mesh, seed=2)
        S2 = BlockSparseMatrix.random((256, 256), block_density=0.02,
                                      block_size=64, mesh=mesh, seed=3)
        return S1, S2

    def test_mv104_stale_stamp_config_drift(self, mesh8):
        # annotated with SpGEMM on, verified under a config that
        # disables the dispatch (threshold 0 = the documented kill
        # switch): the stamp now promises a path that will not run
        S1, S2 = self._pair(mesh8)
        opt = _annotated(S1.multiply(S2), mesh8, MatrelConfig())
        assert opt.attrs["strategy"] == "spgemm"
        diags = analysis.verify_plan(
            opt, mesh8, MatrelConfig(spgemm_density_threshold=0.0))
        assert "MV104" in _codes(diags)

    def test_mv104_unstamped_dispatch(self, mesh8):
        S1, S2 = self._pair(mesh8)
        bad = S1.multiply(S2).with_attrs(strategy="rmm",
                                         strategy_source="model")
        diags = analysis.verify_plan(bad, mesh8)
        assert "MV104" in _codes(diags)
        assert "misreport" in [d for d in diags
                               if d.code == "MV104"][0].message


class TestLayoutPass:
    def test_mv102_unearned_credit(self, rng, mesh8, monkeypatch):
        # simulate the ADVICE r5 bug class: infer_layout hands a
        # sparse_leaf matmul the stamped strategy's layout although the
        # SpMM lowering ignores the stamp — the verifier must catch the
        # two modules disagreeing
        S = BlockSparseMatrix.random((256, 256), block_density=0.05,
                                     block_size=64, mesh=mesh8, seed=4)
        D = _dense(rng, 256, 128, mesh8)
        opt = _annotated(S.multiply(D), mesh8)
        real = planner.infer_layout

        def unearned(node, mesh, memo=None, config=None):
            if node.kind == "matmul":
                return "row"          # the pre-fix free-consume claim
            return real(node, mesh, memo, config)

        monkeypatch.setattr(planner, "infer_layout", unearned)
        diags = analysis.verify_plan(opt, mesh8)
        mv102 = [d for d in diags if d.code == "MV102"]
        assert mv102 and mv102[0].severity == "warning"

    def test_mixed_coo_sparse_takes_coo_path(self, rng, mesh8):
        """Review r6: a mixed coo×sparse matmul above the SpGEMM
        threshold runs the COO SpMV path (Lowerer._matmul checks
        coo_leaf before sparse_leaf) — infer_layout, matmul_decisions
        and both verifier mirrors must all read that branch order, so
        the compact path's replicated-output credit is claimed exactly
        where it is pinned and MV102 stays quiet."""
        from matrel_tpu.analysis import layout_pass
        from matrel_tpu.core.coo import COOMatrix
        # dense-ish operands: estimated output block density ~1.0 keeps
        # the SpGEMM dispatch out of the way
        n_edges = 40_000
        A = COOMatrix.from_edges(rng.integers(0, 256, n_edges),
                                 rng.integers(0, 256, n_edges),
                                 shape=(256, 256))
        S = BlockSparseMatrix.random((256, 64), block_density=1.0,
                                     block_size=64, mesh=mesh8, seed=6)
        cfg = MatrelConfig(pallas_interpret=True)  # compact path pinned
        opt = _annotated(A.multiply(S.expr()), mesh8, cfg)
        decs = planner.matmul_decisions(opt, mesh8, cfg)
        assert [d["dispatch"] for d in decs] == ["coo_spmv"]
        assert planner.infer_layout(opt, mesh8, {}, cfg) == "rep"
        assert layout_pass.pinned_matmul_layout(opt, mesh8, cfg) == "rep"
        assert [d for d in analysis.verify_plan(opt, mesh8, cfg)
                if d.code == "MV102"] == []

    def test_clean_claims_match_pins(self, rng, mesh8):
        # the real infer_layout and the executor mirror agree across a
        # mixed plan (dense strategies + SpMM + SpGEMM dispatches)
        S = BlockSparseMatrix.random((256, 256), block_density=0.05,
                                     block_size=64, mesh=mesh8, seed=5)
        D = _dense(rng, 256, 256, mesh8)
        e = S.multiply(D).multiply(_dense(rng, 256, 64, mesh8).expr())
        assert [d for d in analysis.verify_plan(_annotated(e, mesh8),
                                                mesh8)
                if d.code == "MV102"] == []


class TestPaddingPass:
    def test_mv103_missing_remask_seeded(self, rng, mesh8):
        # simulate an executor that forgot _mask_to_logical on
        # scalar-add: the contract entry flips to BREAKS and the
        # checker must flag the node
        A = _dense(rng, 60, 60, mesh8)   # 60 pads to 64: real padding
        e = _annotated(A.expr().add_scalar(1.0), mesh8)
        broken = dict(padding_pass.PADDING_CONTRACT,
                      scalar=lambda n: padding_pass.BREAKS)
        diags = list(padding_pass.check_padding_flow(
            e, mesh8, MatrelConfig(), contract=broken))
        assert _codes(diags) == ["MV103"]
        assert diags[0].severity == "error"
        assert "scalar" in diags[0].message

    def test_mv103_unknown_kind_warns(self, rng, mesh8):
        A = _dense(rng, 32, 32, mesh8)
        e = _annotated(A.expr().row_sum(), mesh8)
        partial = {k: v for k, v in
                   padding_pass.PADDING_CONTRACT.items() if k != "agg"}
        diags = list(padding_pass.check_padding_flow(
            e, mesh8, MatrelConfig(), contract=partial))
        assert _codes(diags) == ["MV103"]
        assert diags[0].severity == "warning"
        assert "no entry" in diags[0].message

    def test_real_contract_clean_on_breakers(self, rng, mesh8):
        # every invariant-breaking op the executor re-masks verifies
        # clean under the REAL contract
        A = _dense(rng, 60, 60, mesh8)
        B = _dense(rng, 1, 60, mesh8)
        e = _annotated(A.expr().add(B.expr()).add_scalar(2.0)
                       .power(-1.0), mesh8)
        assert list(padding_pass.check_padding_flow(
            e, mesh8, MatrelConfig())) == []


class TestHBMFeasibility:
    """The acceptance criterion: a plan that over-replicates under RMM
    on a 16 GB HBM budget is rejected by admissible(), flagged by the
    verifier, and routed to cpmm."""

    # A replicated (4096 x 2M) f32, B canonically 2D (2M x 4096): with
    # A's gather free, RMM wins the byte model — but needs a/gx + b/gy
    # = 16 + 8 = ~24 GiB per device on the (2, 4) grid, while CPMM's
    # outer-product working set is ~12 GiB.
    N, K, M = 4096, 1 << 21, 4096

    def _matmul(self, mesh, k=None, a_spec=P(None, None)):
        axes = tuple(mesh.axis_names)
        k = k or self.K
        A = _phantom_leaf((self.N, k), a_spec)
        B = _phantom_leaf((k, self.M), P(axes[0], axes[1]))
        return E.matmul(A, B)

    def test_hbm_bytes_closed_forms(self):
        gib = 2.0 ** 30
        rmm = planner.strategy_hbm_bytes("rmm", self.N, self.K, self.M,
                                         2, 4)
        cpmm = planner.strategy_hbm_bytes("cpmm", self.N, self.K,
                                          self.M, 2, 4)
        # a = b = 32 GiB, c = 64 MiB: rmm = a/2 + b/4 + c/8,
        # cpmm = a/8 + b/4 + c/2
        assert rmm == pytest.approx(24.008 * gib, rel=0.001)
        assert cpmm == pytest.approx(12.031 * gib, rel=0.001)
        # xla is estimated like the one-panel rmm (both gathered
        # panels), no longer at 0 (PR 27)
        assert planner.strategy_hbm_bytes("xla", self.N, self.K,
                                          self.M, 2, 4) == rmm
        # panels divide the replication: (4, 8) panels of rmm
        assert planner.strategy_hbm_bytes(
            "rmm", self.N, self.K, self.M, 2, 4, panels=(4, 8)
        ) == pytest.approx((16 / 4 + 8 / 8 + 0.008) * gib, rel=0.001)

    def test_admissible_gate(self):
        kw = dict(hbm_budget_bytes=16 << 30)
        assert not planner.admissible("rmm", self.N, self.K, self.M,
                                      2, 4, **kw)
        assert planner.admissible("cpmm", self.N, self.K, self.M,
                                  2, 4, **kw)
        # xla is gated like the others: exempt, it was handed the plans
        # that could not be allocated (PR 27)
        assert not planner.admissible("xla", self.N, self.K, self.M,
                                      2, 4, **kw)
        # budget 0 = the pre-round-6 divisibility-only behaviour
        assert planner.admissible("rmm", self.N, self.K, self.M, 2, 4,
                                  hbm_budget_bytes=0)
        assert planner.admissible("xla", self.N, self.K, self.M, 2, 4,
                                  hbm_budget_bytes=0)

    def test_planner_routes_by_the_plans_peak(self, mesh8):
        """The gate reckons the plan, operands as they lie included
        (PR 27): both operands 2D, 1 GiB a device each. With room for
        it cpmm is the byte model's pick; where its re-laid B and its
        partial no longer fit, the panelled rmm takes as many panels as
        the budget asks — routed, not refused."""
        axes = tuple(mesh8.axis_names)
        node = self._matmul(mesh8, k=1 << 18, a_spec=P(axes[0], axes[1]))
        free = MatrelConfig(hbm_budget_bytes=0)
        assert planner.choose_strategy_ex(node, mesh8, free) \
            == ("cpmm", "model")
        roomy, d4 = MatrelConfig(hbm_budget_bytes=4 << 30), {}
        assert planner.choose_strategy_ex(node, mesh8, roomy,
                                          hbm_detail=d4) \
            == ("cpmm", "model")
        assert d4["refused_hbm"] == [] \
            and d4["hbm_plan_bytes"] <= 4 << 30
        tight, d2 = MatrelConfig(hbm_budget_bytes=2 << 30), {}
        assert planner.choose_strategy_ex(node, mesh8, tight,
                                          hbm_detail=d2) \
            == ("rmm", "model")
        assert "cpmm" in d2["refused_hbm"]
        assert d2["panels"][0] > 1 and d2["hbm_plan_bytes"] <= 2 << 30
        # an operand that cannot exist on the chip (A replicated:
        # 32 GiB a device): nothing fits, the least is handed over,
        # and the plan says that everything was refused
        none, dn = MatrelConfig(), {}
        s, src = planner.choose_strategy_ex(self._matmul(mesh8), mesh8,
                                            none, hbm_detail=dn)
        assert src == "default" and s in dn["refused_hbm"]
        assert dn["hbm_plan_bytes"] > none.hbm_budget_bytes

    def test_mv105_flags_overbudget_stamp(self, mesh8):
        bad = self._matmul(mesh8).with_attrs(strategy="rmm",
                                             strategy_source="model")
        diags = analysis.verify_plan(bad, mesh8, MatrelConfig())
        mv105 = [d for d in diags if d.code == "MV105"]
        assert mv105 and mv105[0].severity == "error"
        assert "GiB per device" in mv105[0].message
        # budget 0 disables the pass
        assert [d for d in analysis.verify_plan(
            bad, mesh8, MatrelConfig(hbm_budget_bytes=0))
            if d.code == "MV105"] == []


class TestResultCachePass:
    """MV107: a plan consuming a materialized-result-cache entry must
    agree with what the cache recorded at substitution (serve/)."""

    def test_mv107_stale_layout_and_dtype_stamp(self, rng, mesh8):
        B = _dense(rng, 32, 32, mesh8)
        cached = _dense(rng, 32, 32, mesh8)
        # a stamp surviving past invalidation: claims a replicated f64
        # result while the leaf really lies canonically-sharded f32
        stale = E.leaf(cached).with_attrs(result_cache={
            "key_hash": "deadbeef", "layout": "rep",
            "dtype": "float64", "deps": []})
        diags = analysis.verify_plan(
            _annotated(stale.multiply(B.expr()), mesh8), mesh8)
        mv107 = [d for d in diags if d.code == "MV107"]
        assert len(mv107) == 2          # one layout, one dtype finding
        assert all(d.severity == "warning" for d in mv107)
        assert any("layout" in d.message for d in mv107)
        assert any("dtype" in d.message for d in mv107)

    def test_mv107_quiet_on_live_substitution(self, rng, mesh8):
        # the session's own substitution stamps truthfully — clean
        from matrel_tpu.session import MatrelSession
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig(
            result_cache_max_bytes=64 << 20))
        X = _dense(rng, 64, 16, mesh8)
        gram = X.expr().t().multiply(X.expr())
        sess.run(gram)
        B = _dense(rng, 16, 16, mesh8)
        substituted = sess._rc_substitute(gram.multiply(B.expr()))
        assert any(c.attrs.get("result_cache")
                   for c in substituted.children)
        diags = analysis.verify_plan(_annotated(substituted, mesh8),
                                     mesh8)
        assert [d for d in diags if d.code == "MV107"] == []

    def test_mv107_unstamped_leaves_ignored(self, rng, mesh8):
        e = _dense(rng, 32, 32, mesh8).expr().t()
        diags = analysis.verify_plan(_annotated(e, mesh8), mesh8)
        assert [d for d in diags if d.code == "MV107"] == []


class TestWiring:
    # strategy_override bypasses BOTH the cost model and the
    # admissibility gate (choose_strategy_ex returns it first), so a
    # bad override is the realistic way an inadmissible stamp reaches
    # the compile path — and the verifier is the layer that catches it.

    def test_compile_error_mode_raises_pre_trace(self, rng, mesh8):
        from matrel_tpu import executor
        A = _dense(rng, 64, 64, mesh8)
        e = E.matmul(A.expr(), A.expr())   # summa needs a square grid
        with pytest.raises(analysis.VerificationError) as ei:
            executor.compile_expr(e, mesh8, MatrelConfig(
                strategy_override="summa", verify_plans="error"))
        assert "MV101" in str(ei.value)

    def test_compile_warn_mode_records_and_runs(self, rng, mesh8):
        from matrel_tpu import executor
        A = _dense(rng, 64, 64, mesh8)
        plan = executor.compile_expr(
            E.matmul(A.expr(), A.expr()), mesh8,
            MatrelConfig(strategy_override="summa", verify_plans="warn"))
        assert [d["code"] for d in plan.meta["diagnostics"]] == ["MV101"]
        # summa's impl falls back to cpmm off square grids: still runs
        got = plan.run().to_numpy()
        a = A.to_numpy()
        np.testing.assert_allclose(got, a @ a, rtol=1e-4, atol=1e-4)

    def test_session_verify_and_explain(self, rng, mesh8):
        from matrel_tpu import session as sess_mod
        sess = sess_mod.MatrelSession(mesh8, MatrelConfig())
        A = _dense(rng, 64, 32, mesh8)
        e = A.expr().t().multiply(A.expr())
        assert sess.verify(e) == []
        txt = sess.explain(e)
        assert "== Verifier ==" in txt
        assert "clean (0 diagnostics)" in txt

    def test_obs_verify_event(self, rng, mesh8, tmp_path):
        import json
        from matrel_tpu import session as sess_mod
        log = str(tmp_path / "ev.jsonl")
        sess = sess_mod.MatrelSession(mesh8, MatrelConfig(
            obs_level="on", obs_event_log=log, verify_plans="warn"))
        A = _dense(rng, 64, 32, mesh8)
        sess.compute(A.expr().t().multiply(A.expr()))
        kinds = [json.loads(l)["kind"] for l in open(log)]
        assert kinds.count("verify") == 1
        rec = [json.loads(l) for l in open(log)
               if json.loads(l)["kind"] == "verify"][0]
        assert rec["mode"] == "warn"
        assert rec["count"] == 0 and rec["codes"] == []

    def test_config_validates_verify_plans(self):
        with pytest.raises(ValueError, match="verify_plans"):
            MatrelConfig(verify_plans="eror")
        assert MatrelConfig(verify_plans="WARN").verify_plans == "warn"


def test_plan_verify_selfcheck_green():
    """`make lint`'s second half, enforced from inside tier-1: every
    plan in the snapshot corpus (tools/plan_snapshot.py) verifies with
    zero diagnostics."""
    from tools import plan_verify
    assert plan_verify.main() == 0


class TestTopologyPass:
    """MV106 (round 7): the slow-axis collective smell on a weighted
    mesh — fires on hand-stamped plans, never on the planner's own
    output, and costs nothing on a homogeneous mesh."""

    W_CFG = None  # built per-test (fixtures need mesh8)

    def _wcfg(self):
        return MatrelConfig(axis_cost_weights=(1.0, 8.0))

    def _stamped_slow(self, mesh):
        # replicated B makes the broadcast alternative FREE, so the
        # hand-stamped rmm (whose A all-gather rides y, the slow axis)
        # is a gy-fold weighted-bytes regression; the node sits under
        # an outer matmul so no root-reshard context muddies the gap
        import dataclasses
        base = BlockMatrix.from_numpy(np.zeros((8, 8), np.float32),
                                      mesh=mesh)
        brep = BlockMatrix.from_numpy(np.zeros((8, 8), np.float32),
                                      mesh=mesh, spec=P(None, None))

        def fab(src, n, m):
            return E.leaf(dataclasses.replace(src, shape=(n, m)))

        inner = E.matmul(fab(base, 8192, 2048),
                         fab(brep, 2048, 4096)).with_attrs(
            strategy="rmm", strategy_source="override")
        return E.matmul(inner, fab(base, 4096, 64))

    def test_mv106_fires_on_hand_stamped_slow_axis_plan(self, mesh8):
        cfg = self._wcfg()
        ann = planner.annotate_strategies(self._stamped_slow(mesh8),
                                          mesh8, cfg)
        diags = analysis.verify_plan(ann, mesh8, cfg)
        mv106 = [d for d in diags if d.code == "MV106"]
        assert mv106 and all(d.severity == "warning" for d in mv106)
        assert "bmm_right" in mv106[0].message

    def test_mv106_quiet_on_planner_output(self, rng, mesh8):
        # the planner minimises the same weighted bill — a fresh
        # annotation can never be >=2x off its own argmin
        cfg = self._wcfg()
        X = _dense(rng, 256, 64, mesh8)
        e = X.expr().t().multiply(X.expr()).multiply(
            _dense(rng, 64, 32, mesh8).expr())
        diags = analysis.verify_plan(_annotated(e, mesh8, cfg), mesh8,
                                     cfg)
        assert "MV106" not in _codes(diags)

    def test_mv106_free_on_uniform_mesh(self, mesh8):
        # the same hand-stamped plan on a homogeneous mesh: no slow
        # axis exists, the pass yields nothing (rmm vs free-broadcast
        # bmm is a plain cost miss, not a topology smell)
        cfg = MatrelConfig()
        ann = planner.annotate_strategies(self._stamped_slow(mesh8),
                                          mesh8, cfg)
        diags = analysis.verify_plan(ann, mesh8, cfg)
        assert "MV106" not in _codes(diags)

    def test_mv106_respects_root_exposure(self, mesh8):
        # the pass mirrors the planner's root context: a bmm
        # alternative AT the plan root pays the canonical-output
        # re-lay the executor really performs there
        # (_root_reshard_cost x exposure). The SAME stamped multiply
        # is flagged as an interior node (exposure 0 — bmm_right is
        # 4x cheaper) but NOT at the root, where the big output's
        # y-axis re-lay collapses the alternative's margin below 2x —
        # context-free pricing would false-positive every root plan.
        import dataclasses
        cfg = self._wcfg()
        base = BlockMatrix.from_numpy(np.zeros((8, 8), np.float32),
                                      mesh=mesh8)
        brep = BlockMatrix.from_numpy(np.zeros((8, 8), np.float32),
                                      mesh=mesh8, spec=P(None, None))
        stamped = E.matmul(
            E.leaf(dataclasses.replace(base, shape=(8192, 2048))),
            E.leaf(dataclasses.replace(brep, shape=(2048, 4096)))
        ).with_attrs(strategy="rmm", strategy_source="override")
        at_root = analysis.verify_plan(stamped, mesh8, cfg)
        assert "MV106" not in _codes(at_root)
        interior = E.matmul(stamped, E.leaf(dataclasses.replace(
            base, shape=(4096, 64))))
        diags = analysis.verify_plan(
            planner.annotate_strategies(interior, mesh8, cfg), mesh8,
            cfg)
        assert "MV106" in _codes(diags)

    def test_mv106_exempts_measured_stamps(self, mesh8, tmp_path):
        # an autotune wall-clock winner legitimately overrules the
        # byte model (that is the point of measuring) — flagging it
        # would warn on every fresh annotation of an autotune-enabled
        # weighted session (review r7)
        import json
        from matrel_tpu.parallel import autotune
        cfg = self._wcfg().replace(
            autotune=True,
            autotune_table_path=str(tmp_path / "t.json"))
        key = autotune._table_key(2048, 2, 4, "float32", (1.0, 8.0))
        json.dump({key: {"best": "rmm", "times": {"rmm": 1e-6,
                                                  "cpmm": 1.0}}},
                  open(str(tmp_path / "t.json"), "w"))
        autotune._CACHE.clear()
        rng = np.random.default_rng(3)
        a = _dense(rng, 2048, 2048, mesh8)
        b = _dense(rng, 2048, 2048, mesh8)
        inner = E.matmul(a.expr(), b.expr())
        outer = E.matmul(inner, _dense(rng, 2048, 64, mesh8).expr())
        ann = planner.annotate_strategies(outer, mesh8, cfg)
        autotune._CACHE.clear()
        assert ann.children[0].attrs["strategy_source"] == "measured"
        diags = analysis.verify_plan(ann, mesh8, cfg)
        assert "MV106" not in _codes(diags)
