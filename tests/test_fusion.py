"""Whole-plan program fusion (ir/fusion.py; docs/FUSION.md).

Covers the round-12 acceptance surface: region grammar, the off-state
bit-identity contract (zero FusedRegion constructions — poisoned-init),
fused-vs-staged numerical agreement across dense/SpGEMM/COO producers
and precision tiers, the epilogue slots (strategies / spmm / spgemm →
kernel-registry hook), MV111 in both directions, the unit-program seam
dispatch counts, the autotune ``fuse|`` key family, the degradation
rung interaction, and the obs surfaces (decision fields, drift keying,
history roll-up, analyze attribution).
"""

import numpy as np
import pytest

from matrel_tpu import analysis, executor as executor_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import fusion as fusion_lib
from matrel_tpu.ir.rules import optimize
from matrel_tpu.parallel import planner


@pytest.fixture(scope="module")
def mesh8():
    return mesh_lib.make_mesh()


CFG_OFF = MatrelConfig(obs_level="off")
CFG_ON = CFG_OFF.replace(fusion_enable=True)


def _chain(mesh, n=32, k=16, seed=0):
    """(expr, float64 oracle): (XᵀX)·(1/n) + λI, then row-mean."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, k)).astype(np.float32)
    eye = np.eye(k, dtype=np.float32)
    X = BlockMatrix.from_numpy(x, mesh=mesh)
    I = BlockMatrix.from_numpy(eye, mesh=mesh)
    e = X.expr().t().multiply(X.expr()).multiply_scalar(1.0 / n) \
        .add(I.expr().multiply_scalar(0.1)) \
        .row_sum().multiply_scalar(1.0 / k)
    ref = ((x.astype(np.float64).T @ x.astype(np.float64)) / n
           + 0.1 * np.eye(k)).sum(axis=1, keepdims=True) / k
    return e, ref


def _pagerank_step(mesh, n=256, seed=0, alpha=0.85):
    """(expr, float64 oracle): one PageRank round as ONE expression,
    r' = α·(Aᵀ·(w∘r) + Σ(d∘r)/n) + (1-α)/n — prologue below the
    matvec anchor, epilogue above it."""
    rng = np.random.default_rng(seed)
    a = rng.random((n, n), dtype=np.float32)
    r, w = (rng.random((n, 1), dtype=np.float32) for _ in range(2))
    d = (rng.random((n, 1)) < 0.05).astype(np.float32)
    A, R, W, D = (BlockMatrix.from_numpy(x, mesh=mesh)
                  for x in (a, r, w, d))
    e = A.expr().t().multiply(W.expr().elem_multiply(R.expr())) \
        .add(D.expr().elem_multiply(R.expr()).sum()
             .multiply_scalar(1.0 / n)) \
        .multiply_scalar(alpha).add_scalar((1.0 - alpha) / n)
    a, r, w, d = (x.astype(np.float64) for x in (a, r, w, d))
    ref = alpha * (a.T @ (w * r) + (d * r).sum() / n) + (1 - alpha) / n
    return e, ref


def _annotated(e, mesh, cfg):
    opt = planner.annotate_strategies(optimize(e, cfg), mesh, cfg)
    return fusion_lib.annotate_fusion(opt, mesh, cfg)


class TestOffStateBitIdentity:
    def test_off_constructs_no_region_objects(self, mesh8):
        e, _ = _chain(mesh8)
        before = fusion_lib._CONSTRUCTED["count"]
        plan = executor_lib.compile_expr(e, mesh8, CFG_OFF)
        assert fusion_lib._CONSTRUCTED["count"] == before
        assert not fusion_lib.collect_stamps(plan.optimized)
        assert "fusion" not in (plan.meta or {})

    def test_off_poisoned_init(self, mesh8, monkeypatch):
        """The bit-identity contract, enforced structurally: with
        fusion off the compile path must never even INSTANTIATE a
        FusedRegion (the resilience default-config zero-object
        idiom)."""
        def boom(*a, **k):
            raise AssertionError("FusedRegion constructed with "
                                 "fusion_enable off")

        monkeypatch.setattr(fusion_lib, "FusedRegion", boom)
        e, ref = _chain(mesh8)
        out = executor_lib.compile_expr(e, mesh8, CFG_OFF).run()
        np.testing.assert_allclose(out.to_numpy()[:ref.shape[0]],
                                   ref, rtol=1e-4, atol=1e-4)

    def test_segment_returns_empty_when_off(self, mesh8):
        e, _ = _chain(mesh8)
        opt = planner.annotate_strategies(optimize(e, CFG_OFF), mesh8,
                                          CFG_OFF)
        assert fusion_lib.segment(opt, CFG_OFF) == []
        assert fusion_lib.annotate_fusion(opt, mesh8, CFG_OFF) is opt


class TestRegionGrammar:
    def test_epilogue_chain_fuses_with_anchor(self, mesh8):
        e, _ = _chain(mesh8)
        opt = _annotated(e, mesh8, CFG_ON)
        stamps = fusion_lib.collect_stamps(opt)
        assert len(stamps) == 1
        s = stamps[0]
        assert s.attrs["fused_anchor"] is not None
        census = s.attrs["fused_census"]
        assert census["mm"] == 1
        assert census.get("elemwise.add") == 1
        assert s.attrs["fused_saved_dispatches"] >= 3
        assert s.attrs["fused_saved_hbm_bytes"] > 0
        # the signature embeds in '|'-separated autotune keys
        assert "|" not in s.attrs["fused_region"]

    def test_shared_node_is_a_boundary(self, mesh8):
        rng = np.random.default_rng(1)
        A = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        shared = A.expr().multiply_scalar(2.0)
        e = shared.add(shared.elem_multiply(shared))
        opt = _annotated(e, mesh8, CFG_ON)
        for s in fusion_lib.collect_stamps(opt):
            nodes = fusion_lib.region_nodes(s)
            counts = fusion_lib.consumer_counts((opt,))
            for uid, node in nodes.items():
                if uid != s.uid:
                    assert counts[uid] == 1, (
                        "shared node absorbed as a member")

    def test_at_most_one_anchor(self, mesh8):
        rng = np.random.default_rng(2)
        mats = [BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8) for _ in range(4)]
        # (A·B) + (C·D): the add can absorb only ONE producer
        e = mats[0].expr().multiply(mats[1].expr()).add(
            mats[2].expr().multiply(mats[3].expr()))
        opt = _annotated(e, mesh8, CFG_ON)
        for s in fusion_lib.collect_stamps(opt):
            nodes = fusion_lib.region_nodes(s)
            assert sum(1 for n in nodes.values()
                       if n.kind == "matmul") <= 1

    def test_lone_fusable_op_is_not_a_region(self, mesh8):
        rng = np.random.default_rng(3)
        A = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        B = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        # transpose boundary between the add and anything else:
        # the add alone (leaf operands) must not stamp
        e = A.expr().add(B.expr())
        opt = _annotated(e, mesh8, CFG_ON)
        # add + nothing fusable below = 1 member -> no region
        assert not fusion_lib.collect_stamps(opt)

    def test_remask_census_counts_breakers(self, mesh8):
        rng = np.random.default_rng(4)
        A = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        B = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        e = A.expr().multiply(B.expr()).add_scalar(1.0) \
            .multiply_scalar(2.0)
        opt = _annotated(e, mesh8, CFG_ON)
        (s,) = fusion_lib.collect_stamps(opt)
        assert s.attrs["fused_remask"] == 1   # scalar add v!=0 only


class TestFusedExecutionAgrees:
    def test_dense_chain_oracle(self, mesh8):
        e, ref = _chain(mesh8)
        out = executor_lib.compile_expr(e, mesh8, CFG_ON).run()
        np.testing.assert_allclose(out.to_numpy()[:ref.shape[0]],
                                   ref, rtol=1e-4, atol=1e-4)

    def test_fused_equals_staged_exactly(self, mesh8):
        e, _ = _chain(mesh8, seed=5)
        a = executor_lib.compile_expr(e, mesh8, CFG_OFF).run()
        b = executor_lib.compile_expr(e, mesh8, CFG_ON).run()
        np.testing.assert_allclose(a.to_numpy(), b.to_numpy(),
                                   rtol=1e-6, atol=1e-6)

    def test_spgemm_anchor_epilogue(self, mesh8):
        from matrel_tpu.ops import kernel_registry as kr
        bs = 8
        n = bs * 48
        SA = kr.synthesize_structure("row_band", n, bs, mesh8, seed=0)
        SB = kr.synthesize_structure("row_band", n, bs, mesh8, seed=1)
        ref = (SA.to_numpy().astype(np.float64)
               @ SB.to_numpy().astype(np.float64)) * 0.5
        e = SA.multiply(SB).multiply_scalar(0.5)
        # the probabilistic density lift overestimates banded output
        # density; raise the crossover so the S×S dispatch fires
        cfg = CFG_ON.replace(block_size=bs,
                             spgemm_density_threshold=0.6)
        opt = _annotated(e, mesh8, cfg)
        (s,) = fusion_lib.collect_stamps(opt)
        anchor = fusion_lib.region_nodes(s)[s.attrs["fused_anchor"]]
        assert anchor.attrs.get("strategy") == "spgemm"
        out = executor_lib.execute(e, mesh8, cfg).to_numpy()
        scale = max(float(np.abs(ref).max()), 1.0)
        np.testing.assert_allclose(out[:n, :n] / scale, ref / scale,
                                   rtol=1e-4, atol=1e-4)

    def test_precision_tier_preserved_in_region(self, mesh8):
        rng = np.random.default_rng(6)
        a = rng.random((32, 32), dtype=np.float32)
        b = rng.random((32, 32), dtype=np.float32)
        A = BlockMatrix.from_numpy(a, mesh=mesh8)
        B = BlockMatrix.from_numpy(b, mesh=mesh8)
        e = A.expr().multiply(B.expr()).multiply_scalar(2.0) \
            .add_scalar(0.5)
        cfg = CFG_ON.replace(precision_sla="high")
        opt = _annotated(e, mesh8, cfg)
        (s,) = fusion_lib.collect_stamps(opt)
        anchor = fusion_lib.region_nodes(s)[s.attrs["fused_anchor"]]
        assert anchor.attrs.get("precision_tier") == "bf16x3"
        assert s.attrs["fused_tier"] == "bf16x3"
        out = executor_lib.execute(e, mesh8, cfg).to_numpy()
        ref = (a.astype(np.float64) @ b.astype(np.float64)) * 2 + 0.5
        np.testing.assert_allclose(out[:32, :32], ref, rtol=1e-3,
                                   atol=1e-3)


class TestEpilogueSlots:
    def test_run_matmul_epilogue_in_trace(self, mesh8):
        import jax.numpy as jnp
        from matrel_tpu.parallel import strategies
        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.standard_normal((16, 16)).astype(
            np.float32))
        b = jnp.asarray(rng.standard_normal((16, 16)).astype(
            np.float32))
        plain = strategies.run_matmul("xla", a, b, mesh8, CFG_OFF)
        fused = strategies.run_matmul("xla", a, b, mesh8, CFG_OFF,
                                      epilogue=lambda x: x * 3.0)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(plain) * 3.0,
                                   rtol=1e-6)

    def test_spmm_apply_epilogue(self, mesh8):
        from matrel_tpu.core.sparse import BlockSparseMatrix
        from matrel_tpu.ops import spmm as spmm_lib
        S = BlockSparseMatrix.random((64, 64), block_density=0.5,
                                     block_size=8, mesh=mesh8, seed=0)
        D = BlockMatrix.random((64, 8), mesh=mesh8, seed=1)
        plain = spmm_lib.apply(S, D.data, D.shape, CFG_OFF)
        fused = spmm_lib.apply(S, D.data, D.shape, CFG_OFF,
                               epilogue=lambda x: x + 1.0)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(plain) + 1.0, rtol=1e-6)

    def test_spgemm_tilewise_matches_dense_hook(self, mesh8):
        """A zero-preserving scalar epilogue applied tile-wise (the
        specialized classes' registered mode) equals the dense
        post-scatter application — the hook may only change WHERE the
        chain runs, never the product."""
        from matrel_tpu.ops import kernel_registry as kr
        from matrel_tpu.ops import spgemm as spgemm_lib
        bs = 8
        n = bs * 16
        SA = kr.synthesize_structure("row_band", n, bs, mesh8, seed=2)
        SB = kr.synthesize_structure("row_band", n, bs, mesh8, seed=3)
        assert kr.pair_class_of(SA, SB) == "row_band"
        assert kr.epilogue_mode("row_band", True) == "tilewise"
        assert kr.epilogue_mode("row_band", False) == "dense"
        assert kr.epilogue_mode("generic", True) == "dense"
        cfg = CFG_OFF.replace(block_size=bs)
        epi = lambda x: x * 0.25
        tile = spgemm_lib.apply_dense(SA, SB, cfg, epilogue=epi,
                                      epilogue_elementwise=True)
        dense = spgemm_lib.apply_dense(SA, SB, cfg, epilogue=epi,
                                       epilogue_elementwise=False)
        np.testing.assert_allclose(np.asarray(tile),
                                   np.asarray(dense), rtol=1e-6)

    def test_register_epilogue_hook_validates(self):
        from matrel_tpu.ops import kernel_registry as kr
        with pytest.raises(ValueError):
            kr.register_epilogue_hook("row_band", "bogus")


class TestMV111:
    def test_quiet_on_fresh_annotation(self, mesh8):
        e, _ = _chain(mesh8, seed=8)
        opt = _annotated(e, mesh8, CFG_ON)
        assert [d for d in analysis.verify_plan(opt, mesh8, CFG_ON)
                if d.code == "MV111"] == []

    def test_stamp_with_fusion_off_is_error(self, mesh8):
        e, _ = _chain(mesh8, seed=9)
        opt = _annotated(e, mesh8, CFG_ON)
        diags = [d for d in analysis.verify_plan(opt, mesh8, CFG_OFF)
                 if d.code == "MV111"]
        assert diags and all(d.severity == "error" for d in diags)

    def test_unstamped_region_flagged_backward(self, mesh8):
        e, _ = _chain(mesh8, seed=10)
        opt = planner.annotate_strategies(optimize(e, CFG_ON), mesh8,
                                          CFG_ON)   # NOT fused
        diags = [d for d in analysis.verify_plan(opt, mesh8, CFG_ON)
                 if d.code == "MV111"]
        assert diags and diags[0].severity == "error"
        # under autotune the suppression is legitimate -> warning
        cfg_at = CFG_ON.replace(autotune=True)
        diags = [d for d in analysis.verify_plan(opt, mesh8, cfg_at)
                 if d.code == "MV111"]
        assert diags and diags[0].severity == "warning"

    def test_tampered_census_is_error(self, mesh8):
        e, _ = _chain(mesh8, seed=11)
        opt = _annotated(e, mesh8, CFG_ON)

        def tamper(n):
            if "fused_region" in n.attrs:
                return n.with_attrs(fused_census={"mm": 99})
            if not n.children:
                return n
            return n.with_children(tuple(tamper(c)
                                         for c in n.children))

        bad = tamper(opt)
        diags = [d for d in analysis.verify_plan(bad, mesh8, CFG_ON)
                 if d.code == "MV111" and d.severity == "error"]
        assert diags

    def test_tampered_tier_is_error(self, mesh8):
        e, _ = _chain(mesh8, seed=12)
        opt = _annotated(e, mesh8, CFG_ON)

        def tamper(n):
            if "fused_region" in n.attrs:
                return n.with_attrs(fused_tier="bf16x1")
            if not n.children:
                return n
            return n.with_children(tuple(tamper(c)
                                         for c in n.children))

        bad = tamper(opt)
        diags = [d for d in analysis.verify_plan(bad, mesh8, CFG_ON)
                 if d.code == "MV111" and d.severity == "error"]
        assert diags
        assert "tier" in diags[0].message

    def test_error_gate_blocks_tampered_plan(self, mesh8):
        e, _ = _chain(mesh8, seed=13)
        cfg = CFG_ON.replace(verify_plans="error")
        # a clean compile passes the gate
        executor_lib.compile_expr(e, mesh8, cfg)


class TestUnitProgramSeam:
    @pytest.mark.parametrize("chain", [_chain, _pagerank_step],
                             ids=["linreg_epilogue", "pagerank_step"])
    def test_dispatch_counts_shrink(self, mesh8, chain):
        e, ref = chain(mesh8, seed=14)
        staged = executor_lib.compile_staged_units(e, mesh8, CFG_OFF)
        fused = executor_lib.compile_region_units(e, mesh8, CFG_ON)
        assert fused.dispatches < staged.dispatches
        assert sum(1 for *_, members in fused.units if members > 1) >= 1
        a = np.asarray(staged.run())
        b = np.asarray(fused.run())
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(b[:ref.shape[0]], ref, rtol=1e-4,
                                   atol=1e-4)

    def test_region_units_without_fusion_match_staged(self, mesh8):
        e, _ = _chain(mesh8, seed=15)
        ru = executor_lib.compile_region_units(e, mesh8, CFG_OFF)
        su = executor_lib.compile_staged_units(e, mesh8, CFG_OFF)
        assert ru.dispatches == su.dispatches


class TestAutotuneFuseFamily:
    def test_key_format_accepted(self):
        from matrel_tpu.parallel import autotune
        key = autotune._fusion_key("mmx1+scalar.mulx2", 512, 2, 4)
        assert key.startswith("fuse|")
        assert autotune._current_key_format(key)
        assert autotune._current_key_format(key + "|w1x4")
        assert not autotune._current_key_format("fuse|sig|extra|f|g|h|i")

    def test_measure_and_persist_roundtrip(self, mesh8, tmp_path):
        from matrel_tpu.parallel import autotune
        e, _ = _chain(mesh8, seed=16)
        opt = planner.annotate_strategies(optimize(e, CFG_ON), mesh8,
                                          CFG_ON)
        (region,) = fusion_lib.segment(opt, CFG_ON, mesh=mesh8)
        table = str(tmp_path / "fuse.json")
        cfg = CFG_ON.replace(autotune=True, autotune_table_path=table)
        best = autotune.lookup_or_measure_fusion(region, opt, mesh8,
                                                 cfg)
        assert best in (None, "fused", "staged")
        persisted = autotune.load_table(table)
        fuse_keys = [k for k in persisted if k.startswith("fuse|")]
        # ties (None) persist too when both variants measured
        if fuse_keys:
            entry = persisted[fuse_keys[0]]
            assert set(entry["times"]) <= {"fused", "staged"}
            # replay from the persisted table with fresh caches
            autotune._FUSION_CACHE.clear()
            autotune._TABLE_CACHE.clear()
            again = autotune.lookup_or_measure_fusion(region, opt,
                                                      mesh8, cfg)
            assert again == best

    def test_staged_winner_suppresses_stamp(self, mesh8, monkeypatch):
        from matrel_tpu.parallel import autotune
        e, _ = _chain(mesh8, seed=17)
        monkeypatch.setattr(autotune, "lookup_or_measure_fusion",
                            lambda *a, **k: "staged")
        cfg = CFG_ON.replace(autotune=True)
        opt = planner.annotate_strategies(optimize(e, cfg), mesh8, cfg)
        out = fusion_lib.annotate_fusion(opt, mesh8, cfg)
        assert not fusion_lib.collect_stamps(out)


class TestDegradeRung:
    def test_rung3_forces_staged(self):
        from matrel_tpu.resilience import degrade
        base = MatrelConfig(fusion_enable=True)
        assert degrade.apply_rung(base, 2).fusion_enable is True
        assert degrade.apply_rung(base, 3).fusion_enable is False
        assert degrade.apply_rung(base, 4).fusion_enable is False
        # rung 0 identity (bit-identity contract)
        assert degrade.apply_rung(base, 0) is base


class TestObsSurfaces:
    def test_matmul_decisions_carry_boundary(self, mesh8):
        e, _ = _chain(mesh8, seed=18)
        plan = executor_lib.compile_expr(e, mesh8, CFG_ON)
        (d,) = executor_lib.plan_matmul_decisions(plan)
        assert d["fused_region"]
        assert d["fused_census"]["mm"] == 1
        assert d["est_saved_dispatches"] >= 3
        assert d["est_saved_hbm_bytes"] > 0
        assert plan.meta["fusion"]["regions"] == 1

    def test_decisions_unchanged_when_off(self, mesh8):
        e, _ = _chain(mesh8, seed=19)
        plan = executor_lib.compile_expr(e, mesh8, CFG_OFF)
        (d,) = executor_lib.plan_matmul_decisions(plan)
        assert "fused_region" not in d
        assert "est_saved_dispatches" not in d

    def test_drift_keying(self):
        from matrel_tpu.obs import drift
        assert drift._strategy_key(
            {"strategy": "bmm_right",
             "fused_region": "mmx1+scalar.mulx2"}) \
            == "fused:mmx1+scalar.mulx2"
        assert drift._strategy_key(
            {"strategy": "bmm_right"}) == "bmm_right"
        # tier still suffixes the fused key (same-tier populations)
        assert drift._strategy_key(
            {"fused_region": "s", "precision_tier": "bf16x3"}) \
            == "fused:s@bf16x3"

    def test_drift_joins_anchor_by_membership(self):
        from matrel_tpu.obs import drift
        events = [{
            "kind": "analyze", "backend": "cpu",
            "per_op": [{"uid": 99, "label": "fused:sig", "ms": 2.0,
                        "fused_region": "sig", "members": [7]}],
            "matmuls": [{"uid": 7, "dims": [32, 32, 32],
                         "strategy": "xla", "flops": 1e6,
                         "fused_region": "sig",
                         "est_ici_bytes": 0.0}],
        }]
        samples = list(drift.iter_samples(events))
        assert len(samples) == 1
        assert samples[0]["strategy"] == "fused:sig"
        assert samples[0]["ms"] == 2.0

    def test_history_fusion_line(self):
        from matrel_tpu.obs import history
        events = [{"kind": "query", "matmuls": [],
                   "fusion": {"regions": 2,
                              "census": {"mm": 2, "scalar.mul": 3},
                              "est_saved_dispatches": 5,
                              "est_saved_hbm_bytes": 2 << 20}}]
        s = history.summarize(events)
        assert s["fusion"]["regions"] == 2
        text = history.render_summary(events)
        assert "fusion: 2 region(s)" in text
        assert "5 dispatch(es)" in text

    def test_history_no_fusion_line_when_absent(self):
        from matrel_tpu.obs import history
        events = [{"kind": "query", "matmuls": []}]
        assert history.summarize(events)["fusion"] is None
        assert "fusion:" not in history.render_summary(events)

    def test_analyze_attributes_region_not_ghosts(self, mesh8,
                                                  tmp_path):
        from matrel_tpu.obs import analyze as analyze_mod
        from matrel_tpu.session import MatrelSession
        e, _ = _chain(mesh8, seed=20)
        sess = MatrelSession(mesh=mesh8, config=CFG_ON)
        plan = sess.compile(e)
        per_op, _total = analyze_mod.measure_per_op(plan)
        stamps = analyze_mod._fusion_stamps(plan)
        assert stamps, "plan lost its fusion stamp"
        (root_uid,) = stamps
        members = set(stamps[root_uid]["fused_members"])
        # ONE row at the region root, NO rows for absorbed members
        assert root_uid in per_op
        label, seconds = per_op[root_uid]
        assert label.startswith("fused:")
        assert seconds >= 0.0
        assert not (members & set(per_op)), "ghost member rows"
        rec = analyze_mod.analyze_record(plan, per_op, 0.001)
        region_rows = [r for r in rec["per_op"]
                       if r.get("fused_region")]
        assert len(region_rows) == 1
        assert set(region_rows[0]["members"]) == members
        text = analyze_mod.render(plan, per_op, 0.001)
        assert "fused=" in text
        assert "(in fused region" in text

    def test_query_event_carries_fusion(self, mesh8, tmp_path):
        import json
        from matrel_tpu.session import MatrelSession
        log = tmp_path / "ev.jsonl"
        e, _ = _chain(mesh8, seed=21)
        sess = MatrelSession(mesh=mesh8, config=CFG_ON.replace(
            obs_level="on", obs_event_log=str(log)))
        sess.run(e)
        events = [json.loads(l) for l in log.open()]
        q = [ev for ev in events if ev.get("kind") == "query"][0]
        assert q["fusion"]["regions"] == 1
        (d,) = q["matmuls"]
        assert d["fused_region"]


class TestConfigKnob:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("MATREL_FUSION_ENABLE", "1")
        cfg = MatrelConfig.from_env()
        assert cfg.fusion_enable is True
