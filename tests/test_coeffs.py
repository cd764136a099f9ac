"""The cost-model closed loop (parallel/coeffs.py + serve/replan.py +
planner integration; docs/COST_MODEL.md): the coefficient seam parses,
memoises and epoch-stamps drift tables; choose_strategy_ex ranks by
calibrated milliseconds only under full row coverage (all-or-nothing,
stamped ``cost: "measured"``); the ReplanController turns a firing
DRIFT rank flag into a re-calibration + epoch bump with cooldown and
reversal-dwell hysteresis; and the default config constructs NOTHING
from the replan module (poisoned init) and keys plans without any
``coeffv:`` prefix — bit-identical to the pre-loop planner."""

import json
import os

import numpy as np
import pytest

from matrel_tpu import executor as executor_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.obs import drift
from matrel_tpu.parallel import coeffs, planner
from matrel_tpu.serve import replan as replan_lib
from matrel_tpu.session import MatrelSession

CLS = "<=128"


def _row(strategy, gf, mib, count=10, cls=CLS, backend="cpu"):
    return {"strategy": strategy, "class": cls, "backend": backend,
            "count": count, "ms_median": 1.0,
            "ms_per_gflop": gf, "ms_per_est_mib": mib}


def _write(path, rows):
    entries = {f"{r['strategy']}|{r['class']}|{r['backend']}": r
               for r in rows}
    with open(path, "w") as f:
        json.dump({"schema": 1, "entries": entries}, f)
    coeffs.reset_coefficient_cache()


@pytest.fixture()
def table(tmp_path):
    return str(tmp_path / "drift.json")


class TestSeam:
    def test_cold_table(self, table):
        assert coeffs.strategy_coefficients(table) == {}
        assert coeffs.class_coefficients(table) == {}
        assert coeffs.epoch(table) == coeffs.COLD_EPOCH
        assert coeffs.strategy_row("rmm", CLS, "cpu", table) is None

    def test_rows_and_tier_keying(self, table):
        _write(table, [_row("rmm", 1.5, 0.3),
                       _row("rmm@bf16x3", 0.5, 0.3)])
        bare = coeffs.strategy_row("rmm", CLS, "cpu", table)
        tiered = coeffs.strategy_row("rmm", CLS, "cpu", table,
                                     tier="bf16x3")
        assert bare["ms_per_gflop"] == 1.5
        assert tiered["ms_per_gflop"] == 0.5
        assert bare["source"] == tiered["source"] == "measured"

    def test_nonfinite_ratios_dropped_fieldwise(self, table):
        _write(table, [_row("rmm", float("nan"), 0.3),
                       _row("cpmm", float("inf"), float("nan"))])
        row = coeffs.strategy_row("rmm", CLS, "cpu", table)
        assert row["ms_per_gflop"] is None
        assert row["ms_per_mib"] == 0.3
        # both ratios poisoned -> the whole row is unusable, absent
        assert coeffs.strategy_row("cpmm", CLS, "cpu", table) is None

    def test_zero_count_row_dropped(self, table):
        _write(table, [_row("rmm", 1.0, 0.3, count=0)])
        assert coeffs.strategy_coefficients(table) == {}
        assert coeffs.epoch(table) == coeffs.COLD_EPOCH

    def test_stat_signature_invalidation_without_reset(self, table):
        _write(table, [_row("rmm", 1.0, 0.3)])
        assert coeffs.strategy_row("rmm", CLS, "cpu",
                                   table)["ms_per_gflop"] == 1.0
        # a table rewrite (new size/mtime) must be picked up by the
        # NEXT consult with no explicit cache reset — the live re-plan
        # path depends on it
        entries = {f"rmm|{CLS}|cpu": _row("rmm", 2.25, 0.3)}
        with open(table, "w") as f:
            json.dump({"schema": 1, "entries": entries}, f)
        os.utime(table, ns=(1, 1))  # force a distinct stat signature
        assert coeffs.strategy_row("rmm", CLS, "cpu",
                                   table)["ms_per_gflop"] == 2.25

    def test_epoch_stable_across_count_only_merge(self, table):
        _write(table, [_row("rmm", 1.0, 0.3, count=10)])
        ep1 = coeffs.epoch(table)
        _write(table, [_row("rmm", 1.0, 0.3, count=20)])
        assert coeffs.epoch(table) == ep1      # values unchanged
        _write(table, [_row("rmm", 1.1, 0.3, count=20)])
        ep2 = coeffs.epoch(table)
        assert ep2 != ep1 and ep2 != coeffs.COLD_EPOCH

    def test_predict_ms_and_cold_term_fallbacks(self):
        full = {"ms_per_gflop": 2.0, "ms_per_mib": 0.5}
        assert coeffs.predict_ms(full, 3.0, 4 << 20) == \
            pytest.approx(2.0 * 3.0 + 0.5 * 4.0)
        no_mib = {"ms_per_gflop": 2.0, "ms_per_mib": None}
        assert coeffs.predict_ms(no_mib, 3.0, 4 << 20) == \
            pytest.approx(6.0 + coeffs.ANALYTIC_MS_PER_MIB * 4.0)
        no_gf = {"ms_per_gflop": None, "ms_per_mib": 0.5}
        assert coeffs.predict_ms(no_gf, 3.0, 4 << 20) == \
            pytest.approx(coeffs.ANALYTIC_MS_PER_GFLOP * 3.0 + 2.0)

    def test_class_blend_is_count_weighted(self, table):
        _write(table, [_row("rmm", 1.0, 0.2, count=1),
                       _row("cpmm", 3.0, 0.6, count=3)])
        blend = coeffs.class_coefficients(table)[(CLS, "cpu", "")]
        assert blend["ms_per_gflop"] == pytest.approx(2.5)
        assert blend["ms_per_mib"] == pytest.approx(0.5)
        assert blend["count"] == 4

    def test_chain_comm_weights(self, table):
        _write(table, [_row("rmm", 1.0, 0.4, count=5),
                       _row("rmm@bf16x3", 9.0, 9.0, count=50,
                            cls="<=256"),
                       _row("cpmm", 1.0, 0.4, count=5, cls="<=512",
                            backend="tpu")])
        w = coeffs.chain_comm_weights(table, "cpu")
        # FLOP-equivalents per byte: (mib/2^20) / (gf/1e9)
        assert w == {CLS: pytest.approx((0.4 / 2 ** 20) / (1.0 / 1e9))}
        # tiered blends and foreign backends never reach the DP
        assert "<=256" not in w and "<=512" not in w
        assert coeffs.chain_comm_weights(table, "cpu",
                                         min_samples=6) == {}


CANDS = ("bmm_right", "bmm_left", "cpmm", "rmm", "xla")


def _decisions(mesh, cfg, n=128, seed=7):
    A = BlockMatrix.random((n, n), mesh=mesh, seed=seed)
    B = BlockMatrix.random((n, n), mesh=mesh, seed=seed + 1)
    plan = executor_lib.compile_expr(A.expr().multiply(B.expr()),
                                     mesh, cfg)
    return executor_lib.plan_matmul_decisions(plan)


class TestMeasuredRanking:
    def _cfg(self, table, **kw):
        kw.setdefault("coeff_planner_enable", True)
        kw.setdefault("coeff_min_samples", 2)
        return MatrelConfig(obs_level="off", drift_table_path=table,
                            **kw)

    def test_poisoned_table_flips_pick_and_stamps_measured(
            self, mesh8, table):
        analytic = _decisions(
            mesh8, MatrelConfig(obs_level="off",
                                drift_table_path=table))[0]["strategy"]
        decoy = next(s for s in CANDS if s != analytic)
        _write(table, [_row(s, 0.01 if s == decoy else 1.0,
                            0.0001 if s == decoy else 0.5)
                       for s in CANDS])
        d = _decisions(mesh8, self._cfg(table))[0]
        assert d["strategy"] == decoy
        assert d["cost"] == "measured"

    def test_table_built_from_forced_runs_covers_every_class(
            self, mesh8, table):
        """The loop the way traffic closes it: every strategy forced on
        three workloads in three shape classes, the walls written as
        drift samples at the workloads' own matmul shapes through the
        auditor's calibrate/update_table, then fresh sessions ranking
        against that table — every decision ``measured``, every answer
        equal to the analytic planner's."""
        import time
        n, k = 128, 64
        rng = np.random.default_rng(0)
        C = [BlockMatrix.random((n, n), mesh=mesh8, seed=2 + i)
             for i in range(3)]
        P = BlockMatrix.random((2 * n, 2 * n), mesh=mesh8, seed=5)
        R, W = (BlockMatrix.from_numpy(
            rng.random((2 * n, 1), dtype=np.float32), mesh=mesh8)
            for _ in range(2))
        X = BlockMatrix.from_numpy(
            rng.random((4 * n, k), dtype=np.float32), mesh=mesh8)
        eye = BlockMatrix.from_numpy(np.eye(k, dtype=np.float32),
                                     mesh=mesh8)
        workloads = (
            lambda: C[0].expr().multiply(C[1].expr())
            .multiply(C[2].expr()),
            lambda: P.expr().t()
            .multiply(W.expr().elem_multiply(R.expr()))
            .multiply_scalar(0.85).add_scalar(0.15 / (2 * n)),
            lambda: X.expr().t().multiply(X.expr())
            .multiply_scalar(1.0 / (4 * n))
            .add(eye.expr().multiply_scalar(0.1)))
        analytic = MatrelConfig(obs_level="off",
                                drift_table_path=table)
        samples = []
        for make in workloads:
            for s in CANDS:
                cfg = analytic.replace(strategy_override=s)
                sess = MatrelSession(mesh=mesh8, config=cfg)
                walls = []
                try:
                    decs = [
                        d for d in executor_lib.plan_matmul_decisions(
                            executor_lib.compile_expr(make(), mesh8, cfg))
                        if (d.get("flops") or 0) > 0]
                    for _ in range(2):   # clears coeff_min_samples=2
                        t0 = time.perf_counter()
                        sess.run(make()).data.block_until_ready()
                        walls.append((time.perf_counter() - t0) * 1e3)
                except ValueError:
                    # a forced strategy this shape does not admit (a
                    # matvec under bmm): no row, as in traffic
                    continue
                total = sum(d["flops"] for d in decs)
                samples += [{
                    "strategy": d.get("strategy", s),
                    "class": drift.shape_class(
                        tuple(d.get("dims") or ())),
                    "backend": "cpu", "tier": "",
                    "flops": float(d["flops"]),
                    "est_bytes": float(d.get("est_ici_bytes") or 0.0),
                    "ms": ms * d["flops"] / total,
                    "source": "bench"} for ms in walls for d in decs]
        assert len({s["class"] for s in samples}) == 3
        drift.update_table(table, drift.calibrate(samples))
        coeffs.reset_coefficient_cache()
        measured = self._cfg(table)
        for make in workloads:
            decs = executor_lib.plan_matmul_decisions(
                executor_lib.compile_expr(make(), mesh8, measured))
            assert decs and all(d["cost"] == "measured" for d in decs)
            ref = MatrelSession(mesh=mesh8, config=analytic) \
                .run(make()).to_numpy().astype(np.float64)
            got = MatrelSession(mesh=mesh8, config=measured) \
                .run(make()).to_numpy().astype(np.float64)
            scale = max(float(np.abs(ref).max()), 1.0)
            np.testing.assert_allclose(got / scale, ref / scale,
                                       atol=1e-5)

    def test_partial_coverage_stays_analytic(self, mesh8, table):
        # all-or-nothing: one cold candidate means ranking measured
        # milliseconds against raw byte-equivalents — a units error
        _write(table, [_row(s, 1.0, 0.5) for s in CANDS
                       if s != "rmm"])
        d = _decisions(mesh8, self._cfg(table))[0]
        assert d["cost"] == "analytic"

    def test_below_min_samples_stays_analytic(self, mesh8, table):
        _write(table, [_row(s, 1.0, 0.5, count=1) for s in CANDS])
        d = _decisions(mesh8, self._cfg(table,
                                        coeff_min_samples=3))[0]
        assert d["cost"] == "analytic"

    def test_default_config_emits_no_cost_stamp(self, mesh8, table):
        _write(table, [_row(s, 1.0, 0.5) for s in CANDS])
        for d in _decisions(mesh8, MatrelConfig(
                obs_level="off", drift_table_path=table)):
            assert "cost" not in d

    def test_comm_cost_coeff_scales_to_ms(self):
        raw = planner.comm_cost("cpmm", 128, 128, 128, 1.0, 1.0, 2, 4)
        ms = planner.comm_cost("cpmm", 128, 128, 128, 1.0, 1.0, 2, 4,
                               coeff={"ms_per_mib": 2.0})
        assert ms == pytest.approx(2.0 * raw / (1 << 20))
        cold = planner.comm_cost("cpmm", 128, 128, 128, 1.0, 1.0,
                                 2, 4, coeff={})
        assert cold == pytest.approx(
            coeffs.ANALYTIC_MS_PER_MIB * raw / (1 << 20))

    def test_comm_cost_axes_coeff_scales_both_axes(self):
        bx, by = planner.comm_cost_axes("cpmm", 128, 128, 128,
                                        1.0, 1.0, 2, 4)
        mx, my = planner.comm_cost_axes("cpmm", 128, 128, 128,
                                        1.0, 1.0, 2, 4,
                                        coeff={"ms_per_mib": 2.0})
        scale = 2.0 / (1 << 20)
        assert mx == pytest.approx(bx * scale)
        assert my == pytest.approx(by * scale)


def _query(strategy, ms, est, dims=(64, 64, 64)):
    return {"kind": "query", "backend": "cpu", "cache": "miss",
            "execute_ms": ms,
            "matmuls": [{"strategy": strategy, "dims": list(dims),
                         "flops": 2.0 * dims[0] * dims[1] * dims[2],
                         "est_ici_bytes": est}]}


class TestReplanController:
    def _cfg(self, table, **kw):
        kw.setdefault("coeff_replan_cooldown", 2)
        return MatrelConfig(obs_level="off", drift_table_path=table,
                            coeff_planner_enable=True,
                            coeff_replan_enable=True,
                            coeff_replan_interval=10 ** 6, **kw)

    def _feed(self, ctl, strategy, ms, est, k=3):
        for _ in range(k):
            ctl.observe(_query(strategy, ms, est))

    def test_from_config_default_is_structural_zero(self):
        before = replan_lib._CONSTRUCTED["count"]
        assert replan_lib.from_config(MatrelConfig()) is None
        assert replan_lib._CONSTRUCTED["count"] == before

    def test_flag_fires_recalibrates_and_bumps_epoch(self, table):
        ctl = replan_lib.from_config(self._cfg(table))
        assert isinstance(ctl, replan_lib.ReplanController)
        # the model prefers cpmm by bytes; measurement says rmm is
        # 10x faster — the canonical DRIFT inversion
        self._feed(ctl, "cpmm", ms=10.0, est=1000.0)
        self._feed(ctl, "rmm", ms=1.0, est=2000.0)
        rec = ctl.check()
        assert rec is not None and ctl.replans == 1
        assert rec["classes"] == ["<=64"]
        assert rec["old_epoch"] == coeffs.COLD_EPOCH
        assert rec["epoch"] != coeffs.COLD_EPOCH
        assert rec["flags"][0]["model_prefers"] == "cpmm"
        assert rec["flags"][0]["measured_prefers"] == "rmm"
        assert rec["replanned"] == 0          # no session attached
        row = coeffs.strategy_row("cpmm", "<=64", "cpu", table)
        assert row is not None and row["source"] == "measured"
        # actioned samples dropped: the window holds fresh-only
        assert ctl.info()["window"] == 0

    def test_cooldown_suppresses_immediate_refire(self, table):
        ctl = replan_lib.from_config(self._cfg(table))
        self._feed(ctl, "cpmm", ms=10.0, est=1000.0)
        self._feed(ctl, "rmm", ms=1.0, est=2000.0)
        assert ctl.check() is not None
        # same stale inversion refed immediately: the population is
        # cooling, the loop must wait for post-re-plan evidence
        self._feed(ctl, "cpmm", ms=10.0, est=1000.0)
        self._feed(ctl, "rmm", ms=1.0, est=2000.0)
        assert ctl.check() is None
        assert ctl.replans == 1

    def test_reversal_needs_two_consecutive_checks(self, table):
        ctl = replan_lib.from_config(
            self._cfg(table, coeff_replan_cooldown=0))
        self._feed(ctl, "cpmm", ms=10.0, est=1000.0)
        self._feed(ctl, "rmm", ms=1.0, est=2000.0)
        assert ctl.check() is not None
        # the EXACT reversal of the action just taken: one window is
        # noise, two consecutive windows are a real regression
        self._feed(ctl, "rmm", ms=10.0, est=1000.0)
        self._feed(ctl, "cpmm", ms=1.0, est=2000.0)
        assert ctl.check() is None
        assert ctl.check() is not None
        assert ctl.replans == 2

    def test_interval_triggers_check_from_observe(self, table):
        ctl = replan_lib.from_config(
            self._cfg(table).replace(coeff_replan_interval=2))
        ctl.observe(_query("rmm", 1.0, 1000.0))
        assert ctl.checks == 0
        ctl.observe(_query("rmm", 1.0, 1000.0))
        assert ctl.checks == 1

    def test_observe_never_raises(self, table):
        ctl = replan_lib.from_config(self._cfg(table))
        ctl.observe({"kind": "query", "matmuls": 5,
                     "execute_ms": "garbage"})
        ctl.observe({})
        assert ctl.info()["window"] == 0

    def test_replan_config_requires_planner(self):
        with pytest.raises(ValueError):
            MatrelConfig(coeff_replan_enable=True)


class TestDriftEdgeCases:
    def test_empty_inputs(self):
        assert drift.calibrate([]) == {}
        assert drift.rank_flags([]) == []

    def test_single_strategy_population_never_flags(self):
        samples = list(drift.iter_samples(
            [_query("rmm", 10.0, 1000.0)] * 4))
        assert drift.rank_flags(samples) == []

    def test_rank_flag_margin_boundary(self):
        def flags(ms_a):
            samples = list(drift.iter_samples(
                [_query("a", ms_a, 1000.0),
                 _query("b", 1.0, 2000.0)]))
            return drift.rank_flags(samples)
        assert flags(drift.RANK_FLAG_MARGIN * 1.0)      # >= fires
        assert not flags(drift.RANK_FLAG_MARGIN * 0.99)

    def test_iter_samples_exclusions(self):
        good = _query("rmm", 1.0, 1000.0)
        zero_ms = dict(good, execute_ms=0.0)
        rc_hit = dict(good, cache="rc_hit")
        batched = dict(good, batch=3)
        multi = dict(good, matmuls=good["matmuls"] * 2)
        assert len(list(drift.iter_samples(
            [good, zero_ms, rc_hit, batched, multi]))) == 1

    def test_calibrate_single_sample_and_zero_bytes(self):
        s = {"strategy": "rmm", "class": CLS, "backend": "cpu",
             "tier": "", "flops": 2e9, "est_bytes": 0.0, "ms": 3.0,
             "source": "query"}
        row = drift.calibrate([s])[f"rmm|{CLS}|cpu"]
        assert row["count"] == 1
        assert row["ms_per_gflop"] == pytest.approx(1.5)
        assert row["ms_per_est_mib"] is None   # model said zero bytes

    def test_update_table_blend_is_count_weighted(self, tmp_path):
        path = str(tmp_path / "t.json")
        key = f"rmm|{CLS}|cpu"
        base = {"strategy": "rmm", "class": CLS, "backend": "cpu"}
        drift.update_table(path, {key: dict(base, count=10,
                                            ms_median=1.0,
                                            ms_per_gflop=1.0,
                                            ms_per_est_mib=0.2)})
        out = drift.update_table(path, {key: dict(base, count=10,
                                                  ms_median=3.0,
                                                  ms_per_gflop=3.0,
                                                  ms_per_est_mib=0.6)})
        row = out["entries"][key]
        assert row["count"] == 20
        assert row["ms_per_gflop"] == pytest.approx(2.0)
        assert row["ms_per_est_mib"] == pytest.approx(0.4)


class TestZeroOverheadDefault:
    def test_default_session_constructs_no_replan_state(self, mesh8,
                                                        rng):
        before = replan_lib._CONSTRUCTED["count"]
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig())
        X = BlockMatrix.from_numpy(
            rng.standard_normal((48, 16)).astype(np.float32),
            mesh=mesh8)
        out = sess.run(X.expr().t().multiply(X.expr()))
        assert replan_lib._CONSTRUCTED["count"] == before
        assert sess._replan is None
        assert sess._coeff_epoch() is None
        assert sess._coeff_prefix() == ""
        xn = X.to_numpy()
        np.testing.assert_allclose(out.to_numpy(), xn.T @ xn,
                                   rtol=3e-4, atol=3e-4)

    def test_enabled_session_prefixes_plan_keys(self, mesh8, tmp_path):
        table = str(tmp_path / "drift.json")
        _write(table, [_row("rmm", 1.0, 0.3)])
        sess = MatrelSession(
            mesh=mesh8,
            config=MatrelConfig(obs_level="off",
                                drift_table_path=table,
                                coeff_planner_enable=True))
        ep = coeffs.epoch(table)
        assert ep != coeffs.COLD_EPOCH
        assert sess._coeff_epoch() == ep
        assert sess._coeff_prefix() == f"coeffv:{ep}|"

    def test_cold_prefix_is_self_describing(self, mesh8, tmp_path):
        sess = MatrelSession(
            mesh=mesh8,
            config=MatrelConfig(
                obs_level="off",
                drift_table_path=str(tmp_path / "none.json"),
                coeff_planner_enable=True))
        assert sess._coeff_prefix() == "coeffv:cold|"

    def test_defaults_are_off(self):
        cfg = MatrelConfig()
        assert cfg.coeff_planner_enable is False
        assert cfg.coeff_replan_enable is False
