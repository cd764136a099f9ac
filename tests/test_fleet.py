"""Multi-slice serving fleet (serve/fleet.py, serve/placement.py,
core/mesh slice views, MV114 — docs/FLEET.md).

Covers the acceptance battery: placement decisions flip with axis
weights, directory hit-anywhere vs slice-local miss, hot-entry
migration under the reshard peak budget, dead-slice failover with
deadlines/tenant attribution intact, and default-config zero-slice
bit-identity with the poisoned-init guard.
"""

import dataclasses
import json
import time
from concurrent.futures import Future

import numpy as np
import pytest

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.resilience.errors import (DeadlineExceeded,
                                          FleetSliceLost)
from matrel_tpu.resilience.retry import Deadline
from matrel_tpu.serve import placement as placement_lib
from matrel_tpu.serve.fleet import (DirectoryRecord, FleetController,
                                    FleetDirectory)
from matrel_tpu.session import MatrelSession


def _mk(sess, rng, n=64, names=("A", "B")):
    mats = {}
    for nm in names:
        arr = rng.standard_normal((n, n)).astype(np.float32)
        sess.register(nm, sess.from_numpy(arr))
        mats[nm] = arr
    return mats


def _fleet_session(mesh8, rng, n=64, **kw):
    cfg = MatrelConfig(**{"fleet_slices": 2,
                          "result_cache_max_bytes": 1 << 28, **kw})
    sess = MatrelSession(mesh=mesh8, config=cfg)
    mats = _mk(sess, rng, n=n)
    return sess, mats


def _q(sess):
    return sess.table("A").expr().multiply(sess.table("B").expr())


# ---------------------------------------------------------------------------
# core/mesh slice views
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _FakeDev:
    id: int
    slice_index: int


class _FakeMesh:
    def __init__(self, rows):
        self.devices = np.asarray(rows, dtype=object)


class TestSliceViews:
    def test_virtual_partition_splits_contiguously(self, mesh8):
        groups, source = mesh_lib.slice_device_groups(mesh8, 2)
        assert source == "virtual"
        assert [len(g) for g in groups] == [4, 4]
        assert len({d.id for g in groups for d in g}) == 8

    def test_virtual_meshes_near_square(self, mesh8):
        meshes, source = mesh_lib.slice_meshes(mesh8, 2)
        assert source == "virtual"
        for m in meshes:
            assert mesh_lib.mesh_grid_shape(m) == (2, 2)
            assert m.axis_names == mesh8.axis_names

    def test_shared_when_indivisible(self, mesh8):
        groups, source = mesh_lib.slice_device_groups(mesh8, 3)
        assert source == "shared"
        assert all(len(g) == 8 for g in groups)

    def test_detected_from_slice_index(self):
        rows = [[_FakeDev(0, 0), _FakeDev(1, 0)],
                [_FakeDev(2, 1), _FakeDev(3, 1)]]
        groups, source = mesh_lib.slice_device_groups(
            _FakeMesh(rows), 2)
        assert source == "detected"
        assert {d.id for d in groups[0]} == {0, 1}
        assert {d.id for d in groups[1]} == {2, 3}

    def test_slice_index_mismatch_falls_back_virtual(self):
        rows = [[_FakeDev(0, 0), _FakeDev(1, 0)],
                [_FakeDev(2, 1), _FakeDev(3, 1)]]
        groups, source = mesh_lib.slice_device_groups(
            _FakeMesh(rows), 4)
        assert source == "virtual"
        assert [len(g) for g in groups] == [1, 1, 1, 1]

    def test_bad_count_raises(self, mesh8):
        with pytest.raises(ValueError):
            mesh_lib.slice_device_groups(mesh8, 0)


# ---------------------------------------------------------------------------
# fleet keys
# ---------------------------------------------------------------------------


class TestFleetKey:
    def test_name_keyed_and_stable_across_replicas(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        e = _q(sess)
        k1 = placement_lib.fleet_key(e, fleet._names)
        assert k1 is not None and "@A" in k1 and "@B" in k1
        assert "id(" not in k1
        # the rebound (slice-replica) form of the SAME query keys
        # identically — that is the whole cross-slice point
        sl = fleet.slices[1]
        rebound = fleet._rebind(e, sl)
        k2 = placement_lib.fleet_key(rebound, sl.names_by_id)
        assert k1 == k2

    def test_unnamed_leaf_is_ineligible(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        adhoc = sess.from_numpy(
            rng.standard_normal((64, 64)).astype(np.float32))
        e = sess.table("A").expr().multiply(adhoc.expr())
        assert placement_lib.fleet_key(e, fleet._names) is None

    def test_prefix_isolates_slas(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        e = _q(sess)
        k_def = placement_lib.fleet_key(e, fleet._names, "")
        k_fast = placement_lib.fleet_key(e, fleet._names,
                                         "prec:fast|")
        assert k_def != k_fast and k_fast.startswith("prec:fast|")


# ---------------------------------------------------------------------------
# placement decisions
# ---------------------------------------------------------------------------


def _big_expr(mesh8, n=1024):
    from matrel_tpu.core.blockmatrix import BlockMatrix
    A = BlockMatrix.random((n, n), mesh=mesh8, seed=0)
    B = BlockMatrix.random((n, n), mesh=mesh8, seed=1)
    return A.expr().multiply(B.expr())


class TestPlacement:
    def test_effective_dcn_weight(self):
        assert placement_lib.effective_dcn_weight((1.0, 1.0)) \
            == mesh_lib.DCN_AXIS_WEIGHT
        assert placement_lib.effective_dcn_weight((1.0, 1.5)) == 1.5
        assert placement_lib.effective_dcn_weight((8.0, 1.0)) == 8.0
        # a calibrated fast-DCN fabric (weights <= 1.0) is still a
        # calibration — the config contract says anything != (1.0,
        # 1.0) overrides detection, so the cut bills at the measured
        # weight, not the 8x default
        assert placement_lib.effective_dcn_weight((1.0, 0.9)) == 1.0
        assert placement_lib.effective_dcn_weight((0.5, 0.5)) == 0.5

    def test_decision_flips_with_axis_weights(self, mesh8):
        """The acceptance flip: a compute-heavy query SPANS when the
        calibrated weights say the cut is cheap, and stays
        slice-local when the DCN weight makes crossing expensive."""
        cfg = MatrelConfig(fleet_slices=2)
        e = _big_expr(mesh8)
        kw = dict(total_devices=8, slice_devices=4,
                  slice_loads={0: 0, 1: 0}, backend="cpu",
                  eligible=True)
        cheap = placement_lib.decide(e, cfg, (1.0, 1.5), **kw)
        dear = placement_lib.decide(e, cfg, (1.0, 8.0), **kw)
        assert cheap.mode == "span" and cheap.reason == "cost"
        assert dear.mode == "slice" and dear.reason == "cost"

    def test_uniform_weights_price_virtual_cut_as_dcn(self, mesh8):
        # no calibration, no detected boundary: the fleet partition
        # still IS a boundary — small queries stay slice-local
        cfg = MatrelConfig(fleet_slices=2)
        sess = MatrelSession(mesh=mesh8, config=cfg)
        e = sess.from_numpy(np.eye(64, dtype=np.float32)).expr() \
            .multiply(sess.from_numpy(
                np.eye(64, dtype=np.float32)).expr())
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.0), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, eligible=True)
        assert dec.mode == "slice"

    def test_pinned_when_ineligible(self, mesh8):
        cfg = MatrelConfig(fleet_slices=2)
        e = _big_expr(mesh8, n=64)
        dec = placement_lib.decide(
            e, cfg, (1.0, 8.0), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, eligible=False)
        assert dec.mode == "span" and dec.reason == "pinned"

    def test_least_loaded_slice_wins(self, mesh8):
        cfg = MatrelConfig(fleet_slices=2)
        e = _big_expr(mesh8, n=64)
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.0), total_devices=8, slice_devices=4,
            slice_loads={0: 5, 1: 0}, eligible=True)
        assert dec.slice_id == 1

    def test_round_robin_tie_break(self, mesh8):
        cfg = MatrelConfig(fleet_slices=2)
        e = _big_expr(mesh8, n=64)
        kw = dict(total_devices=8, slice_devices=4,
                  slice_loads={0: 0, 1: 0}, eligible=True)
        ids = [placement_lib.decide(e, cfg, (1.0, 1.0), rr_tick=t,
                                    **kw).slice_id
               for t in range(4)]
        assert ids == [0, 1, 0, 1]

    def test_stamp_carries_the_billed_dcn_weight(self, mesh8):
        cfg = MatrelConfig(fleet_slices=2)
        e = _big_expr(mesh8, n=64)
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.5), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, eligible=True)
        st = dec.stamp()
        assert st["dcn_weight"] == 1.5
        assert st["weights"] == [1.0, 1.5]
        # KEY-STABLE fields only: the stamp feeds the plan/result
        # cache structural keys, so drift-sensitive fields (the
        # estimates, coeff_source) must never ride it — they would
        # shatter every span query's cache keys on a drift-table
        # update (the brownout-rung plan-key-shatter class)
        assert set(st) == {"mode", "weights", "dcn_axis",
                           "dcn_weight"}

    def test_span_margin_biases_toward_slices(self, mesh8):
        e = _big_expr(mesh8)
        kw = dict(total_devices=8, slice_devices=4,
                  slice_loads={0: 0, 1: 0}, eligible=True)
        neutral = placement_lib.decide(
            e, MatrelConfig(fleet_slices=2), (1.0, 1.5), **kw)
        strict = placement_lib.decide(
            e, MatrelConfig(fleet_slices=2, fleet_span_margin=0.1),
            (1.0, 1.5), **kw)
        assert neutral.mode == "span" and strict.mode == "slice"


# ---------------------------------------------------------------------------
# drift-calibrated coefficients (the feedback-loop satellite)
# ---------------------------------------------------------------------------


def _seed_drift_table(path, cls="<=1024", backend="cpu",
                      strategy="rmm", gflop=50.0, mib=2.0, count=4):
    table = {"schema": 1, "entries": {
        f"{strategy}|{cls}|{backend}": {
            "strategy": strategy, "class": cls, "backend": backend,
            "count": count, "ms_median": 1.0,
            "ms_per_gflop": gflop, "ms_per_est_mib": mib}}}
    with open(path, "w") as f:
        json.dump(table, f)


class TestPlacementCalibration:
    @pytest.fixture(autouse=True)
    def _fresh_cache(self):
        placement_lib.reset_coefficient_cache()
        yield
        placement_lib.reset_coefficient_cache()

    def test_promotes_rows_per_class_backend_tier(self, tmp_path):
        p = str(tmp_path / "drift.json")
        table = {"schema": 1, "entries": {
            "rmm|<=1024|cpu": {
                "strategy": "rmm", "class": "<=1024",
                "backend": "cpu", "count": 3, "ms_median": 1.0,
                "ms_per_gflop": 10.0, "ms_per_est_mib": 1.0},
            "cpmm|<=1024|cpu": {
                "strategy": "cpmm", "class": "<=1024",
                "backend": "cpu", "count": 1, "ms_median": 1.0,
                "ms_per_gflop": 50.0, "ms_per_est_mib": 5.0},
            "rmm@bf16x1|<=1024|cpu": {
                "strategy": "rmm@bf16x1", "class": "<=1024",
                "backend": "cpu", "count": 2, "ms_median": 1.0,
                "ms_per_gflop": 4.0, "ms_per_est_mib": 0.5},
        }}
        with open(p, "w") as f:
            json.dump(table, f)
        coeffs = placement_lib.placement_coefficients(p)
        # untier rows blend count-weighted: (10*3 + 50*1) / 4 = 20
        row = coeffs[("<=1024", "cpu", "")]
        assert row["ms_per_gflop"] == pytest.approx(20.0)
        assert row["ms_per_mib"] == pytest.approx(2.0)
        assert row["source"] == "measured"
        # tiered rows promote under their own tier key
        tier = coeffs[("<=1024", "cpu", "bf16x1")]
        assert tier["ms_per_gflop"] == pytest.approx(4.0)

    def test_decide_consults_measured_ahead_of_closed_forms(
            self, mesh8, tmp_path):
        p = str(tmp_path / "drift.json")
        _seed_drift_table(p, cls="<=1024")
        cfg = MatrelConfig(fleet_slices=2, drift_table_path=p)
        e = _big_expr(mesh8)         # max dim 1024 -> class <=1024
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.5), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, backend="cpu", eligible=True)
        assert dec.coeff_source == "measured"
        # the measured ms/GFLOP (50x the analytic 1.0) scales the
        # compute term: the estimates must reflect it
        assert dec.est_slice_ms > 10.0

    def test_cold_class_falls_back_to_analytic(self, mesh8,
                                               tmp_path):
        p = str(tmp_path / "drift.json")
        _seed_drift_table(p, cls="<=64")      # wrong shape class
        cfg = MatrelConfig(fleet_slices=2, drift_table_path=p)
        e = _big_expr(mesh8)
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.5), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, backend="cpu", eligible=True)
        assert dec.coeff_source == "analytic"

    def test_calibration_gate_off(self, mesh8, tmp_path):
        p = str(tmp_path / "drift.json")
        _seed_drift_table(p, cls="<=1024")
        cfg = MatrelConfig(fleet_slices=2, drift_table_path=p,
                           fleet_placement_calibration=False)
        e = _big_expr(mesh8)
        dec = placement_lib.decide(
            e, cfg, (1.0, 1.5), total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, backend="cpu", eligible=True)
        assert dec.coeff_source == "analytic"

    def test_absent_table_reads_empty(self, tmp_path):
        assert placement_lib.placement_coefficients(
            str(tmp_path / "nope.json")) == {}


# ---------------------------------------------------------------------------
# the fleet serve plane, end to end
# ---------------------------------------------------------------------------


class TestFleetServe:
    def test_submit_routes_to_slices_and_answers_correctly(
            self, mesh8, rng):
        sess, mats = _fleet_session(mesh8, rng)
        futs = [sess.submit(_q(sess).multiply_scalar(float(i + 1)))
                for i in range(4)]
        outs = [f.result(timeout=60) for f in futs]
        oracle = mats["A"] @ mats["B"]
        for i, o in enumerate(outs):
            np.testing.assert_allclose(np.asarray(o.to_numpy()),
                                       oracle * (i + 1), rtol=2e-4,
                                       atol=2e-4)
        info = sess.fleet_info()
        assert info["placed"]["slice"] == 4
        assert {sl["id"] for sl in info["slices"]} == {0, 1}
        sess.serve_close()

    def test_directory_hit_anywhere_answers_without_recompute(
            self, mesh8, rng):
        sess, mats = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        out1 = sess.submit(q).result(timeout=60)
        sess.serve_drain()
        assert fleet.directory.info()["entries"] == 1
        before = {sl.slice_id: sl.submitted for sl in fleet.slices}
        # the second submission's placement (round-robin) prefers the
        # NON-owning slice — the directory answers from the owner's
        # cache anyway, and no slice pipeline sees the query at all
        out2 = sess.submit(q).result(timeout=60)
        np.testing.assert_allclose(np.asarray(out2.to_numpy()),
                                   np.asarray(out1.to_numpy()))
        after = {sl.slice_id: sl.submitted for sl in fleet.slices}
        assert after == before          # zero recompute, zero routing
        d = fleet.directory.info()
        assert d["hits"] == 1 and d["remote_hits"] == 1
        sess.serve_close()

    def test_working_set_over_one_slice_replays_from_the_fleet(
            self, mesh8, rng):
        """Seven distinct queries against a per-slice cache budget of
        0.6x their working set: one slice would thrash, two slices hold
        it between them, and the replays answer at the directory's
        front door — no slice pipeline sees them, and the odd stream
        length makes some of those hits remote. Then slice 0 is killed
        mid-stream: every future resolves right or with a typed error."""
        from matrel_tpu.resilience.errors import ResilienceError
        n, n_q = 192, 7
        sess, mats = _fleet_session(
            mesh8, rng, n=n, serve_max_batch=1,
            result_cache_max_bytes=int(0.6 * n_q * n * n * 4))
        fleet = sess._ensure_fleet()
        qs = [_q(sess).multiply_scalar(1.0 + 0.5 * i)
              for i in range(n_q)]
        oracle = mats["A"] @ mats["B"]

        def replay():
            return [f.result(timeout=60)
                    for f in [sess.submit(q) for q in qs]]

        replay()
        sess.serve_drain()
        before = {sl.slice_id: sl.submitted for sl in fleet.slices}
        for _ in range(2):
            for i, o in enumerate(replay()):
                np.testing.assert_allclose(
                    np.asarray(o.to_numpy()), oracle * (1.0 + 0.5 * i),
                    rtol=2e-3, atol=2e-3)
        sess.serve_drain()
        assert {sl.slice_id: sl.submitted
                for sl in fleet.slices} == before
        assert fleet.directory.info()["remote_hits"] >= 1

        futs = []
        for r in range(3):
            for i, q in enumerate(qs):
                futs.append((i, sess.submit(q)))
                if r == 1 and i == n_q // 2:
                    fleet.kill_slice(0)
        completed = typed = 0
        for i, f in futs:
            try:
                got = np.asarray(f.result(timeout=60).to_numpy())
            except ResilienceError:
                typed += 1
                continue
            np.testing.assert_allclose(got, oracle * (1.0 + 0.5 * i),
                                       rtol=2e-3, atol=2e-3)
            completed += 1
        assert completed > 0 and completed + typed == len(futs)
        assert sess.fleet_info()["failovers"] == 1
        sess.serve_close()

    def test_slice_local_miss_recomputes_and_records_ownership(
            self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q1 = _q(sess)
        q2 = _q(sess).multiply_scalar(2.0)
        sess.submit(q1).result(timeout=60)
        sess.serve_drain()
        # a DIFFERENT query misses the directory and recomputes on
        # its placed slice, recording new ownership
        sess.submit(q2).result(timeout=60)
        sess.serve_drain()
        d = fleet.directory.info()
        assert d["entries"] == 2 and d["misses"] >= 2
        sess.serve_close()

    def test_migration_replicates_hot_entry_under_budget(
            self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng, fleet_replicate_hits=1)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        owner = fleet.directory.lookup(
            placement_lib.fleet_key(q, fleet._names)).owner
        # remote hit crosses the replication threshold -> the entry
        # replicates into the demanding slice (off-thread, so the hit
        # fast path never pays the copy — quiesce before asserting)
        sess.submit(q).result(timeout=60)
        fleet.quiesce_replication(timeout=30)
        assert fleet.migrations == 1
        rec = fleet.directory.lookup(
            placement_lib.fleet_key(q, fleet._names))
        other = 1 - owner
        assert other in rec.replicas
        repl_sess = fleet.slice_by_id(other).session
        assert repl_sess._result_cache.info()["entries"] >= 1
        # replica-side provenance: the entry carries the fleet stamp
        ent = repl_sess._result_cache.lookup(rec.replicas[other])
        assert ent is not None and ent.fleet["owner"] == owner
        # the NEXT remote ask is served by the replica, locally
        sess.submit(q).result(timeout=60)
        sess.submit(q).result(timeout=60)
        fleet.quiesce_replication(timeout=30)
        assert fleet.migrations == 1      # no re-migration
        sess.serve_close()

    def test_migration_priced_out_by_peak_budget(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng, fleet_replicate_hits=1,
                                 reshard_peak_budget_bytes=64)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fkey = placement_lib.fleet_key(q, fleet._names)
        rec = fleet.directory.lookup(fkey)
        owner_sess = fleet.slice_by_id(rec.owner).session
        ent = owner_sess._result_cache.lookup(rec.owner_key)
        # a sharded 1 GiB entry cannot gather under a 64-byte peak
        # budget: the migration prices out and nothing is inserted
        big = dataclasses.replace(rec, nbytes=1 << 30, layout="2d")
        target = fleet.slice_by_id(1 - rec.owner)
        fleet._replicate_entry(q, fkey, big, ent, "default", target)
        assert fleet.migrations == 0
        assert fleet.migrations_priced_out == 1
        # review-round regression: the verdict memoizes on the live
        # record — later remote hits must not re-run the reshard
        # pricing (and emit one priced-out event each) forever on
        # exactly the hottest keys
        live_rec = fleet.directory.lookup(fkey)
        assert target.slice_id in live_rec.priced_out
        live_rec.hits[target.slice_id] = 99
        fleet._maybe_replicate(q, fkey, live_rec, ent, "default",
                               target)
        fleet.quiesce_replication(timeout=30)
        assert fleet.migrations_priced_out == 1
        sess.serve_close()

    def test_replication_disabled_at_zero(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng, fleet_replicate_hits=0)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        for _ in range(4):
            sess.submit(q).result(timeout=60)
            sess.serve_drain()
        assert fleet.migrations == 0
        sess.serve_close()


class TestFailover:
    def test_kill_slice_requeues_with_futures_intact(self, mesh8,
                                                     rng):
        sess, mats = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        sl = fleet.slices[0]
        pipe = sl.session._ensure_serve()
        oracle = mats["A"] @ mats["B"]
        # queue entries directly (worker not started — exactly the
        # wedged-slice shape), then kill: every future must resolve
        # through a SURVIVOR
        futs = []
        for i in range(3):
            fut = Future()
            e = fleet._rebind(
                _q(sess).multiply_scalar(float(i + 1)), sl)
            pipe._q.put((e, fut, time.perf_counter(), "default",
                         None, "tenantA", None), "tenantA")
            futs.append(fut)
        requeued = fleet.kill_slice(0)
        assert requeued == 3
        assert not fleet.slices[0].alive
        sess.serve_drain()
        for i, f in enumerate(futs):
            out = f.result(timeout=60)
            np.testing.assert_allclose(np.asarray(out.to_numpy()),
                                       oracle * (i + 1), rtol=2e-4,
                                       atol=2e-4)
        assert fleet.failovers == 1 and fleet.requeued == 3
        sess.serve_close()

    def test_failover_preserves_tenant_attribution(self, mesh8, rng):
        sess, _ = _fleet_session(
            mesh8, rng, serve_tenant_weights="tenantA:2,tenantB:1")
        fleet = sess._ensure_fleet()
        sl = fleet.slices[0]
        pipe = sl.session._ensure_serve()
        fut = Future()
        e = fleet._rebind(_q(sess), sl)
        pipe._q.put((e, fut, time.perf_counter(), "default", None,
                     "tenantA", None), "tenantA")
        # hold the survivor's worker so the requeued entry is
        # observable in its queue (the worker would otherwise pop it
        # before the assert). NOT by flipping _closed — readmission
        # now refuses typed on a closed pipeline (the stranding fix);
        # stub the worker-ensure instead.
        target = fleet.slices[1].session._ensure_serve()
        target._ensure_worker = lambda: None
        fleet.kill_slice(0)
        # the survivor's queue sees the entry under the SAME tenant
        assert target._q.tenant_depths().get("tenantA", 0) == 1
        del target._ensure_worker
        target._ensure_worker()
        sess.serve_drain()
        assert fut.result(timeout=60) is not None
        sess.serve_close()

    def test_expired_entry_fails_typed_on_failover(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        sl = fleet.slices[0]
        pipe = sl.session._ensure_serve()
        fut = Future()
        dl = Deadline(0.01)
        time.sleep(0.005)
        e = fleet._rebind(_q(sess), sl)
        pipe._q.put((e, fut, time.perf_counter(), "default", dl, "",
                     None), "")
        time.sleep(0.02)            # expire while queued
        fleet.kill_slice(0)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=10)
        sess.serve_close()

    def test_failover_disabled_fails_typed(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng, fleet_failover=False)
        fleet = sess._ensure_fleet()
        sl = fleet.slices[0]
        pipe = sl.session._ensure_serve()
        fut = Future()
        e = fleet._rebind(_q(sess), sl)
        pipe._q.put((e, fut, time.perf_counter(), "default", None,
                     "", None), "")
        fleet.kill_slice(0)
        with pytest.raises(FleetSliceLost):
            fut.result(timeout=10)
        sess.serve_close()

    def test_no_survivors_is_typed(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        fleet.kill_slice(0)
        fleet.kill_slice(1)
        fut = sess.submit(_q(sess))
        with pytest.raises(FleetSliceLost):
            fut.result(timeout=10)

    def test_wedged_worker_detected_on_submit(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        sl = fleet.slices[0]
        # start, then stop, the worker — and erase the stop flag so
        # the dead thread looks like a crash, not a shutdown
        sess.submit(_q(sess)).result(timeout=60)
        sess.serve_drain()
        pipe = sl.session._serve
        if pipe is None:        # placement sent it to slice 1
            sl = fleet.slices[1]
            pipe = sl.session._serve
        pipe._stop.set()
        pipe._worker.join(timeout=10)
        assert not pipe._worker.is_alive()
        pipe._stop.clear()
        fut = Future()
        e = fleet._rebind(_q(sess).multiply_scalar(3.0), sl)
        pipe._q.put((e, fut, time.perf_counter(), "default", None,
                     "", None), "")
        fleet.check_health()
        assert not sl.alive and fleet.failovers == 1
        sess.serve_drain()
        assert fut.result(timeout=60) is not None
        sess.serve_close()

    def test_dead_slice_directory_records_drop(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fkey = placement_lib.fleet_key(q, fleet._names)
        rec = fleet.directory.lookup(fkey)
        fleet.kill_slice(rec.owner)
        assert fleet.directory.lookup(fkey) is None
        # the query still answers — recomputed on the survivor
        out = sess.submit(q).result(timeout=60)
        assert out is not None
        sess.serve_close()

    def test_readmit_into_closed_survivor_fails_typed(self, mesh8,
                                                      rng):
        # review-round regression: re-admission must go through the
        # pipeline's atomic closed-check + enqueue + worker-ensure
        # seam — a survivor whose pipeline a concurrent close() just
        # flipped refuses TYPED instead of stranding the stolen
        # future in a closed, workerless queue
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        dead = fleet.slice_by_id(0)
        dead.alive = False
        fleet.slice_by_id(1).session._ensure_serve().close(timeout=30)
        fut = Future()
        rebound = fleet._rebind(q, dead)
        entry = (rebound, fut, time.perf_counter(), "default", None,
                 "", None)
        assert fleet._readmit([(entry, "")], dead) == 0
        with pytest.raises(FleetSliceLost):
            fut.result(timeout=5)
        sess.serve_close()

    def test_replica_eviction_falls_back_to_owner(self, mesh8, rng):
        # review-round regression: an evicted REPLICA only loses its
        # own claim — the owner's still-valid copy keeps answering
        # and the directory record survives (no evict/recompute/
        # re-replicate churn on exactly the hottest entries)
        sess, _ = _fleet_session(mesh8, rng, fleet_replicate_hits=1)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fkey = placement_lib.fleet_key(q, fleet._names)
        sess.submit(q).result(timeout=60)    # remote hit -> replicate
        fleet.quiesce_replication(timeout=30)
        rec = fleet.directory.lookup(fkey)
        (repl_id, repl_key), = list(rec.replicas.items())
        fleet.slice_by_id(repl_id).session._result_cache.drop(repl_key)
        # the probe below is itself a remote hit: with replication
        # still armed it would spawn a re-replication that races the
        # claim-dropped assertion (re-claiming is CORRECT sustained-
        # demand behavior — just not what this test measures)
        fleet.config = dataclasses.replace(fleet.config,
                                           fleet_replicate_hits=0)
        before = fleet.directory.info()["invalidated"]
        hit = fleet._directory_answer(q, fkey, "default", repl_id)
        assert hit is not None          # served by the OWNER's copy
        rec2 = fleet.directory.lookup(fkey)
        assert rec2 is not None         # record kept
        assert repl_id not in rec2.replicas   # claim dropped
        assert fleet.directory.info()["invalidated"] == before
        sess.serve_close()


class TestCatalogWriteThrough:
    def test_idempotent_reregister_is_a_fleet_noop(self, mesh8, rng):
        # review-round regression: re-registering the SAME object is
        # a no-op on the single-controller path (the `old is not
        # matrix` guard) and must be one on the fleet path too — the
        # unconditional hook wiped the directory and every slice
        # cache and re-replicated the table on every no-op call
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        d0 = fleet.directory.info()
        assert d0["entries"] >= 1
        gen0 = fleet.directory.reg_gen
        sess.register("A", sess.catalog["A"])     # same object
        assert fleet.directory.reg_gen == gen0
        d1 = fleet.directory.info()
        assert d1["entries"] == d0["entries"]
        assert d1["invalidated"] == d0["invalidated"]
        sess.serve_close()

    def test_unreplicable_table_pins_up_front(self, mesh8, rng):
        # review-round regression: a table NO slice can replicate
        # (sparse/COO on real sub-meshes, failed host stage) must not
        # stay in the fleet's name map — name-mapped, every query
        # over it was fleet-ELIGIBLE, routed to a slice, and bounced
        # through the KeyError fallback per submit forever (recorded
        # as the transient "fallback" reason, never in the pinned
        # census). Unmapped, fleet_key is None and placement pins to
        # the full mesh before any routing.
        from matrel_tpu.core.coo import COOMatrix
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        coo = COOMatrix.from_edges(
            np.array([0, 1, 2]), np.array([1, 2, 0]),
            np.ones(3, dtype=np.float32), shape=(64, 64))
        sess.register("S", coo)
        assert id(coo) not in fleet._names
        e = coo.expr().multiply(sess.table("B").expr())
        assert placement_lib.fleet_key(e, fleet._names) is None
        pinned0 = fleet.pinned
        out = sess.submit(e).result(timeout=60)
        assert fleet.pinned == pinned0 + 1
        assert np.asarray(out.to_numpy()).shape == (64, 64)
        sess.serve_close()

    def test_register_replicates_and_invalidates(self, mesh8, rng):
        sess, mats = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        out1 = sess.submit(q).result(timeout=60)
        sess.serve_drain()
        assert fleet.directory.info()["entries"] == 1
        # rebind A: slice replicas refresh, directory records naming
        # A drop, and the SAME query recomputes against the new value
        newA = rng.standard_normal((64, 64)).astype(np.float32)
        sess.register("A", sess.from_numpy(newA))
        assert fleet.directory.info()["entries"] == 0
        for sl in fleet.slices:
            assert "A" in sl.session.catalog
        q2 = _q(sess)
        out2 = sess.submit(q2).result(timeout=60)
        np.testing.assert_allclose(np.asarray(out2.to_numpy()),
                                   newA @ mats["B"], rtol=2e-4,
                                   atol=2e-4)
        assert not np.allclose(np.asarray(out1.to_numpy()),
                               np.asarray(out2.to_numpy()))
        sess.serve_close()

    def test_rebind_invalidates_directory_before_replication(
            self, mesh8, rng):
        # review-round regression: on_register must drop the stale
        # directory records BEFORE _replicate maps the new matrix id
        # to the name — from that mapping onward a concurrent submit
        # built from the new binding resolves the old record's fleet
        # key, and a still-live record would answer it with the OLD
        # value (lookups don't take the controller lock)
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        assert fleet.directory.info()["entries"] == 1
        seen = {}
        orig = fleet._replicate

        def spy(name, matrix):
            seen["entries"] = fleet.directory.info()["entries"]
            seen["gen"] = fleet.directory.reg_gen
            return orig(name, matrix)

        gen0 = fleet.directory.reg_gen
        fleet._replicate = spy
        try:
            newA = rng.standard_normal((64, 64)).astype(np.float32)
            sess.register("A", sess.from_numpy(newA))
        finally:
            fleet._replicate = orig
        assert seen == {"entries": 0, "gen": gen0 + 1}
        sess.serve_close()


class TestDirectoryHygiene:
    def test_no_ownership_record_when_slice_insert_declined(
            self, mesh8, rng):
        # review-round regression: when the slice did NOT cache under
        # the routing-time key (budget-declined insert here; brownout
        # downshift re-keying in production) the fleet must not
        # record ownership — a dead record would churn
        # (lookup-miss -> drop -> recompute -> re-insert) on every
        # repeat
        cfg = MatrelConfig(fleet_slices=2,
                           result_cache_max_bytes=1024)  # < one result
        sess = MatrelSession(mesh=mesh8, config=cfg)
        _mk(sess, np.random.default_rng(0))
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fleet = sess._ensure_fleet()
        assert fleet.directory.info()["inserts"] == 0
        sess.serve_close()

    def test_close_tears_down_killed_slices(self, mesh8, rng):
        # review-round regression: serve_close must close EVERY
        # slice — a killed slice's session (stopped worker, stolen
        # queue) was skipped, leaving its pipeline/inflight state
        # held for the life of the parent
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fleet.kill_slice(0)
        sess.serve_close(timeout=30)
        for sl in fleet.slices:
            pipe = sl.session._serve
            if pipe is not None:
                assert pipe.closed
                assert pipe._stop.is_set()
                if pipe._worker is not None:
                    # close() signals the worker and returns; the
                    # daemon exits on its next poll tick — join
                    # bounded before asserting it is gone
                    pipe._worker.join(timeout=10)
                    assert not pipe._worker.is_alive()

    def test_close_sweeps_past_a_wedged_slice(self, mesh8, rng):
        # review-round regression: one wedged live slice's
        # DrainTimeout aborted the teardown loop — later slices'
        # workers and the parent pipeline stayed open and the metrics
        # exporter was never stopped (the EADDRINUSE class the
        # exporter-lifecycle fix exists for). Every slice must be
        # closed, then the first live failure propagates.
        from matrel_tpu.resilience.errors import DrainTimeout
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        sess.submit(_q(sess)).result(timeout=60)
        sess.serve_drain()
        boom = DrainTimeout(0.0, 1)

        def wedge(timeout=None):
            raise boom

        fleet.slices[0].session.serve_close = wedge
        stopped = []
        if sess._exporter is None:
            class _Exp:
                def stop(self):
                    stopped.append(True)
            sess._exporter = _Exp()
        with pytest.raises(DrainTimeout):
            sess.serve_close(timeout=30)
        assert stopped == [True]          # exporter stopped anyway
        other = fleet.slices[1].session._serve
        assert other is None or other.closed   # sweep continued
        parent = sess._serve
        assert parent is None or parent.closed
        sess._exporter = None

    def test_drain_covers_killed_slices(self, mesh8, rng):
        # review-round regression: kill_slice steals only QUEUED
        # entries — a batch the worker already pulled keeps executing,
        # and serve_drain's "every in-flight batch has materialised"
        # contract must wait for it. drain skipped dead slices, so
        # those futures could still be unresolved when it returned.
        sess, _ = _fleet_session(mesh8, rng)
        fleet = sess._ensure_fleet()
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        fleet.kill_slice(0)
        drained = []
        for sl in fleet.slices:
            orig = sl.session.serve_drain
            sl.session.serve_drain = (
                lambda timeout=None, _i=sl.slice_id, _o=orig:
                (drained.append(_i), _o(timeout=timeout))[1])
        sess.serve_drain(timeout=30)
        assert set(drained) == {sl.slice_id for sl in fleet.slices}
        # live slices drain first: a wedged corpse must not eat the
        # shared budget before the live fleet has drained
        dead = {sl.slice_id for sl in fleet.slices if not sl.alive}
        assert all(i in dead for i in drained[-len(dead):])
        sess.serve_close()


class TestDirectoryBounds:
    def test_lru_eviction_at_max(self):
        d = FleetDirectory(2)
        for i in range(3):
            d.record_insert(f"k{i}", DirectoryRecord(
                owner=0, owner_key=f"lk{i}", nbytes=8,
                layout="rep", dtype="float32",
                dep_names=frozenset({"A"})))
        assert d.info()["entries"] == 2
        assert d.info()["evicted"] == 1
        assert d.lookup("k0") is None        # oldest evicted

    def test_invalidate_by_name(self):
        d = FleetDirectory(8)
        d.record_insert("k1", DirectoryRecord(
            owner=0, owner_key="a", nbytes=8, layout="rep",
            dtype="float32", dep_names=frozenset({"A"})))
        d.record_insert("k2", DirectoryRecord(
            owner=1, owner_key="b", nbytes=8, layout="rep",
            dtype="float32", dep_names=frozenset({"B"})))
        assert d.invalidate_name("A") == 1
        assert d.lookup("k1") is None and d.lookup("k2") is not None

    def test_claim_replica_refuses_across_generations(self):
        # review-round regression: a migration staged against an
        # old-binding record must not attach its (old-value) replica
        # to a record re-created for the NEW binding after a rebind
        # — the claim carries the staged generation and refuses on a
        # bump (the record_insert expected_gen idiom)
        d = FleetDirectory(8)
        rec = DirectoryRecord(
            owner=0, owner_key="k0", nbytes=8, layout="rep",
            dtype="float32", dep_names=frozenset({"A"}))
        d.record_insert("K", rec)
        staged_gen = d.reg_gen
        d.invalidate_name("A")               # rebind in flight
        d.record_insert("K", DirectoryRecord(
            owner=0, owner_key="k0b", nbytes=8, layout="rep",
            dtype="float32", dep_names=frozenset({"A"})))
        assert not d.claim_replica("K", 1, "k1",
                                   expected_gen=staged_gen)
        assert 1 not in d.lookup("K").replicas
        assert d.claim_replica("K", 1, "k1", expected_gen=d.reg_gen)

    def test_drop_replica_keeps_owner_record(self):
        d = FleetDirectory(8)
        rec = DirectoryRecord(
            owner=0, owner_key="k0", nbytes=8, layout="rep",
            dtype="float32", dep_names=frozenset({"A"}))
        rec.replicas[1] = "k1"
        d.record_insert("K", rec)
        d.drop_replica("K", 1)
        kept = d.lookup("K")
        assert kept is not None and 1 not in kept.replicas
        assert d.info()["invalidated"] == 0


# ---------------------------------------------------------------------------
# MV114 fixtures
# ---------------------------------------------------------------------------


class TestMV114:
    def _leaf_pair(self, mesh8):
        from matrel_tpu.core.blockmatrix import BlockMatrix
        A = BlockMatrix.random((64, 64), mesh=mesh8, seed=0)
        B = BlockMatrix.random((64, 64), mesh=mesh8, seed=1)
        return A.expr().multiply(B.expr())

    def _run(self, root, mesh8, cfg=None):
        from matrel_tpu.analysis.placement_pass import (
            check_placement_stamps)
        return list(check_placement_stamps(
            root, mesh8, cfg or MatrelConfig()))

    def test_registered_in_pipeline(self):
        from matrel_tpu import analysis
        assert any(name == "placement" for name, _ in analysis.PASSES)

    def test_stale_weights_flagged(self, mesh8):
        e = self._leaf_pair(mesh8).with_attrs(placement={
            "mode": "span", "weights": [1.0, 2.0], "dcn_axis": 1,
            "dcn_weight": 2.0})
        got = self._run(e, mesh8)
        assert any(d.code == "MV114" and "topology" in d.message
                   for d in got)

    def test_unpriced_cut_flagged(self, mesh8):
        # the stamp's own weights derive an effective DCN weight of
        # 1.5 — billing the cut at 1.0 means the dominant collective
        # was NOT priced on the DCN axis weight
        cfg = MatrelConfig(axis_cost_weights=(1.0, 1.5))
        e = self._leaf_pair(mesh8).with_attrs(placement={
            "mode": "span", "weights": [1.0, 1.5], "dcn_axis": 1,
            "dcn_weight": 1.0})
        got = self._run(e, mesh8, cfg)
        assert any(d.code == "MV114" and "DCN axis weight"
                   in d.message for d in got)

    def test_fresh_span_stamp_quiet(self, mesh8):
        cfg = MatrelConfig(fleet_slices=2,
                           axis_cost_weights=(1.0, 1.5))
        e = self._leaf_pair(mesh8)
        dec = placement_lib.decide(
            e, cfg, mesh_lib.axis_weights(mesh8, cfg),
            total_devices=8, slice_devices=4,
            slice_loads={0: 0, 1: 0}, eligible=True)
        stamped = e.with_attrs(placement=dec.stamp())
        assert self._run(stamped, mesh8, cfg) == []

    def test_slice_mode_stamp_not_checked(self, mesh8):
        e = self._leaf_pair(mesh8).with_attrs(placement={
            "mode": "slice", "weights": [9.0, 9.0]})
        assert self._run(e, mesh8) == []

    def test_replica_dtype_divergence_flagged(self, mesh8):
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as expr_mod
        M = BlockMatrix.random((64, 64), mesh=mesh8, seed=0)
        leaf = expr_mod.leaf(M).with_attrs(result_cache={
            "key_hash": "x", "layout": "rep", "dtype": "float32",
            "deps": [],
            "fleet": {"owner": 0, "layout": "rep",
                      "dtype": "float64"}})
        got = self._run(leaf.t(), mesh8)
        assert any(d.code == "MV114" and "dtype" in d.message
                   for d in got)

    def test_replica_coherent_stamp_quiet(self, mesh8):
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir import expr as expr_mod
        M = BlockMatrix.random((64, 64), mesh=mesh8, seed=0)
        leaf = expr_mod.leaf(M).with_attrs(result_cache={
            "key_hash": "x", "layout": "rep", "dtype": "float32",
            "deps": [],
            "fleet": {"owner": 0, "layout": "rep",
                      "dtype": "float32"}})
        assert self._run(leaf.t(), mesh8) == []

    def test_end_to_end_span_plan_verifies_clean(self, mesh8, rng):
        # a REAL fleet span submission compiles under
        # verify_plans="error" with MV114 in the pipeline: the stamp
        # the placer writes must satisfy its own verifier
        cfg = MatrelConfig(fleet_slices=2, verify_plans="error",
                           result_cache_max_bytes=1 << 28)
        sess = MatrelSession(mesh=mesh8, config=cfg)
        _mk(sess, rng, n=64)
        adhoc = sess.from_numpy(
            rng.standard_normal((64, 64)).astype(np.float32))
        # an ad-hoc leaf pins the query to the span path
        e = sess.table("A").expr().multiply(adhoc.expr())
        out = sess.submit(e).result(timeout=60)
        assert out is not None
        assert sess.fleet_info()["placed"]["span"] >= 1
        sess.serve_close()


# ---------------------------------------------------------------------------
# default-config bit-identity
# ---------------------------------------------------------------------------


class TestFleetOffBitIdentity:
    def test_zero_fleet_objects_poisoned_init(self, mesh8, rng,
                                              monkeypatch):
        def poisoned(self, *a, **k):
            raise AssertionError(
                "fleet object constructed with fleet_slices=0")
        monkeypatch.setattr(FleetController, "__init__", poisoned)
        monkeypatch.setattr(FleetDirectory, "__init__", poisoned)
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig())
        mats = _mk(sess, rng, n=32)
        out = sess.run(_q(sess))
        np.testing.assert_allclose(np.asarray(out.to_numpy()),
                                   mats["A"] @ mats["B"], rtol=2e-4,
                                   atol=2e-4)
        fut = sess.submit(_q(sess).multiply_scalar(2.0))
        assert fut.result(timeout=60) is not None
        sess.serve_drain()
        assert sess._fleet is None
        assert sess.fleet_info() is None
        sess.serve_close()

    def test_fleet_lazy_until_first_submit(self, mesh8, rng):
        sess, _ = _fleet_session(mesh8, rng)
        assert sess._fleet is None        # construction is lazy
        sess.run(_q(sess))                # run() never builds it
        assert sess._fleet is None
        sess.submit(_q(sess)).result(timeout=60)
        assert sess._fleet is not None
        sess.serve_close()

    def test_config_validation(self):
        with pytest.raises(ValueError):
            MatrelConfig(fleet_slices=-1)
        with pytest.raises(ValueError):
            MatrelConfig(fleet_span_margin=0)
        with pytest.raises(ValueError):
            MatrelConfig(fleet_directory_max=0)
        with pytest.raises(ValueError):
            MatrelConfig(fleet_replicate_hits=-1)


# ---------------------------------------------------------------------------
# obs surfaces
# ---------------------------------------------------------------------------


class TestFleetObs:
    def test_placement_events_and_summary(self, mesh8, rng,
                                          tmp_path):
        log = str(tmp_path / "events.jsonl")
        sess, _ = _fleet_session(mesh8, rng, obs_level="on",
                                 obs_event_log=log)
        q = _q(sess)
        sess.submit(q).result(timeout=60)
        sess.serve_drain()
        sess.submit(q).result(timeout=60)     # directory hit
        sess.serve_drain()
        from matrel_tpu.obs.events import read_events
        from matrel_tpu.obs.history import render_summary, summarize
        events = read_events(log)
        placements = [e for e in events
                      if e.get("kind") == "placement"]
        assert len(placements) == 2
        assert placements[0]["routed"] == "slice"
        assert placements[1]["routed"] in ("directory",
                                           "directory_remote")
        assert placements[0]["coeff_source"] in ("analytic",
                                                 "measured")
        # slice sessions tag their own query events
        tagged = [e for e in events if e.get("kind") == "query"
                  and e.get("slice") is not None]
        assert tagged
        s = summarize(events)
        assert s["fleet"]["placements"] == 2
        assert s["fleet"]["slices"]
        text = render_summary(events)
        assert "fleet:" in text
        sess.serve_close()

    def test_fleet_event_on_kill(self, mesh8, rng, tmp_path):
        log = str(tmp_path / "events.jsonl")
        sess, _ = _fleet_session(mesh8, rng, obs_level="on",
                                 obs_event_log=log)
        sess.submit(_q(sess)).result(timeout=60)
        sess.serve_drain()
        sess._fleet.kill_slice(0)
        from matrel_tpu.obs.events import read_events
        evs = [e for e in read_events(log) if e.get("kind") == "fleet"]
        assert any(e.get("event") == "slice_kill" for e in evs)
        sess.serve_close()

    def test_export_snapshot_and_top_show_fleet(self, mesh8, rng):
        from matrel_tpu.obs import export as export_lib
        from matrel_tpu.obs import top as top_lib
        sess, _ = _fleet_session(mesh8, rng)
        sess.submit(_q(sess)).result(timeout=60)
        sess.serve_drain()
        snap = export_lib.snapshot(sess)
        assert snap["fleet"] is not None
        assert len(snap["fleet"]["slices"]) == 2
        text = top_lib.render(snap)
        assert "fleet: 2 slice(s)" in text
        assert "slice 0:" in text and "slice 1:" in text
        sess.serve_close()

    def test_no_fleet_snapshot_is_none(self, mesh8):
        from matrel_tpu.obs import export as export_lib
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig())
        assert export_lib.snapshot(sess)["fleet"] is None


# ---------------------------------------------------------------------------
# registration-plane locking (the LK102 fix: tools/lockcheck.py)
# ---------------------------------------------------------------------------


class TestRegistrationPlaneLocking:
    def test_replicate_runs_outside_controller_lock(self, mesh8, rng):
        """on_register's re-replication (device->host staging per
        table) must NOT run under the controller lock — that hold
        span wedged kill_slice/failover behind a host transfer — but
        MUST still be serialized by the registration lock (two
        rebinds of one name never interleave)."""
        import threading

        sess, mats = _fleet_session(mesh8, rng, n=32)
        try:
            # the fleet builds lazily on first submit
            sess.submit(_q(sess)).result(timeout=60)
            fc = sess._fleet
            orig = fc._replicate
            seen = {}

            def spy(name, matrix):
                # probe from ANOTHER thread: a nonblocking acquire
                # succeeds iff no thread holds the lock
                def probe():
                    free = fc._lock.acquire(blocking=False)
                    if free:
                        fc._lock.release()
                    seen["controller_free"] = free
                    reg_free = fc._reg_lock.acquire(blocking=False)
                    if reg_free:
                        fc._reg_lock.release()
                    seen["reg_held"] = not reg_free

                t = threading.Thread(target=probe, daemon=True)
                t.start()
                t.join(timeout=30)
                return orig(name, matrix)

            fc._replicate = spy
            sess.register("A", sess.from_numpy(mats["A"]))  # rebind
            assert seen == {"controller_free": True,
                            "reg_held": True}
        finally:
            sess.serve_close(timeout=30)

    def test_rebind_storm_with_concurrent_kill(self, mesh8, rng):
        """The schedule the old hold span wedged: kill_slice (takes
        the controller lock) must complete while a rebind's
        replication is in flight, and answers stay right."""
        import threading

        sess, mats = _fleet_session(mesh8, rng, n=32)
        try:
            sess.submit(_q(sess)).result(timeout=60)  # builds the fleet
            oracle = mats["A"] @ mats["B"]
            done = threading.Event()

            def rebinder():
                for _ in range(4):
                    sess.register("A", sess.from_numpy(mats["A"]))
                done.set()

            t = threading.Thread(target=rebinder, daemon=True)
            t.start()
            sess._fleet.kill_slice(0)
            out = sess.submit(_q(sess)).result(timeout=60)
            t.join(timeout=60)
            assert done.is_set(), "rebind storm wedged"
            np.testing.assert_allclose(np.asarray(out.to_numpy()),
                                       oracle, rtol=3e-3, atol=3e-3)
        finally:
            sess.serve_close(timeout=30)
