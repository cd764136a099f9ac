"""Durable fleet (docs/DURABILITY.md): the HBM -> host -> disk result-
cache spill hierarchy, warm restarts across a process-equivalent
session boundary, the corruption discipline (typed SnapshotCorruption
handled as a miss, corrupt snapshots cold-start), the zero-object
default, and the MV117 spill-provenance pass.

The kill-and-restore battery with a REAL process boundary lives in
``tools/soak.py --battery durable``; these are the deterministic unit
tiers under it.
"""

import logging
import os
import types

import numpy as np
import pytest

from matrel_tpu.analysis import spill_pass
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import expr as E
from matrel_tpu.resilience.errors import (CheckpointCorruption,
                                          SnapshotCorruption)
from matrel_tpu.serve import fleet as fleet_lib
from matrel_tpu.serve import mqo as mqo_lib
from matrel_tpu.serve import result_cache as rc_lib
from matrel_tpu.serve import spill as spill_lib
from matrel_tpu.session import MatrelSession

N = 64
ENTRY = N * N * 4               # one 64x64 f32 gram result's device bytes


def _spill_cfg(tmp_path, **over):
    """A config whose HBM budget holds ~1.5 entries, so the second
    insert demotes the first — the hierarchy exercises on two
    queries."""
    cfg = dict(spill_enable=True,
               result_cache_max_bytes=int(1.5 * ENTRY),
               result_cache_max_entries=8,
               spill_host_max_bytes=8 * ENTRY,
               spill_disk_hits=0,
               state_dir=str(tmp_path))
    cfg.update(over)
    return MatrelConfig(**cfg)


def _register(sess, rng, names, integral=False):
    """name -> (BlockMatrix, numpy gram oracle) for registered mats."""
    out = {}
    for nm in names:
        if integral:
            arr = rng.integers(-4, 5, size=(N, N)).astype(np.float32)
        else:
            arr = rng.standard_normal((N, N)).astype(np.float32)
        m = sess.from_numpy(arr)
        sess.register(nm, m)
        out[nm] = (m, arr.T @ arr)
    return out


def _gram(m):
    return m.expr().t().multiply(m.expr())


def _check(sess, mats, name, **tol):
    got = np.asarray(sess.run(_gram(mats[name][0])).data)
    np.testing.assert_allclose(got, mats[name][1],
                               **(tol or dict(rtol=1e-5, atol=1e-4)))


# ---------------------------------------------------------------------------
# Satellite 1 — result_nbytes must never silently size an entry as 0
# ---------------------------------------------------------------------------


class TestResultNbytes:

    def test_foreign_array_falls_back_to_shape_estimate(self, caplog):
        rc_lib._NBYTES_WARNED[0] = False
        bm = types.SimpleNamespace(data=object(), shape=(64, 16))
        with caplog.at_level(logging.WARNING, "matrel_tpu.serve"):
            assert rc_lib.result_nbytes(bm) == 64 * 16 * 4
        assert any("result_nbytes" in r.message for r in caplog.records)

    def test_warns_once_per_process(self, caplog):
        rc_lib._NBYTES_WARNED[0] = False
        bm = types.SimpleNamespace(data=object(), shape=(8, 8))
        with caplog.at_level(logging.WARNING, "matrel_tpu.serve"):
            rc_lib.result_nbytes(bm)
            caplog.clear()
            assert rc_lib.result_nbytes(bm) == 8 * 8 * 4
        assert not any("result_nbytes" in r.message
                       for r in caplog.records)

    def test_dtype_survives_when_only_shape_is_missing(self):
        rc_lib._NBYTES_WARNED[0] = True      # silence; latch unit above
        data = types.SimpleNamespace(dtype=np.dtype("float64"))
        bm = types.SimpleNamespace(data=data, shape=(8, 8))
        assert rc_lib.result_nbytes(bm) == 8 * 8 * 8

    def test_real_blockmatrix_uses_padded_array(self, mesh8, rng):
        arr = rng.standard_normal((N, N)).astype(np.float32)
        bm = BlockMatrix.from_numpy(arr, mesh=mesh8)
        assert rc_lib.result_nbytes(bm) == int(
            np.prod(bm.data.shape)) * 4

    def test_not_a_blockmatrix_at_all_is_zero(self):
        rc_lib._NBYTES_WARNED[0] = True
        bm = types.SimpleNamespace(data=object(), shape=None)
        assert rc_lib.result_nbytes(bm) == 0


# ---------------------------------------------------------------------------
# Tentpole — tier round-trips, demotion order, the expected-reuse gate
# ---------------------------------------------------------------------------


class TestSpillTiers:

    def test_host_round_trip_recomputes_nothing_wrong(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(tmp_path))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")          # evicts a -> host tier
        sp = sess.result_cache_info()["spill"]
        assert sp["demoted_host"] >= 1 and sp["host_entries"] >= 1
        _check(sess, mats, "a")          # promote, not recompute
        sp = sess.result_cache_info()["spill"]
        assert sp["promoted"] >= 1

    def test_disk_round_trip_writes_and_thaws_artifact(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(
            tmp_path, spill_host_max_bytes=1))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")          # a: HBM -> host -> ages to disk
        sp = sess.result_cache_info()["spill"]
        assert sp["demoted_disk"] == 1 and sp["disk_entries"] == 1
        files = os.listdir(os.path.join(str(tmp_path), "spill"))
        assert [f for f in files if f.endswith(".npy")]
        _check(sess, mats, "a")          # disk_read + h2d thaw
        sp = sess.result_cache_info()["spill"]
        assert sp["promoted"] == 1 and sp["corrupt"] == 0
        # re-inserting a evicted b, which cascaded down to disk in
        # a's old slot — the hierarchy stays full, nothing recomputes
        assert sp["demoted_disk"] == 2 and sp["disk_entries"] == 1

    def test_lru_pressure_ages_oldest_entry_deepest(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(
            tmp_path, spill_host_max_bytes=int(1.5 * ENTRY)))
        events = []
        sess._spill.emit = events.append
        mats = _register(sess, rng, ["a", "b", "c"])
        for nm in ("a", "b", "c"):
            _check(sess, mats, nm)
        # a was evicted first, so host pressure aged it to disk; b
        # stayed host-resident
        sp = sess.result_cache_info()["spill"]
        assert sp["disk_entries"] == 1 and sp["host_entries"] == 1
        # a — evicted first — is the one that went deepest: its
        # repeat promotes from DISK (b's would have come from host)
        _check(sess, mats, "a")
        _check(sess, mats, "b")
        tiers = [e["tier"] for e in events if e["op"] == "promote"]
        assert len(tiers) == 2 and tiers[0] == "disk"
        for e in events:
            for leg in e["legs"]:
                assert leg["leg"] in ("d2h", "h2d", "disk_write",
                                      "disk_read")
                assert leg["bytes"] > 0 and leg["ms"] >= 0

    def test_expected_reuse_gate_drops_cold_entries(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(
            tmp_path, spill_host_max_bytes=1, spill_disk_hits=5))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")          # a evicted cold: hits 0 < 5
        sp = sess.result_cache_info()["spill"]
        assert sp["dropped"] >= 1 and sp["disk_entries"] == 0
        assert not os.path.exists(os.path.join(str(tmp_path), "spill"))
        _check(sess, mats, "a")          # recompute stays correct
        assert sess.result_cache_info()["spill"]["promoted"] == 0

    def test_no_state_dir_means_host_only_tiering(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(
            tmp_path, state_dir="", spill_host_max_bytes=1))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")
        sp = sess.result_cache_info()["spill"]
        assert sp["disk_entries"] == 0 and sp["dropped"] >= 1
        with pytest.raises(ValueError):
            sess.save_state()            # nowhere durable to write


# ---------------------------------------------------------------------------
# Tentpole — rebind invalidation cascades into every lower tier
# ---------------------------------------------------------------------------


class TestInvalidation:

    def test_rebind_kills_host_tier_entries(self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(tmp_path))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")          # a's gram now host-resident
        assert sess.result_cache_info()["spill"]["host_entries"] == 1
        arr2 = rng.standard_normal((N, N)).astype(np.float32)
        sess.register("a", sess.from_numpy(arr2))
        assert sess.result_cache_info()["spill"]["host_entries"] == 0

    def test_rebind_kills_disk_tier_and_unlinks_artifact(
            self, mesh8, rng, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(
            tmp_path, spill_host_max_bytes=1))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")
        spill_dir = os.path.join(str(tmp_path), "spill")
        assert len(os.listdir(spill_dir)) == 1
        sess.register("a", sess.from_numpy(
            rng.standard_normal((N, N)).astype(np.float32)))
        assert sess.result_cache_info()["spill"]["disk_entries"] == 0
        assert os.listdir(spill_dir) == []

    def test_rebind_kills_restored_entries_by_name(
            self, mesh8, rng, tmp_path):
        cfg = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess1, rng, ["a", "b"])
        _check(sess1, mats, "a")
        _check(sess1, mats, "b")
        sess1.save_state()
        sess2 = MatrelSession(mesh=mesh8, config=cfg)
        assert sess2.restore()["restored"]
        assert sess2.result_cache_info()["spill"][
            "restored_entries"] == 2
        arr2 = rng.standard_normal((N, N)).astype(np.float32)
        sess2.register("a", sess2.from_numpy(arr2))
        assert sess2.result_cache_info()["spill"][
            "restored_entries"] == 1
        # the rebound name recomputes against the NEW binding...
        got = np.asarray(sess2.run(_gram(sess2.catalog["a"])).data)
        np.testing.assert_allclose(got, arr2.T @ arr2,
                                   rtol=1e-5, atol=1e-4)
        # ...while the untouched name still thaws from the snapshot
        got = np.asarray(sess2.run(_gram(sess2.catalog["b"])).data)
        np.testing.assert_allclose(got, mats["b"][1],
                                   rtol=1e-5, atol=1e-4)
        assert sess2.result_cache_info()["spill"][
            "thawed_restored"] == 1


# ---------------------------------------------------------------------------
# Structural zero — the default config constructs NO spill objects
# ---------------------------------------------------------------------------


class TestDefaultZeroObjects:

    def test_default_config_never_constructs_spill(
            self, mesh8, monkeypatch):
        def _boom(self, session):
            raise AssertionError(
                "SpillManager constructed under a spill-off config")
        monkeypatch.setattr(spill_lib.SpillManager, "__init__", _boom)
        base = spill_lib._CONSTRUCTED["count"]
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig())
        assert sess._spill is None
        cache_only = MatrelSession(mesh=mesh8, config=MatrelConfig(
            result_cache_max_bytes=64 << 20))
        assert cache_only._spill is None
        assert "spill" not in cache_only.result_cache_info()
        assert spill_lib._CONSTRUCTED["count"] == base


# ---------------------------------------------------------------------------
# Tentpole + satellites 2/3 — save_state / restore and corruption
# ---------------------------------------------------------------------------


class TestSaveRestore:

    def test_warm_restart_serves_from_snapshot(
            self, mesh8, rng, tmp_path):
        cfg = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess1, rng, ["a", "b"])
        _check(sess1, mats, "a")
        _check(sess1, mats, "b")
        summary = sess1.save_state()
        assert summary["rc_entries"] == 2 and summary["catalog"] == 2
        sess2 = MatrelSession(mesh=mesh8, config=cfg)
        out = sess2.restore()
        assert out["restored"] and out["rc_entries"] == 2
        assert out["catalog"] == 2
        for nm in ("a", "b"):
            got = np.asarray(
                sess2.run(_gram(sess2.catalog[nm])).data)
            np.testing.assert_allclose(got, mats[nm][1],
                                       rtol=1e-5, atol=1e-4)
        info = sess2.result_cache_info()
        assert info["spill"]["thawed_restored"] == 2
        # a thawed answer reads as the hit it was, never a miss
        assert info["hits"] == 2 and info["misses"] == 0
        # the re-inserted entries answer the next repeat from HBM
        _ = sess2.run(_gram(sess2.catalog["a"]))
        assert sess2.result_cache_info()["hits"] == 3

    def test_working_set_over_budget_then_thawed_equals_cold(
            self, mesh8, rng, tmp_path):
        """Four entries against an HBM budget of 2.5: sustained repeats
        are served by promotions from the lower tiers, bit-equal to the
        first answers; after save_state a thawed session's first answer
        equals a cold session's recomputation bit for bit."""
        cfg = _spill_cfg(tmp_path,
                         result_cache_max_bytes=int(2.5 * ENTRY),
                         spill_host_max_bytes=2 * ENTRY)
        names = ["w0", "w1", "w2", "w3"]
        sess = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess, rng, names)
        first = {nm: np.asarray(sess.run(_gram(mats[nm][0])).data)
                 for nm in names}
        for _ in range(2):
            for nm in names:
                got = np.asarray(sess.run(_gram(mats[nm][0])).data)
                assert np.array_equal(got, first[nm])
        assert sess.result_cache_info()["spill"]["promoted"] > 0
        sess.save_state()
        thawed = MatrelSession(mesh=mesh8, config=cfg)
        assert thawed.restore()["rc_entries"] > 0
        cold = MatrelSession(
            mesh=mesh8, config=cfg.replace(state_dir=str(
                tmp_path / "cold")))
        got_cold = np.asarray(cold.run(_gram(
            cold.from_numpy(mats["w0"][0].to_numpy()))).data)
        got_thawed = np.asarray(
            thawed.run(_gram(thawed.catalog["w0"])).data)
        assert np.array_equal(got_thawed, got_cold)
        assert thawed.result_cache_info()["spill"][
            "thawed_restored"] == 1

    def test_integer_results_restore_bit_exact(
            self, mesh8, rng, tmp_path):
        cfg = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess1, rng, ["ints"], integral=True)
        _check(sess1, mats, "ints", rtol=0, atol=0)
        sess1.save_state()
        sess2 = MatrelSession(mesh=mesh8, config=cfg)
        assert sess2.restore()["restored"]
        got = np.asarray(sess2.run(_gram(sess2.catalog["ints"])).data)
        assert np.array_equal(got, mats["ints"][1])
        assert sess2.result_cache_info()["spill"][
            "thawed_restored"] == 1

    def test_corrupt_snapshot_warns_and_cold_starts(
            self, mesh8, rng, tmp_path, caplog):
        cfg = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess1, rng, ["a"])
        _check(sess1, mats, "a")
        sess1.save_state()
        state = os.path.join(str(tmp_path), "state")
        for dirpath, _dirs, files in os.walk(state):
            for f in files:
                with open(os.path.join(dirpath, f), "wb") as fh:
                    fh.write(b"not a snapshot")
        sess2 = MatrelSession(mesh=mesh8, config=cfg)
        with caplog.at_level(logging.WARNING):
            out = sess2.restore()        # never raises
        assert out["restored"] is False and out.get("reason")
        # the cold session still answers correctly
        mats2 = _register(sess2, rng, ["a"])
        _check(sess2, mats2, "a")

    def test_missing_snapshot_is_a_clean_cold_start(
            self, mesh8, tmp_path):
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(tmp_path))
        out = sess.restore()
        assert out["restored"] is False
        assert out["reason"] == "no snapshot"

    def test_sha1_tampered_artifact_is_a_miss_not_a_wrong_answer(
            self, mesh8, rng, tmp_path):
        cfg = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=cfg)
        mats = _register(sess1, rng, ["a", "b"])
        _check(sess1, mats, "a")
        _check(sess1, mats, "b")
        sess1.save_state()
        spill_dir = os.path.join(str(tmp_path), "spill")
        victim = sorted(f for f in os.listdir(spill_dir)
                        if f.endswith(".npy"))[0]
        with open(os.path.join(spill_dir, victim), "r+b") as fh:
            fh.seek(0, os.SEEK_END)
            fh.write(b"\x00tampered")
        sess2 = MatrelSession(mesh=mesh8, config=cfg)
        assert sess2.restore()["rc_entries"] == 2
        for nm in ("a", "b"):            # one thaws, one recomputes
            got = np.asarray(
                sess2.run(_gram(sess2.catalog[nm])).data)
            np.testing.assert_allclose(got, mats[nm][1],
                                       rtol=1e-5, atol=1e-4)
        sp = sess2.result_cache_info()["spill"]
        assert sp["corrupt"] == 1 and sp["thawed_restored"] == 1

    def test_read_artifact_raises_typed_snapshot_corruption(
            self, mesh8, tmp_path):
        assert issubclass(SnapshotCorruption, CheckpointCorruption)
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(tmp_path))
        mgr = sess._spill
        arr = np.arange(16, dtype=np.float32).reshape(4, 4)
        file, sha1 = mgr._write_artifact("cafe0001", arr)
        te = spill_lib.TierEntry(tier="disk", meta={"key_hash": "x"},
                                 nbytes=64, file=file, sha1=sha1)
        np.testing.assert_array_equal(mgr._read_artifact(te), arr)
        with open(file, "ab") as fh:
            fh.write(b"garbage")
        with pytest.raises(SnapshotCorruption, match="sha1 mismatch"):
            mgr._read_artifact(te)
        os.remove(file)
        with pytest.raises(SnapshotCorruption):
            mgr._read_artifact(te)

    def test_spill_off_restore_keeps_catalog_skips_entries(
            self, mesh8, rng, tmp_path, caplog):
        on = _spill_cfg(tmp_path, result_cache_max_bytes=64 << 20)
        sess1 = MatrelSession(mesh=mesh8, config=on)
        mats = _register(sess1, rng, ["a"])
        _check(sess1, mats, "a")
        sess1.save_state()
        off = MatrelConfig(result_cache_max_bytes=64 << 20,
                           state_dir=str(tmp_path))
        sess2 = MatrelSession(mesh=mesh8, config=off)
        assert sess2._spill is None
        with caplog.at_level(logging.WARNING):
            out = sess2.restore()
        assert out["restored"] and out["catalog"] == 1
        assert out["rc_entries"] == 0    # no thaw path without spill
        assert any("spill_enable is off" in r.message
                   for r in caplog.records)
        _check(sess2, {"a": (sess2.catalog["a"], mats["a"][1])}, "a")

    def test_save_state_without_any_directory_raises(self, mesh8):
        sess = MatrelSession(mesh=mesh8, config=MatrelConfig(
            spill_enable=True, result_cache_max_bytes=64 << 20))
        with pytest.raises(ValueError, match="state_dir"):
            sess.save_state()


# ---------------------------------------------------------------------------
# Tentpole — fleet demand hints and MQO template keys across a restart
# ---------------------------------------------------------------------------


class TestWarmSeeds:

    def test_fleet_seed_hints_merge_into_first_fresh_insert(self):
        d = fleet_lib.FleetDirectory(max_entries=4)
        n = d.seed_hints([{"key": "k1", "hits": {"0": 3, "1": 2}},
                          "junk", {"key": 7}, {"key": "k2",
                                               "hits": {"0": 1}}])
        assert n == 2 and d.info()["seed_hints"] == 2
        rec = fleet_lib.DirectoryRecord(
            owner=0, owner_key="local", nbytes=64, layout="2d",
            dtype="float32", dep_names=frozenset({"a"}),
            hits={0: 1})
        d.record_insert("k1", rec)
        got = d.lookup("k1")
        assert got.hits == {0: 4, 1: 2}  # pre-restart demand re-armed
        assert d.info()["seed_hints"] == 1

    def test_fleet_export_state_carries_unconsumed_hints(self):
        d = fleet_lib.FleetDirectory(max_entries=4)
        d.seed_hints([{"key": "k2", "hits": {"1": 5}}])
        d.record_insert("k1", fleet_lib.DirectoryRecord(
            owner=0, owner_key="local", nbytes=64, layout="2d",
            dtype="float32", dep_names=frozenset({"a"}), hits={0: 2}))
        out = d.export_state()
        by_key = {r["key"]: r for r in out}
        assert by_key["k1"]["hits"] == {"0": 2}
        assert by_key["k1"]["dep_names"] == ["a"]
        assert "owner_key" not in by_key["k1"]   # id-based, never exported
        assert by_key["k2"]["hits"] == {"1": 5}  # restart-of-a-restart

    def test_mqo_template_keys_seed_and_rewarm(self):
        st = mqo_lib.MqoState(MatrelConfig(cse_enable=True))
        assert st.seed_templates(["t1", "t2", 3]) == 2
        assert st.info()["seeded_templates"] == 2
        assert st.template_keys() == ["t1", "t2"]
        ent = mqo_lib.TemplateEntry(plan=object(), slots=(), pins=())
        st.put_template("t1", ent)
        assert st.info()["templates_rewarmed"] == 1
        assert st.info()["seeded_templates"] == 1
        # a still-unrewarmed seed survives into the next snapshot
        assert st.template_keys() == ["t2", "t1"]

    def test_mqo_seed_respects_template_bound(self):
        st = mqo_lib.MqoState(MatrelConfig(cse_enable=True,
                                           cse_template_max=1))
        assert st.seed_templates(["t1", "t2", "t3"]) == 1


# ---------------------------------------------------------------------------
# MV117 — spill-thaw provenance stamps cohere with the tier hierarchy
# ---------------------------------------------------------------------------


def _stamped_leaf(mesh8, rng, spill):
    A = BlockMatrix.from_numpy(
        rng.standard_normal((32, 32)).astype(np.float32), mesh=mesh8)
    return E.leaf(A).with_attrs(result_cache={
        "key_hash": "cafe", "layout": "2d", "dtype": "float32",
        "deps": [], "spill": spill})


def _mv117(e, cfg=None):
    return [d for d in spill_pass.check_spill_stamps(
        e, None, cfg or MatrelConfig())]


class TestMV117:

    def test_truthful_stamp_is_clean(self, mesh8, rng):
        from matrel_tpu.parallel import reshard
        cfg = MatrelConfig()
        nbytes = 32 * 32 * 4
        plan = reshard.spill_plan("host", "hbm", nbytes)
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "host", "legs": ["h2d"], "cost": "measured",
            "fits": plan.fits(float(cfg.reshard_peak_budget_bytes))})
        assert _mv117(leaf, cfg) == []

    def test_hbm_tier_claim_fires(self, mesh8, rng):
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "hbm", "legs": [], "cost": "measured"})
        diags = _mv117(leaf)
        assert len(diags) == 1 and diags[0].code == "MV117"
        assert "an HBM hit never stamps" in diags[0].message
        assert diags[0].severity == "warning"

    def test_unknown_leg_fires(self, mesh8, rng):
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "host", "legs": ["dma"], "cost": "measured"})
        diags = _mv117(leaf)
        assert len(diags) == 1
        assert "transfer vocabulary" in diags[0].message

    def test_wrong_legs_for_tier_fire(self, mesh8, rng):
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "host", "legs": ["disk_read", "h2d"],
            "cost": "measured"})
        diags = _mv117(leaf)
        assert any("priced on transfers that did not run"
                   in d.message for d in diags)

    def test_restored_tier_prices_the_disk_legs(self, mesh8, rng):
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "restored", "legs": ["disk_read", "h2d"],
            "cost": "measured"})
        assert _mv117(leaf) == []

    def test_stale_fits_verdict_fires(self, mesh8, rng):
        # default budget 0 always fits — a stamp claiming False lies
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "host", "legs": ["h2d"], "cost": "measured",
            "fits": False})
        diags = _mv117(leaf)
        assert any("budget story" in d.message for d in diags)

    def test_unclassifiable_cost_provenance_fires(self, mesh8, rng):
        leaf = _stamped_leaf(mesh8, rng, {
            "tier": "host", "legs": ["h2d"], "cost": "guessed"})
        diags = _mv117(leaf)
        assert any("cannot classify" in d.message for d in diags)

    def test_live_promotion_stamp_passes_verify_plan(
            self, mesh8, rng, tmp_path):
        from matrel_tpu import analysis
        from matrel_tpu.ir import rules
        from matrel_tpu.parallel import planner
        sess = MatrelSession(mesh=mesh8, config=_spill_cfg(tmp_path))
        mats = _register(sess, rng, ["a", "b"])
        _check(sess, mats, "a")
        _check(sess, mats, "b")
        _check(sess, mats, "a")          # promoted: entry now stamped
        B = sess.from_numpy(
            rng.standard_normal((N, N)).astype(np.float32))
        substituted = sess._rc_substitute(
            _gram(mats["a"][0]).multiply(B.expr()))
        stamps = [c.attrs["result_cache"] for c in substituted.children
                  if c.attrs.get("result_cache")]
        assert stamps and stamps[0].get("spill", {}).get(
            "tier") == "host"
        cfg = sess.config
        grid = (2, 4)
        annotated = planner.annotate_strategies(
            rules.optimize(substituted, cfg, grid=grid, mesh=mesh8),
            mesh8, cfg)
        diags = analysis.verify_plan(annotated, mesh8, config=cfg)
        assert [d for d in diags if d.code == "MV117"] == []


# ---------------------------------------------------------------------------
# Config validation — the durability knobs reject broken combinations
# ---------------------------------------------------------------------------


class TestConfigValidation:

    def test_spill_requires_a_result_cache(self):
        with pytest.raises(ValueError, match="result_cache_max_bytes"):
            MatrelConfig(spill_enable=True)

    def test_host_budget_must_be_positive(self):
        with pytest.raises(ValueError, match="spill_host_max_bytes"):
            MatrelConfig(spill_host_max_bytes=0)

    def test_disk_hits_gate_must_be_nonnegative(self):
        with pytest.raises(ValueError, match="spill_disk_hits"):
            MatrelConfig(spill_disk_hits=-1)
