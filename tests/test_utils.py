"""Aux subsystem tests: checkpoint round-trip + GC, resilient driver loop
with injected failure (SURVEY.md §5)."""

import numpy as np
import pytest

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.utils.checkpoint import CheckpointManager
from matrel_tpu.utils import resilience


class TestCheckpoint:
    def test_roundtrip(self, mesh8, rng, tmp_path):
        a = rng.standard_normal((12, 10)).astype(np.float32)
        bm = BlockMatrix.from_numpy(a, mesh=mesh8, nnz=37)
        cm = CheckpointManager(str(tmp_path))
        cm.save(3, matrices={"A": bm}, state={"alpha": 0.85})
        step, mats, arrs, state = cm.restore(mesh8)
        assert step == 3 and state == {"alpha": 0.85}
        got = mats["A"]
        assert got.shape == (12, 10) and got.nnz == 37 and got.spec == bm.spec
        np.testing.assert_allclose(got.to_numpy(), a, rtol=1e-6)

    def test_gc_keeps_last_k(self, mesh8, rng, tmp_path):
        bm = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        cm = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            cm.save(s, matrices={"A": bm})
        assert cm._steps() == [3, 4]
        assert cm.latest_step() == 4

    def test_restore_empty_returns_none(self, mesh8, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        assert cm.restore(mesh8) is None


class TestResilience:
    def test_loop_completes_and_checkpoints(self, mesh8, rng, tmp_path):
        a = rng.standard_normal((8, 8)).astype(np.float32)
        bm = BlockMatrix.from_numpy(a, mesh=mesh8)
        cm = CheckpointManager(str(tmp_path))

        def body(step, mats, state):
            state = dict(state, last=step)
            return mats, state

        mats, state = resilience.run_resilient(
            body, cm, mesh8, {"A": bm}, num_steps=5, checkpoint_interval=2)
        assert state["last"] == 4
        assert cm.latest_step() == 4

    def test_restart_from_checkpoint_after_failure(self, mesh8, rng, tmp_path):
        a = np.ones((8, 8), dtype=np.float32)
        bm = BlockMatrix.from_numpy(a, mesh=mesh8)
        cm = CheckpointManager(str(tmp_path))
        calls = {"failed": False}

        class FakeXlaRuntimeError(Exception):
            pass

        FakeXlaRuntimeError.__name__ = "XlaRuntimeError"

        def body(step, mats, state):
            if step == 3 and not calls["failed"]:
                calls["failed"] = True
                raise FakeXlaRuntimeError("device lost")
            # matrix accumulates step index so we can check resume point
            new = BlockMatrix.from_numpy(
                mats["A"].to_numpy() + 1.0, mesh=mesh8)
            return {"A": new}, dict(state, last=step)

        mats, state = resilience.run_resilient(
            body, cm, mesh8, {"A": bm}, num_steps=5, checkpoint_interval=2)
        assert calls["failed"] and state["last"] == 4
        # A incremented exactly once per completed step (no double-apply
        # for steps made durable before the crash)
        np.testing.assert_allclose(mats["A"].to_numpy(), a + 5.0)

    def test_nonretryable_raises(self, mesh8, rng, tmp_path):
        bm = BlockMatrix.from_numpy(np.ones((8, 8), np.float32), mesh=mesh8)
        cm = CheckpointManager(str(tmp_path))

        def body(step, mats, state):
            raise ValueError("programming error")

        with pytest.raises(ValueError):
            resilience.run_resilient(body, cm, mesh8, {"A": bm}, num_steps=2)

