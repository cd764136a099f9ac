"""Multi-query optimization (serve/mqo.py + session integration):
cross-query CSE (``cse_enable``) — shared interiors of one run_many
batch compute ONCE (dispatch-counted) and feed consumers as cse-stamped
leaves the planner prices (cse_operands) — and plan-template reuse, on
every session — structurally-identical-modulo-leaves queries rebind
into the cached program with ZERO optimize/trace (event-verified),
isolated by SLA prefix and by leaf identity pattern. MV116 proves
substitution transparent (static stamps + dynamic substituted ≡
unshared); the default config never hoists (poisoned init), and a
query the plan cache answers never reaches the templates."""

import numpy as np
import pytest
import scipy.sparse

from matrel_tpu import executor as executor_lib
from matrel_tpu.analysis import cse_pass
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.sparse import BlockSparseMatrix
from matrel_tpu.serve import mqo as mqo_lib
from matrel_tpu.session import MatrelSession

CSE = dict(cse_enable=True)


def _mat(rng, n, m, mesh):
    return BlockMatrix.from_numpy(
        rng.standard_normal((n, m)).astype(np.float32), mesh=mesh)


def _sess(mesh, **cfg):
    return MatrelSession(mesh=mesh, config=MatrelConfig(**cfg))


def _gram_batch(X, k=4):
    """k structurally distinct consumers over ONE shared Gram interior
    (t(X) @ X is a matmul — a fused-region boundary, so it is a hoist
    candidate; the scalar epilogues stay with their consumers)."""
    g = X.expr().t().multiply(X.expr())
    return [g.multiply_scalar(1.0 + i) for i in range(k)]


def _gram_oracles(X, k=4):
    xn = X.to_numpy()
    g = xn.T @ xn
    return [g * (1.0 + i) for i in range(k)]


def _dispatch_spy(monkeypatch):
    """Count matmul dispatches per executed plan — the compute-once
    proof reads total matmuls across every program the batch ran."""
    counts = []
    orig = MatrelSession._arbitrated_run

    def spy(self, plan, bindings=None):
        counts.append(sum(
            len(d) for d in executor_lib.multiplan_root_decisions(plan)))
        return orig(self, plan, bindings=bindings)

    monkeypatch.setattr(MatrelSession, "_arbitrated_run", spy)
    return counts


def _find_cse_leaf(e):
    if e.attrs.get("cse") is not None:
        return e
    for c in e.children:
        hit = _find_cse_leaf(c)
        if hit is not None:
            return hit
    return None


class TestCrossQueryCSE:
    def test_shared_interior_computes_once_dispatch_counted(
            self, mesh8, rng, monkeypatch):
        X = _mat(rng, 48, 16, mesh8)
        counts = _dispatch_spy(monkeypatch)
        off = _sess(mesh8).run_many(_gram_batch(X))
        matmuls_off = sum(counts)
        counts.clear()
        sess = _sess(mesh8, **CSE)
        on = sess.run_many(_gram_batch(X))
        matmuls_on = sum(counts)
        # unshared: the Gram matmul dispatches once PER consumer;
        # hoisted: once total (the compute-once micro-batch), and the
        # consumers' programs hold zero matmuls
        assert matmuls_off == 4
        assert matmuls_on == 1
        info = sess.mqo_info()
        assert info["cse_hoisted"] == 1
        assert info["cse_batches"] == 1
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a.to_numpy(), b.to_numpy())

    def test_batch_answers_match_oracle(self, mesh8, rng):
        sess = _sess(mesh8, **CSE)
        X = _mat(rng, 64, 24, mesh8)
        outs = sess.run_many(_gram_batch(X, k=5))
        for out, want in zip(outs, _gram_oracles(X, k=5)):
            np.testing.assert_allclose(out.to_numpy(), want,
                                       rtol=3e-4, atol=3e-4)

    def test_deep_interior_hoists_once_then_rebinds(self, mesh8, rng):
        """Eight dashboard variants over one cubic polynomial of the
        Gram (four matmuls deep): the whole interior is hoisted once,
        answers are bit-equal to the unshared path, and the same batch
        over a REBOUND leaf answers through the plan templates."""
        def batch(M, k=8):
            g = M.expr().t().multiply(M.expr())
            h = g.multiply(g).multiply(g)
            return [h.multiply_scalar(1.0 + 0.25 * i) for i in range(k)]

        X, X2 = _mat(rng, 512, 128, mesh8), _mat(rng, 512, 128, mesh8)
        off = _sess(mesh8).run_many(batch(X))
        sess = _sess(mesh8, **CSE)
        on = sess.run_many(batch(X))
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a.to_numpy(), b.to_numpy())
        info = sess.mqo_info()
        assert info["cse_hoisted"] == info["cse_batches"] == 1
        outs = sess.run_many(batch(X2))
        assert sess.mqo_info()["template_hits"] \
            - info["template_hits"] >= 1
        x2 = X2.to_numpy().astype(np.float64)
        g2 = x2.T @ x2
        h2 = g2 @ g2 @ g2
        for i, out in enumerate(outs):
            np.testing.assert_allclose(
                out.to_numpy() / np.abs(h2).max(),
                h2 * (1.0 + 0.25 * i) / np.abs(h2).max(), atol=1e-4)

    def test_consumer_plan_carries_cse_stamp_and_pricing(
            self, mesh8, rng):
        sess = _sess(mesh8, **CSE)
        X = _mat(rng, 48, 16, mesh8)
        Bs = [_mat(rng, 16, 16, mesh8) for _ in range(3)]
        g = X.expr().t().multiply(X.expr())
        sess.run_many([g.multiply(B.expr()) for B in Bs])
        assert sess.mqo_info()["cse_hoisted"] == 1
        # the consumers' substituted trees (MV116's ring) feed on a
        # cse-stamped leaf carrying what the hoist recorded
        _orig, sub = sess._mqo.recent[-1]
        leaf = _find_cse_leaf(sub)
        assert leaf is not None
        stamp = leaf.attrs["cse"]
        assert stamp["uses"] == 3
        assert len(stamp["key_hash"]) == 16
        assert stamp["layout"] in ("2d", "row", "col", "rep", "other")
        # and the consumer plan's matmul decisions price the hoist-fed
        # operand (the rc_operands analogue)
        plan = list(sess._plan_cache.values())[-1]
        decs = executor_lib.plan_matmul_decisions(plan)
        assert any(d.get("cse_operands") == [True, False]
                   for d in decs)

    def test_matmul_free_share_is_not_hoisted(self, mesh8, rng):
        # a shared transpose-of-a-leaf is not worth its own dispatch:
        # candidates must carry a matmul under the boundary
        sess = _sess(mesh8, **CSE)
        X = _mat(rng, 32, 32, mesh8)
        t = X.expr().t()
        outs = sess.run_many([t.multiply_scalar(2.0),
                              t.multiply_scalar(3.0)])
        assert sess.mqo_info()["cse_hoisted"] == 0
        xn = X.to_numpy()
        np.testing.assert_allclose(outs[0].to_numpy(), xn.T * 2.0,
                                   rtol=1e-6, atol=1e-6)

    def test_rebind_invalidates_hoisted_interior(self, mesh8, rng):
        # with the result cache on, the hoisted interior inserts under
        # its structural key with the source's dep ids — a catalog
        # rebind must cascade, never serve the stale Gram
        sess = _sess(mesh8, **CSE, result_cache_max_bytes=64 << 20)
        A = _mat(rng, 48, 16, mesh8)
        B = _mat(rng, 48, 16, mesh8)
        sess.register("src", A)
        src = sess.table("src")
        batch = _gram_batch(src, k=3)
        sess.run_many(batch)
        assert sess.mqo_info()["cse_hoisted"] == 1
        sess.register("src", B)
        src2 = sess.table("src")
        outs = sess.run_many(_gram_batch(src2, k=3))
        for out, want in zip(outs, _gram_oracles(B, k=3)):
            np.testing.assert_allclose(out.to_numpy(), want,
                                       rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("cfg", [{}, CSE], ids=["default", "cse_enable"])
class TestPlanTemplates:
    """Templates answer from what the lookup observes — a plan-cache
    miss whose structure a cached plan shares — whatever ``cse_enable``
    says."""

    def test_new_leaves_of_a_known_structure_compile_nothing(
            self, cfg, mesh8, rng, tmp_path):
        # compute()'s fast path: no obs, no tracer, no result cache
        import jax
        from matrel_tpu.obs import trace as trace_lib
        sess = _sess(mesh8, **cfg)
        A = _mat(rng, 48, 16, mesh8)
        B = _mat(rng, 48, 16, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        assert sess.last_plan()["hit"] is False
        cold = len(trace_lib.cold_spans())
        before = len(trace_lib.profile_spans())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            out = sess.run(B.expr().t().multiply(B.expr()))
        finally:
            jax.profiler.stop_trace()
        bn = B.to_numpy()
        np.testing.assert_allclose(out.to_numpy(), bn.T @ bn,
                                   rtol=3e-4, atol=3e-4)
        assert sess.last_plan()["hit"] is True
        assert sess.mqo_info()["template_hits"] == 1
        assert sess.plan_cache_info()["plans"] == 1
        assert not [r for r in trace_lib.cold_spans()[cold:]
                    if r["name"] == "compile"]
        mine = trace_lib.profile_spans()[before:]
        assert [r["attrs"] for r in mine
                if r["name"] == "matrel.plan"] == [
            {"hit": True, "via": "template"}]
        assert [r["attrs"]["path"] for r in mine
                if r["name"] == "matrel.compute"] == ["fast"]
        assert not [r for r in mine if r["name"] == "matrel.compile"]

    def test_template_hit_pays_zero_optimize_event_verified(
            self, cfg, mesh8, rng, tmp_path):
        from matrel_tpu.obs.events import read_events
        log = str(tmp_path / "events.jsonl")
        sess = _sess(mesh8, **cfg, obs_level="on", obs_event_log=log)
        A = _mat(rng, 48, 16, mesh8)
        B = _mat(rng, 48, 16, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        out = sess.run(B.expr().t().multiply(B.expr()))
        bn = B.to_numpy()
        np.testing.assert_allclose(out.to_numpy(), bn.T @ bn,
                                   rtol=3e-4, atol=3e-4)
        info = sess.mqo_info()
        assert info["template_inserts"] == 1
        assert info["template_hits"] == 1
        q = [e for e in read_events(log) if e.get("kind") == "query"]
        assert [e["cache"] for e in q] == ["miss", "template_hit"]
        # the template contract: steady state pays ZERO optimize/trace
        # this query — the event is the proof
        assert q[1]["optimize_ms"] == 0.0
        assert q[1]["trace_ms"] == 0.0
        assert q[0]["optimize_ms"] > 0.0

    def test_multiplan_template_rebinds_whole_batch(self, cfg, mesh8,
                                                    rng):
        sess = _sess(mesh8, **cfg)
        A = _mat(rng, 48, 16, mesh8)
        B = _mat(rng, 48, 16, mesh8)
        sess.run_many(_gram_batch(A, k=3))
        outs = sess.run_many(_gram_batch(B, k=3))
        info = sess.mqo_info()
        assert info["template_hits"] >= 3
        for out, want in zip(outs, _gram_oracles(B, k=3)):
            np.testing.assert_allclose(out.to_numpy(), want,
                                       rtol=3e-4, atol=3e-4)

    def test_identity_pattern_never_aliases(self, cfg, mesh8, rng):
        # t(A) @ A dedupes its two leaves into one Gram operand;
        # t(B) @ C cannot — the abstract key's identity classes
        # (#0/#0 vs #0/#1) must keep them apart
        sess = _sess(mesh8, **cfg)
        A = _mat(rng, 32, 32, mesh8)
        B = _mat(rng, 32, 32, mesh8)
        C = _mat(rng, 32, 32, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        out = sess.run(B.expr().t().multiply(C.expr()))
        assert sess.mqo_info()["template_hits"] == 0
        np.testing.assert_allclose(
            out.to_numpy(), B.to_numpy().T @ C.to_numpy(),
            rtol=3e-4, atol=3e-4)
        # the matching pattern DOES share: a fresh Gram rebinds
        D = _mat(rng, 32, 32, mesh8)
        out2 = sess.run(D.expr().t().multiply(D.expr()))
        assert sess.mqo_info()["template_hits"] == 1
        np.testing.assert_allclose(
            out2.to_numpy(), D.to_numpy().T @ D.to_numpy(),
            rtol=3e-4, atol=3e-4)

    def test_sla_prefix_isolates_templates(self, cfg, mesh8, rng):
        sess = _sess(mesh8, **cfg)
        A = _mat(rng, 48, 16, mesh8)
        B = _mat(rng, 48, 16, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        # same structure, different SLA: the prec: prefix must miss
        sess.run(B.expr().t().multiply(B.expr()), precision="high")
        assert sess.mqo_info()["template_hits"] == 0

    def test_sparse_leaves_keep_identity_tokens(self, cfg, mesh8, rng):
        # sparse payloads are trace CONSTANTS in the compiled program —
        # a different sparse matrix must never rebind into the template
        sess = _sess(mesh8, **cfg)
        sp1 = scipy.sparse.random(64, 64, density=0.3, format="csr",
                                  random_state=1, dtype=np.float32)
        sp2 = scipy.sparse.random(64, 64, density=0.3, format="csr",
                                  random_state=2, dtype=np.float32)
        S1 = BlockSparseMatrix.from_scipy(sp1, block_size=16,
                                          mesh=mesh8)
        S2 = BlockSparseMatrix.from_scipy(sp2, block_size=16,
                                          mesh=mesh8)
        D = _mat(rng, 64, 8, mesh8)
        o1 = sess.run(S1.expr().multiply(D.expr()))
        o2 = sess.run(S2.expr().multiply(D.expr()))
        assert sess.mqo_info()["template_hits"] == 0
        dn = D.to_numpy()
        np.testing.assert_allclose(o1.to_numpy(), sp1.toarray() @ dn,
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(o2.to_numpy(), sp2.toarray() @ dn,
                                   rtol=3e-4, atol=3e-4)


class TestMV116:
    def test_dynamic_verify_clean_over_traffic(self, mesh8, rng):
        sess = _sess(mesh8, **CSE, result_cache_max_bytes=64 << 20)
        for _ in range(3):
            X = _mat(rng, 48, 16, mesh8)
            sess.run_many(_gram_batch(X, k=3))
        assert len(sess._mqo.recent) > 0
        assert cse_pass.verify_cse_executions(sess) == []

    def test_static_stamps_clean_then_tampered(self, mesh8, rng):
        sess = _sess(mesh8, **CSE)
        X = _mat(rng, 48, 16, mesh8)
        sess.run_many(_gram_batch(X, k=3))
        _orig, sub = sess._mqo.recent[-1]
        assert list(cse_pass.check_cse_stamps(
            sub, mesh8, sess.config)) == []
        # a stamp whose dtype no longer agrees with the leaf's matrix
        # is a mispriced plan — warning severity, the MV107 class
        leaf = _find_cse_leaf(sub)
        bad = leaf.with_attrs(cse={**leaf.attrs["cse"],
                                   "dtype": "float64"})
        diags = list(cse_pass.check_cse_stamps(bad, mesh8,
                                               sess.config))
        assert len(diags) == 1
        assert diags[0].code == "MV116"
        assert diags[0].severity == "warning"

    def test_session_verify_includes_cse_pass(self, mesh8, rng):
        sess = _sess(mesh8, **CSE)
        X = _mat(rng, 48, 16, mesh8)
        sess.run_many(_gram_batch(X, k=3))
        _orig, sub = sess._mqo.recent[-1]
        assert sess.verify(sub) == []


class TestZeroOverheadDefault:
    def test_default_config_constructs_nothing(self, mesh8, rng,
                                               monkeypatch):
        # the bypass: cse_enable off (the default) never hoists (the
        # poisoned-init proof), and a query or a batch the plan cache
        # answers never reaches the templates
        before = mqo_lib._CONSTRUCTED["count"]
        sess = _sess(mesh8)
        assert sess.mqo_info() == {
            "templates": 0, "template_hits": 0, "template_inserts": 0,
            "cse_hoisted": 0, "cse_batches": 0}
        X = _mat(rng, 48, 16, mesh8)
        batch = _gram_batch(X, k=4)
        gram = X.expr().t().multiply(X.expr())
        sess.run_many(batch)
        sess.run(gram)

        def poisoned(e):
            raise AssertionError("a concrete hit walked template_key")
        monkeypatch.setattr(mqo_lib, "template_key", poisoned)
        outs = sess.run_many(batch)
        sess.run(gram)
        assert sess.last_plan()["hit"] is True
        assert mqo_lib._CONSTRUCTED["count"] == before
        assert not sess._mqo.recent
        info = sess.mqo_info()
        assert (info["cse_hoisted"], info["cse_batches"],
                info["template_hits"]) == (0, 0, 0)
        assert info["templates"] == info["template_inserts"] == 2
        for out, want in zip(outs, _gram_oracles(X, k=4)):
            np.testing.assert_allclose(out.to_numpy(), want,
                                       rtol=3e-4, atol=3e-4)

    def test_default_is_off(self):
        assert MatrelConfig().cse_enable is False
