"""Serving layer (matrel_tpu/serve/ + session integration): the
cross-query materialized-result cache (structural keying, byte-budgeted
LRU, catalog-rebind invalidation, planner substitution), micro-batched
admission through session.run_many (MultiPlan in the session plan
cache, input-order results, duplicate dedup), the async submit
pipeline's future API, and the off-by-default contracts — cache off
must be bit-identical to the pre-serve behaviour and obs off must emit
nothing."""

import json
import os

import numpy as np
import pytest

from matrel_tpu import executor as executor_lib
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.serve.result_cache import ResultCache
from matrel_tpu.session import MatrelSession, _plan_key

RC = dict(result_cache_max_bytes=64 << 20)


def _mat(rng, n, m, mesh):
    return BlockMatrix.from_numpy(
        rng.standard_normal((n, m)).astype(np.float32), mesh=mesh)


def _sess(mesh, **cfg):
    return MatrelSession(mesh=mesh, config=MatrelConfig(**cfg))


class TestResultCacheHits:
    def test_repeated_query_answers_from_cache(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        X = _mat(rng, 64, 16, mesh8)
        gram = X.expr().t().multiply(X.expr())
        r1 = sess.run(gram)
        r2 = sess.run(gram)
        # the SAME device-resident result comes back — no compile, no
        # execute (the repeated-dashboard-query fast path)
        assert r2 is r1
        info = sess.result_cache_info()
        assert info["entries"] == 1
        assert info["hits"] == 1

    def test_structurally_identical_fresh_expr_hits(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        X = _mat(rng, 64, 16, mesh8)
        r1 = sess.run(X.expr().t().multiply(X.expr()))
        # a NEW expression tree over the same matrix keys identically
        r2 = sess.run(X.expr().t().multiply(X.expr()))
        assert r2 is r1

    def test_interior_subplan_enters_planning_as_leaf(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        X = _mat(rng, 64, 16, mesh8)
        y = _mat(rng, 64, 1, mesh8)
        gram = X.expr().t().multiply(X.expr())
        sess.run(gram)
        out = sess.run(gram.multiply(X.expr().t().multiply(y.expr())))
        # the compiled plan consumed the cached Gram as a stamped leaf
        plan = list(sess._plan_cache.values())[-1]
        stamps = [l.attrs.get("result_cache")
                  for l in plan.leaf_order
                  if l.attrs.get("result_cache")]
        assert len(stamps) == 1
        assert stamps[0]["layout"] in ("2d", "row", "col", "rep",
                                       "other")
        xn, yn = X.to_numpy(), y.to_numpy()
        want = xn.T @ xn @ (xn.T @ yn)
        np.testing.assert_allclose(out.to_numpy(), want, rtol=3e-4,
                                   atol=3e-4)

    def test_matmul_decisions_record_rc_operands(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        X = _mat(rng, 64, 16, mesh8)
        B = _mat(rng, 16, 16, mesh8)
        gram = X.expr().t().multiply(X.expr())
        sess.run(gram)
        sess.run(gram.multiply(B.expr()))
        plan = list(sess._plan_cache.values())[-1]
        decs = executor_lib.plan_matmul_decisions(plan)
        assert any(d.get("rc_operands") == [True, False] for d in decs)


class TestInvalidation:
    def test_catalog_rebind_invalidates_dependents(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        B = _mat(rng, 32, 32, mesh8)
        sess.register("A", A)
        sess.run(sess.table("A").expr().t().multiply(
            sess.table("A").expr()))
        assert sess.result_cache_info()["entries"] == 1
        sess.register("A", B)          # rebind — old results are stale
        info = sess.result_cache_info()
        assert info["entries"] == 0
        assert info["invalidated"] == 1

    def test_invalidation_cascades_through_derived_entries(self, mesh8,
                                                           rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 16, mesh8)
        C = _mat(rng, 16, 16, mesh8)
        sess.register("A", A)
        gram = A.expr().t().multiply(A.expr())
        sess.run(gram)
        # second query CONSUMES the cached gram (substituted leaf) —
        # its entry's deps must reach back to A, not stop at the
        # cached intermediate
        sess.run(gram.multiply(C.expr()))
        assert sess.result_cache_info()["entries"] == 2
        sess.register("A", C)
        assert sess.result_cache_info()["entries"] == 0

    def test_unrelated_rebind_keeps_entries(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        B = _mat(rng, 32, 32, mesh8)
        sess.register("A", A)
        sess.register("B", B)
        sess.run(A.expr().t().multiply(A.expr()))
        sess.register("B", _mat(rng, 32, 32, mesh8))
        assert sess.result_cache_info()["entries"] == 1

    def test_load_catalog_rebind_invalidates(self, mesh8, rng,
                                             tmp_path):
        # load_catalog overwrites existing names with freshly-restored
        # matrix objects — that is a rebind and must invalidate like
        # register() does
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        sess.register("A", A)
        sess.save_catalog(str(tmp_path))
        sess.run(A.expr().t().multiply(A.expr()))
        assert sess.result_cache_info()["entries"] == 1
        sess.load_catalog(str(tmp_path))
        info = sess.result_cache_info()
        assert info["entries"] == 0
        assert info["invalidated"] == 1

    def test_register_same_object_is_not_a_rebind(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        sess.register("A", A)
        sess.run(A.expr().t().multiply(A.expr()))
        sess.register("A", A)
        assert sess.result_cache_info()["invalidated"] == 0


class TestEviction:
    def test_byte_budget_evicts_lru_order(self, mesh8, rng):
        # each 32x32 f32 result pins 4096 bytes padded; budget holds 2
        sess = _sess(mesh8, result_cache_max_bytes=2 * 32 * 32 * 4)
        mats = [_mat(rng, 32, 32, mesh8) for _ in range(3)]
        qs = [m.expr().t().multiply(m.expr()) for m in mats]
        sess.run(qs[0])
        sess.run(qs[1])
        assert sess.result_cache_info()["entries"] == 2
        sess.run(qs[2])                # evicts qs[0] (LRU)
        info = sess.result_cache_info()
        assert info["entries"] == 2
        assert info["evicted"] == 1
        # qs[0] misses (recomputes; re-inserted, evicting qs[1]);
        # qs[2] — touched most recently before it — still hits
        hits_before = info["hits"]
        sess.run(qs[0])
        assert sess.result_cache_info()["hits"] == hits_before
        sess.run(qs[2])
        assert sess.result_cache_info()["hits"] == hits_before + 1

    def test_hit_refreshes_lru_position(self, mesh8, rng):
        sess = _sess(mesh8, result_cache_max_bytes=2 * 32 * 32 * 4)
        mats = [_mat(rng, 32, 32, mesh8) for _ in range(3)]
        qs = [m.expr().t().multiply(m.expr()) for m in mats]
        r0 = sess.run(qs[0])
        sess.run(qs[1])
        assert sess.run(qs[0]) is r0   # refresh qs[0]
        sess.run(qs[2])                # evicts qs[1], NOT qs[0]
        assert sess.run(qs[0]) is r0   # still cached

    def test_entry_count_bound_caps_pin_retention(self, mesh8, rng):
        # the byte budget counts RESULT bytes only — pins keep the
        # query's inputs alive, so the count bound is what stops tiny
        # results over many ad-hoc inputs retaining unbounded memory
        sess = _sess(mesh8, result_cache_max_bytes=64 << 20,
                     result_cache_max_entries=2)
        mats = [_mat(rng, 32, 32, mesh8) for _ in range(3)]
        for m in mats:
            sess.run(m.expr().t().multiply(m.expr()))
        info = sess.result_cache_info()
        assert info["entries"] == 2
        assert info["evicted"] == 1

    def test_oversized_result_never_inserted(self, mesh8, rng):
        sess = _sess(mesh8, result_cache_max_bytes=64)
        A = _mat(rng, 32, 32, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        assert sess.result_cache_info()["entries"] == 0


class TestCacheOffBitIdentical:
    def test_default_is_off(self):
        assert MatrelConfig().result_cache_max_bytes == 0

    def test_off_path_never_touches_the_cache(self, mesh8, rng,
                                              monkeypatch):
        # structural guard, the obs-off idiom: with the cache off, the
        # query path may not even CONSULT it
        def boom(*a, **k):
            raise AssertionError("result cache consulted while off")
        monkeypatch.setattr(ResultCache, "lookup", boom)
        monkeypatch.setattr(ResultCache, "probe", boom)
        monkeypatch.setattr(ResultCache, "put", boom)
        sess = _sess(mesh8)
        A = _mat(rng, 32, 32, mesh8)
        sess.run(A.expr().t().multiply(A.expr()))
        sess.run_many([A.expr().t()])

    def test_off_plans_and_results_unchanged(self, mesh8, rng):
        # the compiled plan for a query must be the SAME cache entry /
        # key with the serve layer present-but-off as the pre-serve
        # session produced: no substitution, no key prefix, no extra
        # leaves
        sess = _sess(mesh8)
        X = _mat(rng, 64, 16, mesh8)
        e = X.expr().t().multiply(X.expr())
        key, _ = _plan_key(e)
        plan, hit, got_key = sess._compile_entry(e)
        assert got_key == key
        assert all(l.attrs.get("result_cache") is None
                   for l in plan.leaf_order)
        out = sess.run(e)
        xn = X.to_numpy()
        np.testing.assert_allclose(out.to_numpy(), xn.T @ xn,
                                   rtol=3e-4, atol=3e-4)

    def test_cached_results_match_uncached(self, mesh8, rng):
        X = _mat(rng, 64, 16, mesh8)
        y = _mat(rng, 64, 1, mesh8)
        gram = X.expr().t().multiply(X.expr())
        q2 = gram.multiply(X.expr().t().multiply(y.expr()))
        on = _sess(mesh8, **RC)
        off = _sess(mesh8)
        for q in (gram, q2, gram, q2):
            a = on.run(q).to_numpy()
            b = off.run(q).to_numpy()
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


class TestRunMany:
    def test_matches_sequential(self, mesh8, rng):
        sess = _sess(mesh8)
        A = _mat(rng, 32, 16, mesh8)
        B = _mat(rng, 16, 32, mesh8)
        qs = [A.expr().multiply(B.expr()),
              A.expr().t(),
              B.expr().multiply(A.expr()).multiply_scalar(2.0)]
        batch = sess.run_many(qs)
        seq = [_sess(mesh8).run(q) for q in qs]
        for got, want in zip(batch, seq):
            np.testing.assert_allclose(got.to_numpy(), want.to_numpy(),
                                       rtol=1e-5, atol=1e-5)

    def test_duplicate_roots_dedupe_into_one_program(self, mesh8, rng):
        sess = _sess(mesh8)
        A = _mat(rng, 32, 32, mesh8)
        q = A.expr().t().multiply(A.expr())
        outs = sess.run_many([q, q, q])
        assert sess.plan_cache_info()["plans"] == 1
        for o in outs[1:]:
            np.testing.assert_array_equal(o.to_numpy(),
                                          outs[0].to_numpy())

    def test_multiplan_participates_in_plan_cache(self, mesh8, rng,
                                                  monkeypatch):
        sess = _sess(mesh8)
        A = _mat(rng, 32, 16, mesh8)
        B = _mat(rng, 16, 32, mesh8)
        qs = [A.expr().multiply(B.expr()), A.expr().t()]
        sess.run_many(qs)
        assert sess.plan_cache_info()["plans"] == 1
        calls = []
        orig = executor_lib.compile_exprs
        monkeypatch.setattr(executor_lib, "compile_exprs",
                            lambda *a, **k: calls.append(1)
                            or orig(*a, **k))
        sess.run_many(qs)                  # same batch: pure hit
        sess.run_many(list(reversed(qs)))  # permuted: still a hit
        assert calls == []
        assert sess.plan_cache_info()["plans"] == 1

    def test_permuted_batch_results_keep_input_order(self, mesh8, rng):
        sess = _sess(mesh8)
        A = _mat(rng, 32, 16, mesh8)
        B = _mat(rng, 16, 32, mesh8)
        q1 = A.expr().multiply(B.expr())        # 32x32
        q2 = B.expr().multiply(A.expr())        # 16x16
        o1, o2 = sess.run_many([q1, q2])
        p2, p1 = sess.run_many([q2, q1])
        assert o1.shape == (32, 32) and o2.shape == (16, 16)
        np.testing.assert_array_equal(o1.to_numpy(), p1.to_numpy())
        np.testing.assert_array_equal(o2.to_numpy(), p2.to_numpy())

    def test_batch_with_result_cache(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        q = A.expr().t().multiply(A.expr())
        first = sess.run_many([q, q.multiply_scalar(2.0)])
        again = sess.run_many([q, q.multiply_scalar(2.0)])
        assert again[0] is first[0]
        assert again[1] is first[1]

    @pytest.mark.parametrize("batched", [False, True],
                             ids=["sequential", "batched"])
    def test_repeated_mixed_stream_second_round_all_hits(
            self, mesh8, rng, batched):
        """Dashboard traffic: six distinct queries (a PageRank-style
        step, a normal-equations solve, a reordered chain, each with a
        scalar variant) replayed round-robin. With the cache on, every
        query of the second round is a whole hit — the first round's
        own result object — and agrees with an uncached session."""
        n, k = 256, 64
        M, r = _mat(rng, n, n, mesh8), _mat(rng, n, 1, mesh8)
        X, y = _mat(rng, n, k, mesh8), _mat(rng, n, 1, mesh8)
        A, B, C = (_mat(rng, n, k, mesh8), _mat(rng, k, n, mesh8),
                   _mat(rng, n, k, mesh8))
        pr = M.expr().multiply(r.expr()).multiply_scalar(0.85)
        xt = X.expr().t()
        linreg = xt.multiply(X.expr()).solve(xt.multiply(y.expr()))
        chain = A.expr().multiply(B.expr().multiply(C.expr()))
        qs = [pr, pr.add_scalar(0.15 / n), linreg,
              linreg.multiply_scalar(2.0), chain,
              chain.multiply_scalar(0.5)]
        stream = [qs[i % len(qs)] for i in range(18)]
        sess = _sess(mesh8, **RC)

        def replay():
            if not batched:
                return [sess.run(q) for q in stream]
            return [o for j in range(0, len(stream), 6)
                    for o in sess.run_many(stream[j:j + 6])]

        first = replay()
        hits = sess.result_cache_info()["hits"]
        again = replay()
        assert sess.result_cache_info()["hits"] - hits >= len(stream)
        assert all(a is f for a, f in zip(again, first))
        off = _sess(mesh8)
        for q, got in zip(qs, again):
            np.testing.assert_allclose(got.to_numpy(),
                                       off.run(q).to_numpy(),
                                       rtol=1e-4, atol=1e-4)

    def test_empty_batch(self, mesh8):
        assert _sess(mesh8).run_many([]) == []


class TestMultiPlanParity:
    def test_donate_rebound_leaves(self, mesh8, rng):
        # MultiPlan.run(donate=True) — the CompiledPlan parity fix
        A = _mat(rng, 32, 32, mesh8)
        B = _mat(rng, 32, 32, mesh8)
        e = A.expr().multiply(B.expr())
        plan = executor_lib.compile_exprs([e], mesh8,
                                          MatrelConfig())
        a_leaf = plan.leaf_order[0]
        fresh = _mat(rng, 32, 32, mesh8)
        # read the donated operand BEFORE running: donation hands its
        # buffer to XLA (that being impossible afterwards is the point)
        want = fresh.to_numpy() @ B.to_numpy()
        (out,) = plan.run(bindings={a_leaf.uid: fresh}, donate=True)
        np.testing.assert_allclose(out.to_numpy(), want, rtol=1e-5,
                                   atol=1e-5)

    def test_multiplan_byte_accounting_in_session_cache(self, mesh8,
                                                        rng):
        # a MultiPlan with hoisted sparse payloads must be accounted
        # (and evictable) by the session byte budget like single plans
        from matrel_tpu.core.coo import COOMatrix
        sess = _sess(mesh8, plan_cache_max_bytes=1,
                     plan_cache_max_plans=64)
        x = _mat(rng, 2000, 2, mesh8)
        rows = rng.integers(0, 2000, 600_000)
        cols = rng.integers(0, 2000, 600_000)
        S = COOMatrix.from_edges(rows, cols, shape=(2000, 2000))
        sess.run_many([S.expr().multiply(x.expr())])
        assert sess.plan_cache_info()["plans"] == 1  # sole-plan guard
        sess.run_many([S.expr().multiply(x.expr()).multiply_scalar(2.0)])
        # over the 1-byte budget: the older MultiPlan evicted
        assert sess.plan_cache_info()["plans"] == 1
        assert sess.plan_cache_info()["evicted"] >= 1


class TestFutures:
    def test_submit_result_matches_compute(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 16, mesh8)
        fut = sess.submit(A.expr().t().multiply(A.expr()))
        out = fut.result(timeout=120)
        an = A.to_numpy()
        np.testing.assert_allclose(out.to_numpy(), an.T @ an,
                                   rtol=3e-4, atol=3e-4)
        sess.serve_drain()

    def test_submit_many_all_resolve(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        A = _mat(rng, 32, 32, mesh8)
        qs = [A.expr().multiply_scalar(float(s)) for s in range(6)]
        futs = [sess.submit(q) for q in qs]
        sess.serve_drain()
        an = A.to_numpy()
        for s, f in enumerate(futs):
            np.testing.assert_allclose(f.result(timeout=120).to_numpy(),
                                       an * s, rtol=1e-5, atol=1e-5)

    def test_cancelled_future_does_not_kill_worker(self, mesh8, rng):
        # a future cancelled while queued must drop out of its batch;
        # set_result on it would raise InvalidStateError, kill the
        # admission worker, and strand every sibling future
        import time as time_mod
        from matrel_tpu.serve.pipeline import ServePipeline
        sess = _sess(mesh8, **RC)
        pl = ServePipeline(sess)
        A = _mat(rng, 32, 32, mesh8)
        from concurrent.futures import Future
        f_cancel, f_ok = Future(), Future()
        # enqueue BOTH before the worker exists, so the cancel is
        # deterministic (still pending when the batch is admitted)
        pl._q.put((A.expr().t(), f_cancel, time_mod.perf_counter()))
        pl._q.put((A.expr().multiply_scalar(2.0), f_ok,
                   time_mod.perf_counter()))
        assert f_cancel.cancel()
        pl._ensure_worker()
        out = f_ok.result(timeout=120)
        np.testing.assert_allclose(out.to_numpy(), 2 * A.to_numpy(),
                                   rtol=1e-6, atol=1e-6)
        assert f_cancel.cancelled()
        pl.drain()
        assert pl._worker.is_alive()

    def test_submit_exception_propagates(self, mesh8, rng):
        # a query whose lowering REFUSES (join pair cap) must fail its
        # future with the original error, not hang or kill the worker
        sess = _sess(mesh8, join_pair_cap_entries=4)
        A = _mat(rng, 32, 1, mesh8)
        B = _mat(rng, 32, 1, mesh8)
        bad = A.expr().join_on_value(B.expr(), merge="add")
        fut = sess.submit(bad)
        with pytest.raises(ValueError, match="join_pair_cap_entries"):
            fut.result(timeout=120)
        # the worker survived: a healthy query still serves
        ok = sess.submit(A.expr().t())
        np.testing.assert_allclose(ok.result(timeout=120).to_numpy(),
                                   A.to_numpy().T, rtol=1e-6,
                                   atol=1e-6)
        sess.serve_drain()


class TestServeObservability:
    def _events(self, path):
        with open(path) as f:
            return [json.loads(l) for l in f if l.strip()]

    def test_run_many_emits_per_root_query_and_serve_events(
            self, mesh8, rng, tmp_path):
        log = str(tmp_path / "events.jsonl")
        sess = _sess(mesh8, obs_level="on", obs_event_log=log, **RC)
        A = _mat(rng, 32, 16, mesh8)
        B = _mat(rng, 16, 32, mesh8)
        qs = [A.expr().multiply(B.expr()), A.expr().t(),
              B.expr().t()]
        sess.run_many(qs)
        events = self._events(log)
        queries = [e for e in events if e["kind"] == "query"]
        serves = [e for e in events if e["kind"] == "serve"]
        assert len(queries) == 3           # one per ROOT — the
        assert len(serves) == 1            # MultiPlan obs parity fix
        assert serves[0]["batch_size"] == 3
        assert serves[0]["executed"] == 3
        assert serves[0]["rc_hits"] == 0
        assert "result_cache" in serves[0]
        assert serves[0]["result_cache"]["entries"] == 3
        for q in queries:
            assert q["batch"]["size"] == 3
            assert isinstance(q["matmuls"], list)
        # matmul decisions are PER ROOT, not the batch aggregate
        assert sum(len(q["matmuls"]) for q in queries) == 1
        # rewrite-rule hits attributed once, not once per root
        assert sum(1 for q in queries if q["rule_hits"]) <= 1

    def test_rc_hit_emits_query_event(self, mesh8, rng, tmp_path):
        log = str(tmp_path / "events.jsonl")
        sess = _sess(mesh8, obs_level="on", obs_event_log=log, **RC)
        A = _mat(rng, 32, 32, mesh8)
        q = A.expr().t().multiply(A.expr())
        sess.run(q)
        sess.run(q)
        queries = [e for e in self._events(log)
                   if e["kind"] == "query"]
        assert [e["cache"] for e in queries] == ["miss", "rc_hit"]
        assert queries[1]["matmuls"] == []

    def test_serve_events_roll_up_in_history_summary(self, mesh8, rng,
                                                     tmp_path):
        from matrel_tpu.obs import history
        from matrel_tpu.obs.events import read_events
        log = str(tmp_path / "events.jsonl")
        sess = _sess(mesh8, obs_level="on", obs_event_log=log, **RC)
        A = _mat(rng, 32, 32, mesh8)
        q = A.expr().t().multiply(A.expr())
        sess.run_many([q, q.multiply_scalar(2.0)])
        sess.run_many([q, q.multiply_scalar(2.0)])
        events = read_events(log)
        s = history.summarize(events)
        assert s["serve"]["batches"] == 2
        assert s["serve"]["queries"] == 4
        assert s["serve"]["qps"] is not None and s["serve"]["qps"] > 0
        assert s["serve"]["rc_hit_ratio"] == 0.5
        text = history.render_summary(events)
        assert "serve:" in text and "QPS" in text

    def test_summary_hit_ratio_sums_per_record_deltas(self):
        # the ratio must come from each record's OWN rc_hits/batch_size,
        # not the last record's cumulative session-lifetime counters —
        # a multi-session log would otherwise report only the final
        # session's cache behaviour
        from matrel_tpu.obs import history
        events = [
            {"kind": "serve", "batch_size": 10, "rc_hits": 9,
             "wall_ms": 5.0, "result_cache": {"hits": 900,
                                              "misses": 100}},
            {"kind": "serve", "batch_size": 10, "rc_hits": 0,
             "wall_ms": 5.0, "result_cache": {"hits": 0,
                                              "misses": 10}},
        ]
        s = history.summarize(events)
        assert s["serve"]["rc_hit_ratio"] == 0.45

    def test_obs_off_emits_nothing(self, mesh8, rng, tmp_path):
        log = str(tmp_path / "events.jsonl")
        os.environ.pop("MATREL_OBS_EVENT_LOG", None)
        sess = _sess(mesh8, obs_event_log=log, **RC)
        A = _mat(rng, 32, 32, mesh8)
        q = A.expr().t().multiply(A.expr())
        sess.run_many([q, q])
        sess.run(q)
        fut = sess.submit(q.multiply_scalar(2.0))
        fut.result(timeout=120)
        sess.serve_drain()
        assert not os.path.exists(log)


class TestResultCacheInfoSurface:
    def test_info_fields(self, mesh8, rng):
        sess = _sess(mesh8, **RC)
        info = sess.result_cache_info()
        assert set(info) == {"entries", "bytes", "hits", "misses",
                             "interior_hits", "evicted", "invalidated",
                             "stale_entries", "stale_bytes",
                             "stale_hits", "max_bytes", "max_entries",
                             "patched", "rekeyed"}
        assert info["max_bytes"] == RC["result_cache_max_bytes"]
        assert info["max_entries"] == 256

    def test_config_validates_serve_knobs(self):
        with pytest.raises(ValueError, match="serve_max_batch"):
            MatrelConfig(serve_max_batch=0)
        with pytest.raises(ValueError, match="serve_max_inflight"):
            MatrelConfig(serve_max_inflight=0)
