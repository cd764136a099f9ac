"""Query-lifecycle observability (matrel_tpu/obs/) — registry, event
log, explain(analyze=True) and the obs_level="off" zero-overhead
contract the bench relies on."""

import json
import os
import threading

import numpy as np
import pytest

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.obs.events import (EventLog, SCHEMA_VERSION, iter_events,
                                   read_events)
from matrel_tpu.obs.metrics import MetricsRegistry
from matrel_tpu.session import MatrelSession


@pytest.fixture
def chain3(mesh8, rng):
    """The 3-matrix chain demo shape: (64x96)(96x128)(128x32)."""
    A = BlockMatrix.from_numpy(
        rng.standard_normal((64, 96)).astype(np.float32), mesh=mesh8)
    B = BlockMatrix.from_numpy(
        rng.standard_normal((96, 128)).astype(np.float32), mesh=mesh8)
    C = BlockMatrix.from_numpy(
        rng.standard_normal((128, 32)).astype(np.float32), mesh=mesh8)
    return A.expr() @ B.expr() @ C.expr()


def _session(mesh, tmp_path, level="on", **cfg):
    return MatrelSession(mesh=mesh, config=MatrelConfig(
        obs_level=level,
        obs_event_log=str(tmp_path / "events.jsonl"), **cfg))


class TestMetricsRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("plan_cache.hit")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        # same name → same counter; distinct names are independent
        assert reg.counter("plan_cache.hit") is c
        assert reg.counter("plan_cache.miss").value == 0.0

    def test_gauge_last_write_wins(self):
        reg = MetricsRegistry()
        g = reg.gauge("plan_cache.plans")
        g.set(3)
        g.set(1)
        assert g.value == 1.0

    def test_histogram_semantics(self):
        reg = MetricsRegistry()
        h = reg.histogram("query.execute_ms")
        for v in (4.0, 1.0, 3.0, 2.0):
            h.observe(v)
        assert h.count == 4
        assert h.total == 10.0
        assert h.mean == 2.5
        assert (h.min, h.max) == (1.0, 4.0)
        assert h.percentile(0.0) == 1.0
        assert h.percentile(1.0) == 4.0
        s = h.summary()
        assert s["count"] == 4 and s["mean"] == 2.5

    def test_histogram_sketch_bounded(self):
        # the bounded-memory contract moved from a sample reservoir
        # to the quantile sketch: bucket count stays capped no matter
        # how many observations (or how wide their range), all-time
        # count/min/max stay exact
        from matrel_tpu.obs import metrics as m
        reg = MetricsRegistry()
        h = reg.histogram("x")
        n = 3 * m._MAX_BUCKETS
        for v in range(n):
            h.observe(float(v) * 1e3 + 0.5)
        assert h.count == n                          # all-time stats kept
        assert len(h._sketch._buckets) <= m._MAX_BUCKETS
        assert h.max == float(n - 1) * 1e3 + 0.5

    def test_snapshot_and_reset(self):
        reg = MetricsRegistry()
        reg.counter("a").inc(2)
        reg.gauge("b").set(7)
        reg.histogram("c").observe(1.5)
        snap = reg.snapshot()
        assert snap["counters"]["a"] == 2.0
        assert snap["gauges"]["b"] == 7.0
        assert snap["histograms"]["c"]["count"] == 1
        json.dumps(snap)                            # JSON-ready contract
        reg.reset()
        assert reg.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}

    def test_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("n")
        h = reg.histogram("h")

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(1.0)

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000
        assert h.count == 8000 and h.total == 8000.0


class TestEventLog:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path)
        written = log.emit("query", {"query_id": "q1", "execute_ms": 1.25,
                                     "out_shape": [4, 4]})
        assert written["schema"] == SCHEMA_VERSION
        assert written["kind"] == "query" and "ts" in written
        [back] = read_events(path)
        assert back == json.loads(json.dumps(written))

    def test_numpy_values_serialise(self, tmp_path):
        log = EventLog(str(tmp_path / "ev.jsonl"))
        log.emit("query", {"nnz": np.int64(7), "ms": np.float32(1.5),
                           "shape": np.array([2, 3])})
        [rec] = read_events(log.path)
        assert rec["nnz"] == 7 and rec["shape"] == [2, 3]

    def test_reader_skips_garbage_and_foreign_schema(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        EventLog(path).emit("query", {"query_id": "q1"})
        with open(path, "a") as f:
            f.write("{truncated mid-cra\n")               # crashed writer
            f.write(json.dumps({"schema": SCHEMA_VERSION + 99,
                                "kind": "query"}) + "\n")  # future schema
            f.write("[1, 2]\n")                            # non-record
        recs = read_events(path)
        assert len(recs) == 1 and recs[0]["query_id"] == "q1"

    def test_missing_file_reads_empty(self, tmp_path):
        assert read_events(str(tmp_path / "nope.jsonl")) == []
        assert list(iter_events(str(tmp_path / "nope.jsonl"))) == []

    def test_emit_never_raises(self, tmp_path):
        log = EventLog(str(tmp_path / "no" / "such" / "dir" / "ev.jsonl"))
        assert log.emit("query", {"query_id": "q1"}) is None   # swallowed


class TestEventLogRotation:
    """obs_event_log_max_bytes: single-``.1``-sibling rotation with
    transparent reader stitching; 0 (the default) keeps the historical
    unbounded append byte-for-byte."""

    def _emit_n(self, log, n, start=0):
        for i in range(start, start + n):
            log.emit("query", {"seq": i})

    def test_off_path_never_rotates(self, tmp_path):
        from matrel_tpu.obs.events import rotated_path
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path)               # max_bytes=0: historical
        self._emit_n(log, 50)
        assert not os.path.exists(rotated_path(path))
        recs = read_events(path)
        assert [r["seq"] for r in recs] == list(range(50))
        # byte-identical off-path: exactly one line per record, no
        # truncation, no sibling — the pre-rotation file shape
        with open(path) as f:
            assert sum(1 for _ in f) == 50

    def test_rotates_to_single_sibling_and_readers_stitch(
            self, tmp_path):
        from matrel_tpu.obs.events import rotated_path
        path = str(tmp_path / "ev.jsonl")
        probe = EventLog(path)
        probe.emit("query", {"seq": -1})
        line_sz = os.path.getsize(path)
        os.remove(path)
        # threshold = ~8 lines: one crossing over a 12-record stream
        log = EventLog(path, max_bytes=8 * line_sz)
        self._emit_n(log, 12)
        assert os.path.exists(rotated_path(path))
        # the pair stitches oldest-first into one continuous history
        recs = read_events(path)
        assert [r["seq"] for r in recs] == list(range(12))
        # and iter_events yields the same order
        assert [r["seq"] for r in iter_events(path)] == list(range(12))

    def test_rotation_bounds_disk_at_two_files(self, tmp_path):
        from matrel_tpu.obs.events import rotated_path
        path = str(tmp_path / "ev.jsonl")
        probe = EventLog(path)
        probe.emit("query", {"seq": -1})
        line_sz = os.path.getsize(path)
        os.remove(path)
        log = EventLog(path, max_bytes=4 * line_sz)
        self._emit_n(log, 40)              # many crossings
        # a crossing rotates the main file away; the next emit
        # recreates it — either way disk stays ~2x the threshold
        main_sz = os.path.getsize(path) if os.path.exists(path) else 0
        assert main_sz <= 5 * line_sz
        assert os.path.getsize(rotated_path(path)) <= 5 * line_sz
        # the history window is the newest suffix, ending at the last
        # record — rotation REPLACES the sibling, never accumulates
        seqs = [r["seq"] for r in read_events(path)]
        assert seqs == list(range(seqs[0], 40))
        assert not os.path.exists(path + ".2")

    def test_tail_bytes_spans_both_files(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        probe = EventLog(path)
        probe.emit("query", {"seq": -1})
        line_sz = os.path.getsize(path)
        os.remove(path)
        log = EventLog(path, max_bytes=8 * line_sz)
        self._emit_n(log, 10)              # .1 holds 0..7, main 8..9
        # a tail budget bigger than the main file reaches into the
        # sibling's tail (its cut-off first line dropped, not corrupt)
        recs = read_events(path, tail_bytes=5 * line_sz + 10)
        seqs = [r["seq"] for r in recs]
        assert seqs == seqs and seqs[-1] == 9
        assert 2 <= len(seqs) <= 6
        assert seqs == list(range(10 - len(seqs), 10))
        # a budget inside the main file never opens the sibling
        recs = read_events(path, tail_bytes=line_sz + 5)
        assert [r["seq"] for r in recs] == [9]

    def test_rotate_mid_read_never_raises(self, tmp_path):
        # the reader's stat/open race: the main file rotates away
        # between the size probe and the open — the reader continues
        # with what it can open, never raises
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path)
        self._emit_n(log, 6)
        real_open = open

        def racing_open(fpath, *a, **kw):
            if fpath == path and os.path.exists(path):
                os.replace(path, path + ".1")  # rotation wins the race
            return real_open(fpath, *a, **kw)

        import builtins
        orig = builtins.open
        builtins.open = racing_open
        try:
            recs = list(iter_events(path))
        finally:
            builtins.open = orig
        # .1 was read before the race hit the main file; nothing lost
        assert [r["seq"] for r in recs] == list(range(6))

    def test_many_writers_interleave_whole_lines(self, tmp_path,
                                                 caplog):
        # O_APPEND + one write() per record: 8 writers x 200 records
        # on one path produce 1600 parseable lines and ZERO corrupt-
        # line warnings from the reader
        path = str(tmp_path / "ev.jsonl")

        def work(w):
            log = EventLog(path)
            for i in range(200):
                log.emit("query", {"w": w, "i": i})

        ts = [threading.Thread(target=work, args=(w,))
              for w in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        with caplog.at_level("WARNING", logger="matrel_tpu.obs"):
            recs = read_events(path)
        assert len(recs) == 1600
        per_writer = {}
        for r in recs:
            per_writer.setdefault(r["w"], []).append(r["i"])
        # every writer's records all landed, in ITS OWN order
        assert all(v == list(range(200))
                   for v in per_writer.values())
        assert not [m for m in caplog.messages if "corrupt" in m]

    def test_torn_line_counted_and_warned(self, tmp_path, caplog):
        # a crashed writer's partial line: the reader skips it,
        # COUNTS it, and warns once (the robust-reader contract) —
        # same across the rotation pair
        from matrel_tpu.obs.events import rotated_path
        path = str(tmp_path / "ev.jsonl")
        log = EventLog(path)
        self._emit_n(log, 2)
        with open(rotated_path(path), "w") as f:
            f.write('{"schema": 1, "kind": "query", "seq": -2}\n')
            f.write('{"torn mid-wri\n')
        with caplog.at_level("WARNING", logger="matrel_tpu.obs"):
            recs = read_events(path)
        assert [r["seq"] for r in recs] == [-2, 0, 1]
        assert any("1 corrupt line" in m for m in caplog.messages)

    def test_session_knob_flows_and_log_rebuilds(self, mesh8,
                                                 tmp_path, chain3):
        from matrel_tpu.obs.events import rotated_path
        sess = _session(mesh8, tmp_path, obs_event_log_max_bytes=600)
        for _ in range(6):
            sess.run(chain3)
        path = str(tmp_path / "events.jsonl")
        assert os.path.exists(rotated_path(path))
        # the readers (history et al. route through read_events) see
        # a continuous stitched history ending at the newest record
        recs = read_events(path)
        assert any(r["kind"] == "query" for r in recs)
        # flipping the knob rebuilds the session's writer
        sess.config = sess.config.replace(obs_event_log_max_bytes=0)
        assert sess._obs_event_log().max_bytes == 0


class TestSessionEvents:
    def test_one_record_per_run_with_cache_outcomes(self, mesh8, tmp_path,
                                                    chain3):
        sess = _session(mesh8, tmp_path)
        sess.run(chain3)
        sess.run(chain3)
        recs = read_events(sess.config.obs_event_log,
                           kinds=("query",))
        assert len(recs) == 2                  # exactly one per run
        first, second = recs
        assert first["cache"] == "miss" and second["cache"] == "hit"
        assert first["query_id"] != second["query_id"]
        for r in recs:
            # the documented schema (docs/OBSERVABILITY.md)
            assert r["schema"] == SCHEMA_VERSION and r["kind"] == "query"
            assert r["source"] == "dsl"
            assert r["out_shape"] == [64, 32]
            assert isinstance(r["execute_ms"], (int, float))
            assert isinstance(r["matmuls"], list) and len(r["matmuls"]) == 2
            for d in r["matmuls"]:
                assert {"uid", "strategy", "source", "flops",
                        "dims"} <= set(d)
            assert "plans" in r["plan_cache"]
        # compile-time fields come from the plan meta (shared by both)
        assert isinstance(first["optimize_ms"], (int, float))
        assert first["first_execution"] is True
        assert second["first_execution"] is False

    def test_metrics_registry_updated(self, mesh8, tmp_path, chain3):
        from matrel_tpu.obs.metrics import REGISTRY
        REGISTRY.reset()
        sess = _session(mesh8, tmp_path)
        sess.run(chain3)
        sess.run(chain3)
        snap = REGISTRY.snapshot()
        assert snap["counters"]["query.count"] == 2
        assert snap["counters"]["plan_cache.miss"] == 1
        assert snap["counters"]["plan_cache.hit"] == 1
        assert snap["histograms"]["query.execute_ms"]["count"] == 2
        REGISTRY.reset()

    def test_chain_dp_not_counted_for_plain_matmul(self, mesh8, rng):
        # reorder_chains rebuilds matmul nodes even when it keeps the
        # parenthesisation — a plain 2-operand matmul must not count as
        # a chain_dp restructure
        from matrel_tpu.ir import rules
        a = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        b = BlockMatrix.from_numpy(
            rng.standard_normal((8, 8)).astype(np.float32), mesh=mesh8)
        counts = {}
        rules.optimize(a.expr() @ b.expr(), counts=counts)
        assert "chain_dp" not in counts

    def test_rule_hits_compile_scoped(self, mesh8, tmp_path, chain3):
        """Hit records carry {} rule_hits (rules fired once, at
        compile), so history's roll-up counts real optimizer work."""
        from matrel_tpu.obs.metrics import REGISTRY
        REGISTRY.reset()
        sess = _session(mesh8, tmp_path)
        sess.run(chain3)
        sess.run(chain3)
        miss, hit = read_events(sess.config.obs_event_log,
                                kinds=("query",))
        assert miss["rule_hits"].get("chain_dp") == 1
        assert hit["rule_hits"] == {}
        assert REGISTRY.snapshot()["counters"]["optimizer.rule.chain_dp"] \
            == 1
        REGISTRY.reset()

    def test_scalar_sql_still_returns_plain_number(self, mesh8,
                                                   tmp_path):
        # the _sql_hash stamp must not break scalar-only queries, which
        # compile to a plain float rather than a MatExpr
        sess = _session(mesh8, tmp_path)
        assert sess.sql("2 * 3") == 6.0

    def test_sql_source_hash(self, mesh8, tmp_path, rng):
        sess = _session(mesh8, tmp_path)
        a = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32), mesh=mesh8)
        sess.register("A", a)
        sess.run(sess.sql("SELECT A * A FROM A"))
        [rec] = read_events(sess.config.obs_event_log,
                            kinds=("query",))
        assert rec["source"] == "sql"
        assert len(rec["source_hash"]) == 16

    def test_eviction_counted(self, mesh8, tmp_path, rng):
        sess = _session(mesh8, tmp_path, plan_cache_max_plans=2)
        for i in range(4):
            # a shape each: a new array of a known shape is no new plan
            # (a template answers it)
            m = BlockMatrix.from_numpy(
                rng.standard_normal((8, 8 * (i + 1))).astype(np.float32),
                mesh=mesh8)
            sess.run(m.expr().t())
        recs = read_events(sess.config.obs_event_log,
                           kinds=("query",))
        assert recs[-1]["plan_cache"]["evicted"] == 2
        assert sess.plan_cache_info()["evicted"] == 2


class TestExplainAnalyze:
    def test_one_timed_row_per_physical_op(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path)
        text = sess.explain(chain3, analyze=True)
        assert "== Analyzed physical plan" in text
        plan = sess.compile(chain3)

        def uids(n, acc):
            acc.add(n.uid)
            for c in n.children:
                uids(c, acc)
            return acc

        n_ops = len(uids(plan.optimized, set()))
        analyzed = text.split("== Analyzed physical plan")[1]
        assert analyzed.count(" ms]") == n_ops
        # the chain demo acceptance surface: strategy + estimated bytes
        # on every matmul row, and the fused-program line
        matmul_rows = [ln for ln in analyzed.splitlines()
                       if ln.lstrip().startswith("matmul")]
        assert len(matmul_rows) == 2
        for row in matmul_rows:
            assert "strategy=" in row and "est_ici=" in row
        assert "fused program:" in analyzed

    def test_per_op_times_are_exclusive(self, mesh8, tmp_path, chain3):
        """ev() recurses through _eval, so naive timing would report
        each parent inclusive of its children (~depth x the real
        runtime when summed); the hook must subtract child frames."""
        from matrel_tpu.obs.analyze import measure_per_op
        sess = _session(mesh8, tmp_path)
        plan = sess.compile(chain3)
        per_op, eager_total = measure_per_op(plan)
        total = sum(s for _, s in per_op.values())
        # exclusive times sum to at most the whole eager run (plus a
        # little hook overhead); inclusive times would sum to ~2x+ on
        # this depth-3 tree
        assert total <= eager_total * 1.1 + 0.05

    def test_analyze_requires_physical(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path)
        with pytest.raises(ValueError, match="physical"):
            sess.explain(chain3, physical=False, analyze=True)

    def test_explain_sql_analyze(self, mesh8, tmp_path, rng):
        sess = _session(mesh8, tmp_path)
        a = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32), mesh=mesh8)
        sess.register("A", a)
        text = sess.explain_sql("SELECT A * A FROM A", analyze=True)
        assert "== Analyzed physical plan" in text and " ms]" in text


class TestObsOffContract:
    """obs_level="off" (the bench default): zero events, zero extra
    syncs on the query path."""

    def test_no_events_no_syncs(self, mesh8, tmp_path, chain3,
                                monkeypatch):
        import jax
        emits = []
        monkeypatch.setattr(EventLog, "emit",
                            lambda self, *a, **k: emits.append(a))
        syncs = []
        real_sync = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: (syncs.append(1), real_sync(x))[1])
        sess = _session(mesh8, tmp_path, level="off")
        out = sess.run(chain3)
        assert out.shape == (64, 32)
        assert emits == []                      # zero events
        assert syncs == []                      # zero per-op syncs
        assert not (tmp_path / "events.jsonl").exists()

    def test_default_config_is_off(self):
        assert MatrelConfig().obs_level == "off"

    def test_obs_level_validated_and_normalised(self):
        # "OFF" must not silently enable instrumentation
        assert MatrelConfig(obs_level="OFF").obs_level == "off"
        assert MatrelConfig(obs_level="Analyze").obs_level == "analyze"
        with pytest.raises(ValueError, match="obs_level"):
            MatrelConfig(obs_level="of")


class TestHistory:
    def _seed_log(self, tmp_path):
        log = EventLog(str(tmp_path / "ev.jsonl"))
        for i, cache in enumerate(["miss", "hit", "hit"]):
            log.emit("query", {
                "query_id": f"q{i}", "source": "dsl", "cache": cache,
                "optimize_ms": 4.0, "execute_ms": 10.0,
                "out_shape": [4, 4],
                "rule_hits": {"fold_transpose": 1},
                "plan_cache": {"plans": 1, "evicted": 0},
                "matmuls": [{"uid": 1, "strategy": "rmm",
                             "flops": 1e9, "est_ici_bytes": 2.0 ** 20}]})
        return log.path

    def test_summarize(self, tmp_path):
        from matrel_tpu.obs.history import summarize
        s = summarize(read_events(self._seed_log(tmp_path)))
        assert s["queries"] == 3
        assert s["cache_hit_rate"] == pytest.approx(2 / 3, abs=1e-3)
        assert s["execute_ms_total"] == 30.0
        assert s["strategies"]["rmm"]["count"] == 3
        assert s["rule_hits"]["fold_transpose"] == 3

    def test_render_tables(self, tmp_path):
        from matrel_tpu.obs.history import render_queries, render_summary
        events = read_events(self._seed_log(tmp_path))
        table = render_queries(events, last=2)
        assert "q1" in table and "q2" in table and "q0" not in table
        summary = render_summary(events)
        assert "cache hit rate: 0.667" in summary
        assert "rmm" in summary

    def test_cli(self, tmp_path, capsys):
        import subprocess
        import sys
        path = self._seed_log(tmp_path)
        out = subprocess.run(
            [sys.executable, "-m", "matrel_tpu", "history", "--summary",
             "--log", path],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0
        assert "cache hit rate" in out.stdout


class TestInstrumentationGuard:
    def test_every_lowering_dispatch_is_annotated(self):
        """Structural check: each `self._eval(` dispatch call site in
        executor.py sits inside a `with annotate(` block, so a new op
        path can't silently skip the per-op scope/timing hook. Two
        sanctioned exceptions, both DELIBERATELY single-frame: the
        fused-region member sites (one `with annotate("matrel.fused:…")`
        frame covers the whole member set — that per-edge frame
        collapse IS the fusion design, docs/FUSION.md) and the
        unit-program seam (jitted region emission for the bench/
        autotune measurement harness) — each must say so inline."""
        import inspect
        from matrel_tpu import executor
        lines = inspect.getsource(executor).splitlines()
        sites = [i for i, ln in enumerate(lines)
                 if "self._eval(" in ln and "def _eval" not in ln]
        assert sites, "executor lost its central _eval dispatch"
        exempt = ("fused-region member", "unit-program member")
        for i in sites:
            if any(tag in lines[i] for tag in exempt):
                continue
            window = "\n".join(lines[max(0, i - 5):i])
            assert "with annotate(" in window, (
                f"executor.py line {i + 1}: lowering dispatch not "
                f"wrapped in annotate()")

    def test_programs_carry_stable_names(self, mesh8, chain3):
        """What a profiler trace names the program's device work by:
        the jitted plan (``XLA Modules``: ``jit_matrel_plan_<root
        kind>``) and the PageRank round loops carry stable names, not
        a closure's (the Pallas kernels' names are guarded per call
        site in TestProfilerTier)."""
        from matrel_tpu.workloads import pagerank as pr
        sess = MatrelSession(mesh=mesh8)
        plan = sess.compile(chain3)
        assert plan.jitted.__name__ == "matrel_plan_matmul"
        multi = sess._compile_multi_entry([chain3, chain3.t()])[0]
        assert multi.jitted.__name__ == "matrel_plan_multi"
        static = (64, 64, 64, 8)
        names = {
            pr._compact_runner_loop(64, 2, 0.85, static, 0, 3,
                                    True).__name__,
            pr._onehot_runner(64, 2, 0.85, static[:3], 4).__name__,
            pr._edges_runner(64, 2, 0.85)[1].__name__}
        assert names == {"matrel_pagerank_compact",
                         "matrel_pagerank_onehot",
                         "matrel_pagerank_segment"}


class TestTracingSpans:
    """Round 9 tentpole: parent-linked span records through admission →
    plan → verify → trace → execute, in the same schema-versioned log."""

    def test_query_spans_with_parent_links(self, mesh8, tmp_path,
                                           chain3):
        sess = _session(mesh8, tmp_path)
        sess.run(chain3)
        spans = [e for e in read_events(sess.config.obs_event_log)
                 if e["kind"] == "span"]
        names = {s["name"] for s in spans}
        assert {"compute", "plan", "compile", "plan.optimize",
                "plan.verify", "plan.trace", "dispatch",
                "query.execute"} <= names
        by_id = {s["span_id"]: s for s in spans}
        # every compile phase parent-links (transitively) to the query
        # root span — the chrome exporter's nesting source of truth
        root = next(s for s in spans if s["name"] == "compute")
        assert root["parent_id"] is None
        assert root["attrs"]["path"] == "observed"
        for name in ("plan.optimize", "query.execute", "dispatch"):
            s = next(x for x in spans if x["name"] == name)
            seen = set()
            while s["parent_id"] is not None:
                assert s["parent_id"] in by_id
                assert s["span_id"] not in seen
                seen.add(s["span_id"])
                s = by_id[s["parent_id"]]
            assert s["name"] == "compute"
            assert s["qid"] == root["qid"]
        # the compile phases nest in the miss's compile span, beside
        # the plan-cache probe that missed
        compile_ = next(s for s in spans if s["name"] == "compile")
        assert by_id[next(s for s in spans if s["name"] == "plan.trace")[
            "parent_id"]] is compile_
        assert next(s for s in spans
                    if s["name"] == "plan")["attrs"] == {"hit": False}
        assert compile_["attrs"]["executors"] \
            == sess.compile(chain3).meta["executors"]
        for s in spans:
            assert s["schema"] == SCHEMA_VERSION
            assert isinstance(s["dur_ms"], (int, float))
            assert isinstance(s["t0"], (int, float))

    def test_serve_batch_spans(self, mesh8, tmp_path, chain3, rng):
        sess = _session(mesh8, tmp_path)
        a = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        sess.run_many([chain3, a.expr().t(), a.expr()])
        spans = [e for e in read_events(sess.config.obs_event_log)
                 if e["kind"] == "span"]
        batch = next(s for s in spans if s["name"] == "serve.batch")
        assert batch["attrs"]["size"] == 3
        execute = next(s for s in spans if s["name"] == "serve.execute")
        # execute nests under the batch (possibly through "plan")
        by_id = {s["span_id"]: s for s in spans}
        p = execute
        while p["parent_id"] is not None:
            p = by_id[p["parent_id"]]
        assert p["span_id"] == batch["span_id"]

    def test_chrome_export_round_trip(self, mesh8, tmp_path, chain3):
        from matrel_tpu.obs.trace import chrome_trace
        sess = _session(mesh8, tmp_path)
        sess.run_many([chain3])
        events = read_events(sess.config.obs_event_log)
        doc = json.loads(json.dumps(chrome_trace(events)))
        assert doc["traceEvents"]
        ids = set()
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X"
            assert ev["dur"] >= 0 and ev["ts"] > 0
            assert {"pid", "tid", "name", "args"} <= set(ev)
            ids.add(ev["args"]["span_id"])
        # parent links survive the export (the Perfetto args payload)
        assert any(ev["args"].get("parent_id") in ids
                   for ev in doc["traceEvents"])

    def test_chrome_export_last_filters_roots(self, tmp_path):
        from matrel_tpu.obs.trace import chrome_trace
        log = EventLog(str(tmp_path / "sp.jsonl"))
        for root in (1, 4):
            log.emit("span", {"name": "query", "span_id": root,
                              "parent_id": None, "t0": 100.0 + root,
                              "dur_ms": 5.0, "pid": 1, "tid": 1})
            log.emit("span", {"name": "plan", "span_id": root + 1,
                              "parent_id": root, "t0": 100.0 + root,
                              "dur_ms": 2.0, "pid": 1, "tid": 1})
        doc = chrome_trace(read_events(log.path), last=1)
        got = {ev["args"]["span_id"] for ev in doc["traceEvents"]}
        assert got == {4, 5}            # last root + its child only

    def test_chrome_export_last_keys_by_pid(self, tmp_path):
        """Span-id sequences restart per PROCESS; a shared log mixes
        pids by design, so the --last closure must never pull an
        unrelated process's identically-numbered spans."""
        from matrel_tpu.obs.trace import chrome_trace
        log = EventLog(str(tmp_path / "sp.jsonl"))
        for pid, t0 in ((111, 100.0), (222, 200.0)):
            log.emit("span", {"name": "query", "span_id": 1,
                              "parent_id": None, "t0": t0,
                              "dur_ms": 5.0, "pid": pid, "tid": 1})
            log.emit("span", {"name": "plan", "span_id": 2,
                              "parent_id": 1, "t0": t0,
                              "dur_ms": 2.0, "pid": pid, "tid": 1})
        doc = chrome_trace(read_events(log.path), last=1)
        assert {ev["pid"] for ev in doc["traceEvents"]} == {222}
        assert len(doc["traceEvents"]) == 2

    def test_trace_cli(self, mesh8, tmp_path, chain3):
        import subprocess
        import sys
        sess = _session(mesh8, tmp_path)
        sess.run(chain3)
        out_path = str(tmp_path / "trace.chrome.json")
        out = subprocess.run(
            [sys.executable, "-m", "matrel_tpu", "trace", "--export",
             "chrome", "--log", sess.config.obs_event_log,
             "--out", out_path],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        status = json.loads(out.stdout.strip().splitlines()[-1])
        assert status["spans"] > 0
        with open(out_path) as f:
            doc = json.load(f)
        assert len(doc["traceEvents"]) == status["spans"]


class TestFlightRecorder:
    """The always-cheap post-mortem ring: independent of obs_level,
    dumped on failures or on demand."""

    def test_records_spans_with_obs_off(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path, level="off",
                        obs_flight_recorder=64,
                        obs_flight_recorder_path=str(
                            tmp_path / "flight.json"))
        sess.run(chain3)
        # no event log (obs off) — but the ring holds the span trail
        assert not (tmp_path / "events.jsonl").exists()
        assert len(sess._flight) > 0
        names = {r["name"] for r in sess._flight.snapshot()
                 if r.get("kind") == "span"}
        assert {"compute", "plan.optimize", "dispatch"} <= names

    def test_ring_is_bounded(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path, level="off",
                        obs_flight_recorder=4)
        for _ in range(3):
            sess.run(chain3)
        assert len(sess._flight) == 4          # last N only

    def test_explicit_dump_round_trip(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path, obs_flight_recorder=64,
                        obs_flight_recorder_path=str(
                            tmp_path / "flight.json"))
        sess.run(chain3)
        p = sess.dump_flight_recorder()
        assert p == str(tmp_path / "flight.json")
        with open(p) as f:
            art = json.load(f)
        assert art["schema"] == SCHEMA_VERSION
        assert art["kind"] == "flight_recorder"
        assert art["reason"] == "explicit"
        assert art["capacity"] == 64
        kinds = {r.get("kind") for r in art["records"]}
        assert "span" in kinds and "query" in kinds  # obs on: both flow

    def test_dump_disabled_returns_none(self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path)       # recorder off (default)
        sess.run(chain3)
        assert sess._flight is None
        assert sess.dump_flight_recorder() is None

    def test_dump_on_compile_failure(self, mesh8, tmp_path, chain3,
                                     monkeypatch):
        from matrel_tpu import executor as executor_lib
        sess = _session(mesh8, tmp_path, obs_flight_recorder=64,
                        obs_flight_recorder_path=str(
                            tmp_path / "flight.json"))
        sess.run(chain3)                       # populate the ring

        def boom(*a, **k):
            raise RuntimeError("lowering exploded")

        monkeypatch.setattr(executor_lib, "compile_expr", boom)
        with pytest.raises(RuntimeError, match="lowering exploded"):
            sess.run(chain3.t())               # distinct key → compile
        with open(tmp_path / "flight.json") as f:
            art = json.load(f)
        assert art["reason"] == "compile_failure"
        assert "lowering exploded" in art["error"]
        assert art["records"]                  # the trail, not a bare
                                               # error string

    def test_dump_on_verification_error(self, mesh8, tmp_path, chain3,
                                        monkeypatch):
        from matrel_tpu import executor as executor_lib
        from matrel_tpu.analysis import VerificationError
        sess = _session(mesh8, tmp_path, obs_flight_recorder=64,
                        obs_flight_recorder_path=str(
                            tmp_path / "flight.json"))

        def boom(*a, **k):
            raise VerificationError([])

        monkeypatch.setattr(executor_lib, "compile_expr", boom)
        with pytest.raises(VerificationError):
            sess.run(chain3)
        with open(tmp_path / "flight.json") as f:
            art = json.load(f)
        assert art["reason"] == "verification_error"

    def test_dump_on_serve_batch_failure(self, mesh8, tmp_path, chain3,
                                         monkeypatch):
        from matrel_tpu import executor as executor_lib
        sess = _session(mesh8, tmp_path, obs_flight_recorder=64,
                        obs_flight_recorder_path=str(
                            tmp_path / "flight.json"))

        def boom(*a, **k):
            raise RuntimeError("batch compile died")

        monkeypatch.setattr(executor_lib, "compile_exprs", boom)
        fut = sess.submit(chain3)
        with pytest.raises(RuntimeError, match="batch compile died"):
            fut.result(timeout=30)
        sess.serve_drain()
        with open(tmp_path / "flight.json") as f:
            art = json.load(f)
        assert art["reason"] == "serve_batch_failure"


class TestObsOffServePath:
    """obs_level="off" + flight recorder off on the serve repeated-
    traffic path: zero events, zero span OBJECTS (the structural twin
    of TestObsOffContract's zero-sync guard — PR 5's QPS must not pay
    for tier 2)."""

    def test_repeated_serve_path_creates_no_spans(self, mesh8, tmp_path,
                                                  chain3, rng,
                                                  monkeypatch):
        from matrel_tpu.obs import trace as trace_lib
        sess = _session(mesh8, tmp_path, level="off",
                        result_cache_max_bytes=1 << 26)
        assert sess._tracer is None and sess._flight is None
        a = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32),
            mesh=mesh8)
        stream = [chain3, a.expr().t()]
        sess.run_many(stream)                  # warm: compiles once
        emits = []
        monkeypatch.setattr(EventLog, "emit",
                            lambda self, *args, **kw: emits.append(args))

        def no_spans(*a, **k):
            raise AssertionError(
                "span object constructed on the off-path serve loop")

        monkeypatch.setattr(trace_lib.Span, "__init__", no_spans)
        outs = sess.run_many(stream)           # repeated traffic:
        assert len(outs) == 2                  # rc/plan-cache hits only
        assert emits == []


def _pallas_call_sites():
    """(file, line, call node) of every ``pallas_call`` in the package."""
    import ast
    import glob
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "matrel_tpu")
    sites = []
    for path in sorted(glob.glob(os.path.join(root, "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites.append((os.path.relpath(path, root), node.lineno,
                              node))
    return sites


_PALLAS_SITES = _pallas_call_sites()


class TestProfilerTier:
    """The third activation tier: a running ``jax.profiler`` session
    makes every span live on the default-config path — a
    ``TraceAnnotation`` on the profiler's host plane and a record in
    the process-wide ring — and with none running the path constructs
    nothing."""

    @staticmethod
    def _trace(tmp_path):
        import contextlib
        import jax

        @contextlib.contextmanager
        def session():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path / "prof"),
                                     profiler_options=opts)
            try:
                yield
            finally:
                jax.profiler.stop_trace()
        return session()

    @staticmethod
    def _sql_session(mesh8, rng):
        sess = MatrelSession(mesh=mesh8)        # the default config
        assert sess._tracer is None and sess._flight is None
        for name in ("A", "B"):
            sess.register(name, BlockMatrix.from_numpy(
                rng.standard_normal((32, 32)).astype(np.float32),
                mesh=mesh8))
        return sess

    def test_fast_path_spans_nest_and_share_qid(self, mesh8, rng,
                                                tmp_path):
        from matrel_tpu.obs import trace as trace_lib
        sess = self._sql_session(mesh8, rng)
        q = "rowsum(A * B)"
        want = sess.compute(sess.sql(q)).to_numpy()     # warm
        before = len(trace_lib.profile_spans())
        with self._trace(tmp_path):
            got = sess.compute(sess.sql(q)).to_numpy()
        np.testing.assert_array_equal(got, want)
        # a collection that falls into the query is a span too (PR 35)
        recs = [r for r in trace_lib.profile_spans()[before:]
                if r["name"] != "matrel.gc"]
        by_name = {r["name"]: r for r in recs}
        assert set(by_name) == {"matrel.sql", "matrel.compute",
                                "matrel.plan", "matrel.dispatch",
                                "matrel.dispatch.launch", "matrel.fetch",
                                "matrel.fetch.wait", "matrel.fetch.copy"}
        comp = by_name["matrel.compute"]
        assert comp["parent_id"] is None
        assert comp["attrs"] == {"root_kind": "agg", "path": "fast"}
        # PR 35: what is beneath dispatch and fetch, each inside its own
        for child, parent in (("matrel.plan", comp),
                              ("matrel.dispatch", comp),
                              ("matrel.dispatch.launch",
                               by_name["matrel.dispatch"]),
                              ("matrel.fetch.wait", by_name["matrel.fetch"]),
                              ("matrel.fetch.copy", by_name["matrel.fetch"])):
            c = by_name[child]
            assert c["parent_id"] == parent["span_id"]
            assert c["qid"] == parent["qid"]
            assert parent["start_ns"] <= c["start_ns"] <= c["end_ns"] \
                <= parent["end_ns"]
        assert by_name["matrel.fetch.wait"]["end_ns"] \
            <= by_name["matrel.fetch.copy"]["start_ns"]
        assert by_name["matrel.plan"]["attrs"] == {"hit": True}
        assert by_name["matrel.dispatch"]["attrs"]["executors"] \
            == sess.compile(sess.sql(q)).meta["executors"]
        assert by_name["matrel.sql"]["attrs"] == {"chars": len(q)}
        fetched = by_name["matrel.fetch"]["attrs"]
        assert set(fetched) == {"ready", "bytes"}
        assert type(fetched["ready"]) is bool
        assert fetched["bytes"] == got.nbytes
        # roots of their own: three entry calls, three qids
        assert len({by_name[n]["qid"] for n in (
            "matrel.sql", "matrel.compute", "matrel.fetch")}) == 3
        # the same spans lie on the profiler's host plane, with the qid
        from jax.profiler import ProfileData
        import glob
        [path] = glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                               / "*" / "*.xplane.pb"))
        events = [(ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("matrel.")
                  and ev.name != "matrel.gc"]
        assert sorted(n for n, _ in events) == sorted(by_name)
        assert {n: st["qid"] for n, st in events} \
            == {n: r["qid"] for n, r in by_name.items()}
        # and with the session over, the path is dark again
        traced = len(trace_lib.profile_spans())
        sess.compute(sess.sql(q)).to_numpy()
        assert len(trace_lib.profile_spans()) == traced

    def test_collector_pause_is_a_span_while_live(self, mesh8, rng,
                                                  tmp_path):
        """``gc.callbacks`` holds the callback from the first live entry
        to the first collection that finds no session; a collection in
        between is ``matrel.gc`` with ``generation`` and ``collected``,
        a child of the span it interrupted or a root."""
        import gc
        from matrel_tpu.obs import trace as trace_lib
        sess = self._sql_session(mesh8, rng)
        q = "rowsum(A * B)"
        sess.compute(sess.sql(q)).to_numpy()            # warm
        gc.collect()        # one left by an earlier test takes itself out
        assert trace_lib._on_gc not in gc.callbacks
        before = len(trace_lib.profile_spans())

        class Cycle:
            def __init__(self):
                self.me = self

        with self._trace(tmp_path):
            assert trace_lib._on_gc not in gc.callbacks  # no entry yet
            sess.sql(q)
            assert gc.callbacks.count(trace_lib._on_gc) == 1
            Cycle()
            gc.collect()                                # between queries
            with trace_lib.entry("outer"):
                gc.collect(0)                           # inside a span
            assert gc.callbacks.count(trace_lib._on_gc) == 1
        recs = trace_lib.profile_spans()[before:]
        outer = next(r for r in recs if r["name"] == "matrel.outer")
        full, young = [r for r in recs if r["name"] == "matrel.gc"
                       and r["attrs"]["generation"] in (2, 0)][-2:]
        assert full["attrs"]["generation"] == 2
        assert full["attrs"]["collected"] >= 1 and full["parent_id"] is None
        assert set(young["attrs"]) == {"generation", "collected"}
        assert young["parent_id"] == outer["span_id"]
        assert young["qid"] == outer["qid"]
        assert outer["start_ns"] <= young["start_ns"] <= young["end_ns"] \
            <= outer["end_ns"]
        # the session is over: the next collection finds none
        assert trace_lib._on_gc in gc.callbacks
        traced = len(trace_lib.profile_spans())
        gc.collect()
        assert trace_lib._on_gc not in gc.callbacks
        assert len(trace_lib.profile_spans()) == traced
        # and behind a later registrant it stays until it is the last
        # (the interpreter walks the list by index)
        def later(phase, info):
            pass
        with self._trace(tmp_path / "again"):
            sess.sql(q)
        gc.callbacks.append(later)
        try:
            gc.collect()
            assert trace_lib._on_gc in gc.callbacks
        finally:
            gc.callbacks.remove(later)
        gc.collect()
        assert trace_lib._on_gc not in gc.callbacks

    @pytest.mark.parametrize("entry", ["sql_compute_fetch", "run_many",
                                       "pagerank_edges"])
    def test_profiler_off_constructs_nothing(self, entry, mesh8, rng,
                                             monkeypatch):
        """The structural twin of
        test_repeated_serve_path_creates_no_spans for the default
        deployment: no Span, no TraceAnnotation, nothing in the ring."""
        import gc
        import jax
        from matrel_tpu.obs import trace as trace_lib
        from matrel_tpu.workloads import pagerank as pr
        sess = self._sql_session(mesh8, rng)
        src = rng.integers(0, 200, 1500).astype(np.int32)
        dst = rng.integers(0, 200, 1500).astype(np.int32)

        def run():
            if entry == "sql_compute_fetch":
                return sess.compute(sess.sql("rowsum(A * B)")).to_numpy()
            if entry == "run_many":
                return sess.run_many([sess.table("A").expr().t(),
                                      sess.sql("A * B")])[1].to_numpy()
            return np.asarray(pr.pagerank_edges(src, dst, 200, rounds=3,
                                                impl="onehot"))

        want = run()                                    # warm
        before = len(trace_lib.profile_spans())

        def poisoned(*a, **k):
            raise AssertionError("constructed with no profiler running")

        real = jax.profiler.TraceAnnotation.__init__

        def no_program_annotation(self, name, **kw):
            # jax annotates its own calls whether a session runs or not
            if name.startswith(trace_lib.PROFILE_PREFIX):
                poisoned()
            real(self, name, **kw)

        monkeypatch.setattr(trace_lib.Span, "__init__", poisoned)
        monkeypatch.setattr(jax.profiler.TraceAnnotation, "__init__",
                            no_program_annotation)
        # PR 35: dark, to_numpy is the one statement it was (no flag
        # read, no sync of its own) and nobody listens to the collector
        array_type = type(sess.table("A").data)
        monkeypatch.setattr(array_type, "is_ready", poisoned)
        monkeypatch.setattr(array_type, "block_until_ready", poisoned)
        gc.collect()        # a callback left by a traced test goes here
        callbacks = list(gc.callbacks)
        np.testing.assert_array_equal(run(), want)
        assert len(trace_lib.profile_spans()) == before
        assert gc.callbacks == callbacks
        assert trace_lib._on_gc not in callbacks

    def test_pagerank_spans_and_path_counts(self, rng, tmp_path,
                                            monkeypatch):
        from matrel_tpu.obs import trace as trace_lib
        from matrel_tpu.workloads import pagerank as pr
        monkeypatch.setattr(pr, "_PLAN_CACHE", [])
        monkeypatch.setattr(pr, "_PROBE_CHUNK", 512)
        src = rng.integers(0, 300, 2000).astype(np.int32)
        dst = rng.integers(0, 300, 2000).astype(np.int32)
        counts = pr.path_counts()
        before = len(trace_lib.profile_spans())
        with self._trace(tmp_path):
            for _ in range(2):
                pr.pagerank_edges(src, dst, 300, rounds=3, impl="onehot")
            pr.pagerank_edges(src, dst, 300, rounds=3, impl="segment")
        recs = [r for r in trace_lib.profile_spans()[before:]
                if r["name"] != "matrel.gc"]    # a collection's, PR 35
        roots = [r for r in recs if r["name"] == "matrel.pagerank"]
        assert [r["attrs"]["impl"] for r in roots] \
            == ["onehot", "onehot", "segment"]
        after = pr.path_counts()
        assert after["onehot"] == counts["onehot"] + 2
        assert after["segment"] == counts["segment"] + 1
        first, second, seg = (
            [r for r in recs if r["qid"] == root["qid"]
             and r is not root] for root in roots)
        # the second call knows its graph by comparing both arrays
        # with the copies the first call's plan kept: PR 53: their
        # first chunks, then the launch, then the rest behind it
        for kids, hit, known in (
                (first, False, {"bytes": 0, "how": "new",
                                "under_launch": False}),
                (second, True, {"bytes": 2 * 4 * 2000, "how": "compare",
                                "under_launch": True, "confirmed": True})):
            names = [r["name"] for r in sorted(
                kids, key=lambda r: r["start_ns"])]
            # PR 33: a build times its host fill and its upload
            assert names == (
                ["matrel.pagerank.plan", "matrel.pagerank.dispatch",
                 "matrel.pagerank.fingerprint"] if hit else
                ["matrel.pagerank.fingerprint", "matrel.pagerank.plan",
                 "matrel.pagerank.plan.build",
                 "matrel.pagerank.plan.upload",
                 "matrel.pagerank.dispatch"])
            by = {r["name"]: r for r in kids}
            said = by["matrel.pagerank.plan"]["attrs"]
            assert said["hit"] is hit
            # PR 33: on a hit as on a build, the plan's layout
            # PR 36: and its hub table, none in the blocks layout
            assert set(said) == {
                "hit", "layout", "edges", "slots", "chunks", "chunk",
                "overflow_edges", "row_values", "panels", "plan_bytes",
                "hubs", "hub_slots", "hub_chunks", "hub_walk_rows"}
            assert said["layout"] == "blocks" and said["panels"] == 1
            assert said["hubs"] == said["hub_slots"] == said["hub_chunks"] \
                == said["hub_walk_rows"] == 0
            assert said["slots"] == said["chunks"] * said["chunk"]
            assert by["matrel.pagerank.fingerprint"]["attrs"] == known
        assert [r["name"] for r in seg] == ["matrel.pagerank.dispatch"]

    def test_serve_batch_span_carries_queue_wait(self, mesh8, tmp_path,
                                                 chain3):
        """``queue_wait_ms`` per query rides the ``serve.batch`` span,
        so it is readable with obs off (here: the flight recorder)."""
        sess = _session(mesh8, tmp_path, level="off",
                        obs_flight_recorder=64)
        sess.submit(chain3).result(timeout=120)
        sess.serve_close()
        batch = next(r for r in sess._flight.snapshot()
                     if r.get("name") == "serve.batch")
        [wait] = batch["attrs"]["queue_wait_ms"]
        assert wait >= 0.0

    @pytest.mark.parametrize("kind", ["xla", "pallas_spmm"])
    def test_plan_meta_executors(self, kind, mesh8, rng):
        """Which executor a plan runs, publicly: ``plan.meta`` and
        ``plan_executors()`` (``plan_cache_info()`` rides every obs-on
        record and stays a len and two ints)."""
        from matrel_tpu.core.sparse import BlockSparseMatrix
        if kind == "xla":
            sess = MatrelSession(mesh=mesh8, config=MatrelConfig(
                strategy_override="xla"))
            a = BlockMatrix.from_numpy(
                rng.standard_normal((32, 32)).astype(np.float32),
                mesh=mesh8)
            e = a.expr() @ a.expr()
        else:
            import jax
            from matrel_tpu.core import mesh as mesh_lib
            one = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
            cfg = MatrelConfig(pallas_interpret=True)
            sess = MatrelSession(mesh=one, config=cfg)
            S = BlockSparseMatrix.random((256, 256), block_density=0.5,
                                         block_size=128, mesh=one,
                                         seed=3, config=cfg)
            D = BlockMatrix.random((256, 128), mesh=one, seed=4,
                                   config=cfg)
            e = S.multiply(D)
        plan = sess.compile(e)
        assert plan.meta["executors"] == [kind]
        assert sess.plan_executors() == [[kind]]
        assert "executors" not in sess.plan_cache_info()

    @pytest.mark.parametrize(
        "site", _PALLAS_SITES,
        ids=[f"{f}:{ln}" for f, ln, _ in _PALLAS_SITES])
    def test_every_pallas_call_has_a_stable_name(self, site):
        """A kernel's name in the device trace is what its
        ``pallas_call`` is given, not a closure's: a literal starting
        ``matrel_`` at every call site."""
        import ast
        _, _, call = site
        name = next((kw.value for kw in call.keywords
                     if kw.arg == "name"), None)
        assert isinstance(name, ast.Constant), "no literal name="
        assert isinstance(name.value, str) \
            and name.value.startswith("matrel_")

    def test_all_pallas_call_sites_were_found(self):
        # PR 36: the hub scatter; PR 44: less the routed SpMV's two;
        # PR 47: the sampled scatter; PR 50: the chunk grid's reduction;
        # PR 51: the hub chunks' reduction; PR 54: the fused chain;
        # PR 55: the Gram's triangle; PR 57: the sampled product's dense
        # lines
        assert len(_PALLAS_SITES) == 14


class TestAnalyzeEvent:
    """explain(analyze=True) with obs on emits one `analyze` record —
    the drift auditor's measured-vs-estimated feed."""

    def test_analyze_record_joins_per_op_to_decisions(self, mesh8,
                                                      tmp_path, chain3):
        sess = _session(mesh8, tmp_path)
        sess.explain(chain3, analyze=True)
        recs = [e for e in read_events(sess.config.obs_event_log)
                if e["kind"] == "analyze"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["backend"] == "cpu"
        assert rec["fused_ms"] > 0
        uids = {p["uid"] for p in rec["per_op"]}
        assert len(rec["matmuls"]) == 2
        for d in rec["matmuls"]:
            assert d["uid"] in uids            # the drift join key
        for p in rec["per_op"]:
            assert isinstance(p["ms"], (int, float))

    def test_no_analyze_event_when_obs_off(self, mesh8, tmp_path,
                                           chain3):
        sess = _session(mesh8, tmp_path, level="off")
        sess.explain(chain3, analyze=True)
        assert not (tmp_path / "events.jsonl").exists()


class TestDriftAuditor:
    """obs/drift.py: calibration ratios + the rank-order flag (the
    empirical complement of MV106) from a recorded log."""

    def _analyze_event(self, log, strategy, est_bytes, ms,
                       dims=(1024, 1024, 1024), uid=7):
        log.emit("analyze", {
            "backend": "cpu", "fused_ms": ms,
            "per_op": [{"uid": uid, "label": f"matmul:{strategy}",
                        "ms": ms}],
            "matmuls": [{"uid": uid, "strategy": strategy,
                         "dims": list(dims),
                         "flops": 2.0 * dims[0] * dims[1] * dims[2],
                         "est_ici_bytes": est_bytes}]})

    def _seed_miscalibrated(self, tmp_path):
        """cpmm estimated 4x CHEAPER than rmm but measured 3x SLOWER —
        the seeded drift the auditor must flag."""
        log = EventLog(str(tmp_path / "drift.jsonl"))
        for _ in range(3):
            self._analyze_event(log, "cpmm", est_bytes=1.0 * 2 ** 20,
                                ms=30.0)
            self._analyze_event(log, "rmm", est_bytes=4.0 * 2 ** 20,
                                ms=10.0)
        return log.path

    def test_calibration_rows(self, tmp_path):
        from matrel_tpu.obs import drift
        events = read_events(self._seed_miscalibrated(tmp_path))
        samples = list(drift.iter_samples(events))
        assert len(samples) == 6
        calib = drift.calibrate(samples)
        row = calib["cpmm|<=1024|cpu"]
        assert row["count"] == 3
        assert row["ms_median"] == 30.0
        assert row["ms_per_est_mib"] == pytest.approx(30.0)
        assert row["ms_per_gflop"] == pytest.approx(
            30.0 / (2.0 * 1024 ** 3 / 1e9))

    def test_rank_order_flag_fires_on_seeded_drift(self, tmp_path):
        from matrel_tpu.obs import drift
        events = read_events(self._seed_miscalibrated(tmp_path))
        flags = drift.rank_flags(list(drift.iter_samples(events)))
        assert len(flags) == 1
        fl = flags[0]
        assert fl["model_prefers"] == "cpmm"
        assert fl["measured_prefers"] == "rmm"
        assert fl["slowdown"] == pytest.approx(3.0)

    def test_agreeing_log_raises_no_flag(self, tmp_path):
        from matrel_tpu.obs import drift
        log = EventLog(str(tmp_path / "ok.jsonl"))
        self._analyze_event(log, "cpmm", est_bytes=1.0 * 2 ** 20,
                            ms=10.0)
        self._analyze_event(log, "rmm", est_bytes=4.0 * 2 ** 20,
                            ms=30.0)
        flags = drift.rank_flags(list(drift.iter_samples(
            read_events(log.path))))
        assert flags == []

    def test_query_samples_filtered(self, tmp_path):
        """Single-matmul query records feed the auditor; batched roots
        and rc hits (amortised / zero execute) must not."""
        from matrel_tpu.obs import drift
        log = EventLog(str(tmp_path / "q.jsonl"))
        base = {"source": "dsl", "out_shape": [4, 4], "backend": "cpu",
                "plan_cache": {},
                "matmuls": [{"uid": 1, "strategy": "rmm",
                             "dims": [64, 64, 64], "flops": 5e5,
                             "est_ici_bytes": 1024.0}]}
        log.emit("query", dict(base, cache="miss", execute_ms=5.0))
        log.emit("query", dict(base, cache="rc_hit", execute_ms=0.0))
        log.emit("query", dict(base, cache="hit", execute_ms=5.0,
                               batch={"size": 4, "index": 0}))
        samples = list(drift.iter_samples(read_events(log.path)))
        assert len(samples) == 1 and samples[0]["source"] == "query"

    def test_table_persist_and_merge(self, tmp_path):
        from matrel_tpu.obs import drift
        events = read_events(self._seed_miscalibrated(tmp_path))
        calib = drift.calibrate(list(drift.iter_samples(events)))
        path = str(tmp_path / "table.json")
        t1 = drift.update_table(path, calib)
        assert t1["entries"]["cpmm|<=1024|cpu"]["count"] == 3
        t2 = drift.update_table(path, calib)     # second session merges
        assert t2["entries"]["cpmm|<=1024|cpu"]["count"] == 6
        with open(path) as f:                    # artifact parses
            on_disk = json.load(f)
        assert on_disk["schema"] == drift.TABLE_SCHEMA
        # corrupt table reads as empty, never an error
        with open(path, "w") as f:
            f.write("{nope")
        assert drift.load_table(path)["entries"] == {}

    def test_history_drift_cli(self, tmp_path, capsys):
        from matrel_tpu.obs import history
        path = self._seed_miscalibrated(tmp_path)
        args = type("A", (), {
            "log": path, "summary": False, "last": None, "drift": True,
            "drift_table": str(tmp_path / "table.json"),
            "no_save": False})()
        assert history.main(args) == 0
        out = capsys.readouterr().out
        assert "DRIFT" in out and "model prefers cpmm" in out
        assert "calibration table" in out
        assert (tmp_path / "table.json").exists()

    def test_end_to_end_session_feeds_auditor(self, mesh8, tmp_path,
                                              chain3):
        """A recorded session (analyze + plain queries) must yield
        calibration rows through the real pipeline."""
        from matrel_tpu.obs import drift
        sess = _session(mesh8, tmp_path)
        sess.explain(chain3, analyze=True)
        events = read_events(sess.config.obs_event_log)
        report = drift.report(events, persist=False)
        assert "calibration row" in report
        assert len(drift.calibrate(
            list(drift.iter_samples(events)))) >= 1


class TestPhaseQuantiles:
    """Satellite: history --summary p50/p95 for optimize/trace/execute
    per query kind — since round 15 through the SHARED sketch
    definition (obs/metrics.percentile), so estimates agree with the
    nearest-rank oracle within the documented relative error."""

    def _seed(self, tmp_path):
        log = EventLog(str(tmp_path / "ev.jsonl"))
        for i in range(10):
            log.emit("query", {
                "query_id": f"m{i}", "root_kind": "matmul",
                "cache": "miss", "optimize_ms": float(i + 1),
                "trace_ms": 2.0 * (i + 1),
                "execute_ms": 10.0 * (i + 1),
                "out_shape": [4, 4], "plan_cache": {}, "matmuls": []})
        log.emit("query", {
            "query_id": "a0", "root_kind": "agg", "cache": "miss",
            "optimize_ms": 7.0, "trace_ms": None, "execute_ms": 3.0,
            "out_shape": [1, 1], "plan_cache": {}, "matmuls": []})
        return log.path

    def test_quantiles_per_kind(self, tmp_path):
        from matrel_tpu.obs.history import summarize
        s = summarize(read_events(self._seed(tmp_path)))
        pq = s["phase_quantiles"]
        mm = pq["matmul"]
        assert mm["count"] == 10
        # nearest-rank (lower) oracle over [1..10]: p50 -> rank
        # floor(.5*9)=4 -> 5.0, p95 -> rank floor(.95*9)=8 -> 9.0;
        # the sketch agrees within its documented 1% relative error
        # (obs/metrics.DEFAULT_ALPHA)
        from matrel_tpu.obs.metrics import DEFAULT_ALPHA
        rel = DEFAULT_ALPHA
        assert mm["optimize_ms"]["p50"] == pytest.approx(5.0, rel=rel)
        assert mm["optimize_ms"]["p95"] == pytest.approx(9.0, rel=rel)
        assert mm["execute_ms"]["p95"] == pytest.approx(90.0, rel=rel)
        agg = pq["agg"]
        assert agg["execute_ms"]["p50"] == pytest.approx(3.0, rel=rel)
        assert agg["trace_ms"]["p50"] is None   # Nones dropped, not 0

    def test_render_shows_phase_table(self, tmp_path):
        from matrel_tpu.obs.history import render_summary
        out = render_summary(read_events(self._seed(tmp_path)))
        assert "opt p50/p95" in out
        assert "matmul" in out and "agg" in out


class TestAxisBytesRollup:
    """Round 7: per-axis comm bytes (planner.matmul_decisions'
    est_axis_bytes) roll up per strategy in history --summary, so a
    regression shifting traffic onto the slow DCN axis shows in the
    event log even when the flat total holds."""

    def _seed(self, tmp_path):
        log = EventLog(str(tmp_path / "ax.jsonl"))
        for i in range(2):
            log.emit("query", {
                "query_id": f"q{i}", "source": "dsl", "cache": "miss",
                "execute_ms": 1.0, "out_shape": [4, 4],
                "plan_cache": {"plans": 1, "evicted": 0},
                "matmuls": [
                    {"uid": 1, "strategy": "rmm", "flops": 1e9,
                     "est_ici_bytes": 3.0 * 2 ** 20,
                     "est_axis_bytes": [1.0 * 2 ** 20, 2.0 * 2 ** 20],
                     "axis_weights": [1.0, 8.0]},
                    # legacy record without the field: must not crash
                    {"uid": 2, "strategy": "cpmm", "flops": 1e9,
                     "est_ici_bytes": 2.0 ** 20}]})
        return log.path

    def test_summarize_accumulates_per_axis(self, tmp_path):
        from matrel_tpu.obs.history import summarize
        s = summarize(read_events(self._seed(tmp_path)))
        rmm = s["strategies"]["rmm"]
        assert rmm["est_axis_bytes_x"] == pytest.approx(2.0 * 2 ** 20)
        assert rmm["est_axis_bytes_y"] == pytest.approx(4.0 * 2 ** 20)
        assert "est_axis_bytes_x" not in s["strategies"]["cpmm"]

    def test_render_shows_axis_column(self, tmp_path):
        from matrel_tpu.obs.history import render_summary
        out = render_summary(read_events(self._seed(tmp_path)))
        assert "axes x/y: 2.00/4.00 MiB" in out

    def test_weighted_query_event_carries_axis_bytes(self, tmp_path,
                                                     mesh8, rng):
        # end to end: an observed weighted session writes decisions
        # with the per-axis decomposition into the event log
        from matrel_tpu.session import MatrelSession
        cfg = MatrelConfig(obs_level="on",
                           obs_event_log=str(tmp_path / "q.jsonl"),
                           axis_cost_weights=(1.0, 8.0))
        sess = MatrelSession(mesh=mesh8, config=cfg)
        a = sess.from_numpy(
            rng.standard_normal((64, 32)).astype(np.float32))
        b = sess.from_numpy(
            rng.standard_normal((32, 16)).astype(np.float32))
        sess.compute(a.expr().multiply(b.expr()))
        (ev,) = [e for e in read_events(cfg.obs_event_log)
                 if e["kind"] == "query"]
        (d,) = ev["matmuls"]
        assert len(d["est_axis_bytes"]) == 2
        assert d["axis_weights"] == [1.0, 8.0]


class TestQuantileSketch:
    """Round 15 tentpole: the DDSketch-style streaming quantile sketch
    (obs/metrics.QuantileSketch) — accuracy vs numpy oracles across
    adversarial distributions, merge associativity, and the documented
    relative-error bound asserted at every tested q."""

    QS = (0.0, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)

    @staticmethod
    def _oracle(vals, q):
        # the sketch's stated definition: nearest-rank (lower) — the
        # value at 0-indexed rank floor(q*(n-1))
        return float(np.percentile(vals, q * 100.0, method="lower"))

    def _distributions(self):
        rng = np.random.default_rng(7)
        return {
            "uniform": rng.random(5000) * 100.0,
            "heavy_tail": rng.lognormal(3.0, 1.5, 5000),
            "bimodal": np.concatenate(
                [rng.normal(10.0, 1.0, 2500),
                 rng.normal(1000.0, 50.0, 2500)]).clip(0.01),
            "constant": np.full(1000, 7.5),
            "tiny": np.array([3.0, 1.0, 2.0]),
            "with_zeros": np.concatenate(
                [np.zeros(500), rng.random(1500) * 10.0]),
        }

    def test_relative_error_bound_every_q(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        for name, vals in self._distributions().items():
            sk = QuantileSketch()
            for v in vals:
                sk.add(float(v))
            for q in self.QS:
                oracle = self._oracle(vals, q)
                est = sk.quantile(q)
                if oracle <= 1e-9:
                    assert abs(est - oracle) <= 1e-9, (name, q)
                else:
                    err = abs(est - oracle) / oracle
                    assert err <= sk.alpha + 1e-12, \
                        (name, q, est, oracle, err)

    def test_extremes_exact(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        sk = QuantileSketch()
        for v in (4.0, 1.0, 3.0, 2.0):
            sk.add(v)
        assert sk.quantile(0.0) == 1.0      # exact tracked min
        assert sk.quantile(1.0) == 4.0      # exact tracked max

    def test_merge_matches_single_sketch_and_associates(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        import copy
        rng = np.random.default_rng(3)
        vals = rng.lognormal(2.0, 1.0, 3000)
        whole = QuantileSketch()
        parts = [QuantileSketch() for _ in range(3)]
        for i, v in enumerate(vals):
            whole.add(float(v))
            parts[i % 3].add(float(v))
        a, b, c = parts
        ab_c = copy.deepcopy(a).merge(b).merge(c)
        a_bc = copy.deepcopy(a).merge(copy.deepcopy(b).merge(c))
        for q in self.QS:
            # associativity is EXACT (bucket counts add); merged ==
            # single-sketch is exact too — same buckets either way
            assert ab_c.quantile(q) == a_bc.quantile(q)
            assert ab_c.quantile(q) == whole.quantile(q)
        assert ab_c.count == whole.count == 3000
        assert ab_c.sum == pytest.approx(whole.sum)

    def test_merge_rejects_mismatched_alpha(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        with pytest.raises(ValueError, match="alpha"):
            QuantileSketch(0.01).merge(QuantileSketch(0.02))

    def test_bucket_collapse_bounds_memory_keeps_high_q(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        sk = QuantileSketch(max_buckets=32)
        rng = np.random.default_rng(0)
        # dynamic range far beyond 32 buckets forces collapses
        vals = np.exp(rng.uniform(-5, 15, 4000))
        for v in vals:
            sk.add(float(v))
        assert len(sk._buckets) <= 32
        # the collapse folds LOW buckets upward: quantiles whose rank
        # lies in the SURVIVING (high) buckets keep the bound — 32
        # kept buckets over this ~1000-bucket-wide distribution cover
        # roughly the top 3% of mass, so the SLO-bearing tail is what
        # survives (the DDSketch collapse direction, by design)
        for q in (0.99, 0.999):
            oracle = self._oracle(vals, q)
            assert abs(sk.quantile(q) - oracle) / oracle \
                <= sk.alpha + 1e-12
        assert sk.quantile(1.0) == float(vals.max())

    def test_serialisation_round_trip(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        sk = QuantileSketch()
        for v in (1.0, 5.0, 0.0, 250.0):
            sk.add(v)
        back = QuantileSketch.from_dict(
            json.loads(json.dumps(sk.to_dict())))
        for q in self.QS:
            assert back.quantile(q) == sk.quantile(q)
        assert back.count == sk.count and back.zeros == sk.zeros

    def test_constructor_validation(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        with pytest.raises(ValueError):
            QuantileSketch(alpha=0.0)
        with pytest.raises(ValueError):
            QuantileSketch(alpha=1.0)
        with pytest.raises(ValueError):
            QuantileSketch(max_buckets=1)

    def test_negative_values_clamp_to_zero_bucket(self):
        from matrel_tpu.obs.metrics import QuantileSketch
        sk = QuantileSketch()
        for v in (-3.0, 0.0, 2.0, 4.0):
            sk.add(v)
        assert sk.zeros == 2
        # nearest-rank oracle at q=.99 over 4 values is rank 2 -> 2.0
        assert sk.quantile(0.99) == pytest.approx(2.0, rel=sk.alpha)
        assert sk.quantile(1.0) == 4.0


class TestHistorySketchAgreement:
    """Satellite fix regression: obs/history's percentile helper used
    to nearest-rank raw lists per invocation while the live plane
    reported sketch estimates — now BOTH flow through
    obs.metrics.percentile, pinned to agree with the nearest-rank
    oracle within the sketch bound on every tested distribution/q."""

    def test_pctile_agreement_within_bound(self):
        from matrel_tpu.obs.history import _pctile
        from matrel_tpu.obs.metrics import (DEFAULT_ALPHA,
                                            QuantileSketch,
                                            percentile)
        rng = np.random.default_rng(11)
        for vals in (rng.random(777) * 50.0,
                     rng.lognormal(1.0, 2.0, 777),
                     np.full(40, 3.25)):
            vals = [float(v) for v in vals]
            for q in (0.5, 0.9, 0.95, 0.99):
                oracle = float(np.percentile(vals, q * 100.0,
                                             method="lower"))
                hist = _pctile(sorted(vals), q)
                assert abs(hist - oracle) <= DEFAULT_ALPHA * oracle
                # history's helper IS the shared definition — exactly
                # what a live sketch over the same values reports
                sk = QuantileSketch()
                for v in vals:
                    sk.add(v)
                assert hist == sk.quantile(q)
                assert hist == percentile(vals, q)

    def test_pctile_empty_is_none(self):
        from matrel_tpu.obs.history import _pctile
        assert _pctile([], 0.5) is None


class TestColdTier:
    """PR 52: the sites that run only where a plan is made leave a
    record in ``cold_spans()`` with everything else off (no tracer, no
    flight recorder, no profiler session), jax's own account of each
    compile lands beside them by function name, and a warm query
    leaves nothing anywhere."""

    @staticmethod
    def _mark():
        """A span id no record made from now on falls under: the ring
        is the process's and may be full, so its length says nothing."""
        from matrel_tpu.obs import trace as trace_lib
        return next(trace_lib._SPAN_SEQ)

    @staticmethod
    def _since(mark):
        from matrel_tpu.obs import trace as trace_lib
        return [r for r in trace_lib.cold_spans() if r["span_id"] > mark]

    @staticmethod
    def _dark(mesh8):
        sess = MatrelSession(mesh=mesh8)        # the default config
        assert sess.config.obs_level == "off"
        assert sess._tracer is None and sess._flight is None
        return sess

    def test_a_cold_compile_is_recorded_with_everything_off(
            self, mesh8, chain3):
        sess = self._dark(mesh8)
        mark = self._mark()
        sess.compute(chain3)
        recs = self._since(mark)
        by_name = {r["name"]: r for r in recs}
        compile_ = by_name["compile"]
        assert compile_["parent_id"] is None
        assert compile_["attrs"]["executors"] \
            == sess.compile(chain3).meta["executors"]
        for name in ("plan.optimize", "plan.verify", "plan.trace"):
            r = by_name[name]
            assert r["parent_id"] == compile_["span_id"]
            assert r["qid"] == compile_["qid"]
            assert compile_["start_ns"] <= r["start_ns"] \
                <= r["end_ns"] <= compile_["end_ns"]
        # span() beneath an open cold span records too
        assert by_name["plan.strategy"]["parent_id"] == compile_["span_id"]
        meta = sess.compile(chain3).meta
        for key, name in (("optimize_ms", "plan.optimize"),
                          ("trace_ms", "plan.trace")):
            r = by_name[name]
            length_ms = (r["end_ns"] - r["start_ns"]) * 1e-6
            # one span, two clocks (perf_counter for dur_ms)
            assert meta[key] == pytest.approx(length_ms, abs=0.5, rel=0.02)
        # a record's name is bare, and nothing reached the profile ring
        assert not any(r["name"].startswith("matrel.") for r in recs)

    def test_b_a_warm_query_leaves_nothing(self, mesh8, chain3):
        from matrel_tpu.obs import trace as trace_lib
        sess = self._dark(mesh8)
        want = sess.compute(chain3).to_numpy()      # the cold compile
        assert trace_lib.entry("compute", None) is trace_lib._NOOP
        assert trace_lib.span("dispatch") is trace_lib._NOOP
        mark = self._mark()
        profiled = len(trace_lib.profile_spans())
        for _ in range(100):
            # blocked a round: programs with a collective each, queued
            # unblocked on 8 virtual devices, starve XLA's thread pool
            out = sess.compute(chain3).to_numpy()
        np.testing.assert_array_equal(out, want)
        assert self._since(mark) == []
        assert len(trace_lib.profile_spans()) == profiled

    def test_c_a_fresh_jit_is_heard_by_function_name(self, mesh8):
        import jax
        import jax.numpy as jnp
        self._dark(mesh8)       # a session's start listens

        def cold_tier_probe_c(x):
            return x * 2.0 + 1.0

        fn = jax.jit(cold_tier_probe_c)
        mark = self._mark()
        assert float(fn(jnp.ones(3))[0]) == 3.0
        recs = [r for r in self._since(mark)
                if "cold_tier_probe_c" in str(r["attrs"].get("fun_name"))]
        assert sorted(r["name"] for r in recs
                      if r["name"] != "jit.cache") \
            == ["jit.backend", "jit.lower", "jit.trace"]
        for r in recs:
            assert r["parent_id"] is None or r["name"] == "jit.cache"
            assert 0 <= r["end_ns"] - r["start_ns"] < 60e9
        mark = self._mark()
        fn(jnp.ones(3))
        assert self._since(mark) == []

    def test_c_a_jit_event_names_the_open_cold_span(self, mesh8):
        import jax
        import jax.numpy as jnp
        from matrel_tpu.obs import trace as trace_lib
        self._dark(mesh8)
        mark = self._mark()
        with trace_lib.phase("coo.slab.fill", entries=3) as sp:
            jax.jit(lambda x: x - 7.0)(jnp.ones(3))
        recs = self._since(mark)
        jits = [r for r in recs if r["name"].startswith("jit.")
                and r["name"] != "jit.cache"]
        assert {r["name"] for r in jits} \
            == {"jit.trace", "jit.lower", "jit.backend"}
        assert {r["parent_id"] for r in jits} == {sp.span_id}
        assert recs[-1]["name"] == "coo.slab.fill"
        assert recs[-1]["attrs"] == {"entries": 3}

    def test_d_query_execute_never_reaches_the_cold_ring(
            self, mesh8, tmp_path, chain3):
        sess = _session(mesh8, tmp_path, level="on")
        mark = self._mark()
        sess.run(chain3)
        sess.run(chain3)
        logged = [e["name"] for e in read_events(sess.config.obs_event_log)
                  if e["kind"] == "span"]
        assert logged.count("query.execute") == 2
        cold = [r["name"] for r in self._since(mark)]
        assert "compile" in cold and "query.execute" not in cold
        assert "compute" not in cold and "dispatch" not in cold
        # and the phases still go to the tracer as they did
        assert {"compile", "plan.optimize", "plan.trace"} <= set(logged)

    def test_e_the_ring_is_bounded(self):
        from matrel_tpu.obs import trace as trace_lib
        assert trace_lib._COLD_RING.capacity \
            == trace_lib.COLD_RING_CAPACITY == 16384
        for k in range(trace_lib.COLD_RING_CAPACITY + 10):
            with trace_lib.phase("plan.verify", k=k):
                pass
        recs = trace_lib.cold_spans()
        assert len(recs) == trace_lib.COLD_RING_CAPACITY
        # the oldest fell out
        assert recs[-1]["attrs"] == {"k": trace_lib.COLD_RING_CAPACITY + 9}
        assert recs[0]["attrs"] == {"k": 10}

    def test_e_a_listener_never_fails_a_compile(self, mesh8, monkeypatch):
        import jax
        import jax.numpy as jnp
        from matrel_tpu.obs import trace as trace_lib
        self._dark(mesh8)
        mark = self._mark()
        # a later jax's renamed events: ignored by all three listeners
        jax.monitoring.record_event("/jax/compilation_cache/renamed")
        jax.monitoring.record_event_duration_secs("/jax/core/renamed", 1.5)
        jax.monitoring.record_event_time_span("/jax/core/renamed", 1.0, 2.0)
        assert self._since(mark) == []

        def boom(record):
            raise RuntimeError("a broken ring")

        monkeypatch.setattr(trace_lib, "_cold_append", boom)
        assert float(jax.jit(lambda x: x * 5.0)(jnp.ones(2))[0]) == 5.0
        trace_lib._on_jax_event(None)       # nor on nonsense
        trace_lib._on_jax_secs(None, None)
        trace_lib._on_jax_span(None, "a", "b", fun_name=3)
        monkeypatch.undo()
        assert self._since(mark) == []

    def test_f_two_sessions_hear_each_event_once(self, mesh8):
        import jax
        import jax.numpy as jnp
        from jax._src import monitoring
        from matrel_tpu.obs import trace as trace_lib
        self._dark(mesh8)
        self._dark(mesh8)
        trace_lib.hear_jax()
        for listeners, mine in (
                (monitoring.get_event_time_span_listeners(),
                 trace_lib._on_jax_span),
                (monitoring.get_event_listeners(), trace_lib._on_jax_event),
                (monitoring.get_event_duration_listeners(),
                 trace_lib._on_jax_secs)):
            assert listeners.count(mine) == 1

        def cold_tier_probe_f(x):
            return x + 11.0

        mark = self._mark()
        jax.jit(cold_tier_probe_f)(jnp.ones(2))
        assert sorted(
            r["name"] for r in self._since(mark)
            if "cold_tier_probe_f" in str(r["attrs"].get("fun_name"))
            and r["name"] != "jit.cache") \
            == ["jit.backend", "jit.lower", "jit.trace"]

    def test_f_the_cache_verdict_rides_its_backend_compile(self, mesh8):
        """jax tells of a hit (an event, then two durations) inside the
        backend-compile span and of that span at its end: the
        ``jit.cache`` record is the child of the ``jit.backend`` one."""
        import jax
        self._dark(mesh8)
        mark = self._mark()
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 4.0)
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 100.0, 100.5,
            fun_name="jit(loaded)")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 101.0, 109.0,
            fun_name="jit(compiled)")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 110.0, 110.1,
            fun_name="jit(neither)")
        loaded, hit, compiled, miss, neither = self._since(mark)
        assert (loaded["name"], loaded["start_ns"], loaded["end_ns"]) \
            == ("jit.backend", 100_000_000_000, 100_500_000_000)
        assert hit["name"] == "jit.cache"
        assert hit["parent_id"] == loaded["span_id"]
        assert hit["start_ns"] == hit["end_ns"]
        assert hit["attrs"] == {"hit": True, "saved_s": 4.0,
                                "retrieval_s": 0.25,
                                "fun_name": "jit(loaded)"}
        assert miss["parent_id"] == compiled["span_id"]
        assert miss["attrs"] == {"hit": False, "fun_name": "jit(compiled)"}
        assert neither["attrs"] == {"fun_name": "jit(neither)"}

    def test_g_a_coo_products_first_compile(self, rng, tmp_path,
                                            monkeypatch):
        """On one device with the compact executors (interpreted) a COO
        product's first compile leaves its host build and its upload
        in the cold ring: under a profiler session the very records
        the profile tier gets, and with everything off the same names
        and attribute keys."""
        import jax
        from matrel_tpu import config as config_lib
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.obs import trace as trace_lib
        from matrel_tpu.ops import spmv as spmv_lib
        cfg = MatrelConfig(pallas_interpret=True)
        monkeypatch.setattr(config_lib, "_default_config", cfg)
        monkeypatch.setattr(coo_lib, "_plan_layout", lambda: "auto")
        monkeypatch.setattr(coo_lib, "_DENSE_SHARE", 0.0)
        monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
        mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        sess = MatrelSession(mesh=mesh, config=cfg)
        dense = BlockMatrix.from_numpy(
            rng.standard_normal((300, 8)).astype(np.float32), mesh=mesh)

        def product():
            m = COOMatrix.from_edges(
                rng.integers(0, 400, 3000), rng.integers(0, 300, 3000),
                rng.random(3000, dtype=np.float32), shape=(400, 300))
            return m, m.expr() @ dense.expr()

        names = ("spmm.plan.build", "spmm.plan.upload", "spmm.plan")
        m, e = product()
        mark = self._mark()
        profiled = len(trace_lib.profile_spans())
        with TestProfilerTier._trace(tmp_path):
            got = sess.compute(e).to_numpy()
        np.testing.assert_allclose(
            got, m.to_dense() @ dense.to_numpy(), rtol=1e-4, atol=1e-4)
        prof = {r["span_id"]: r
                for r in trace_lib.profile_spans()[profiled:]}
        lit = [r for r in self._since(mark) if r["name"] in names]
        assert {r["name"] for r in lit} == set(names)
        for r in lit:
            p = prof[r["span_id"]]
            assert p["name"] == "matrel." + r["name"]
            assert {k: p[k] for k in r if k != "name"} \
                == {k: r[k] for k in r if k != "name"}
        mark = self._mark()
        _, e = product()
        profiled = len(trace_lib.profile_spans())
        sess.compute(e)
        assert len(trace_lib.profile_spans()) == profiled
        dark = [r for r in self._since(mark) if r["name"] in names]
        by_name = {r["name"]: r for r in dark}
        assert set(by_name) == set(names)
        for r in lit:
            assert set(by_name[r["name"]]["attrs"]) == set(r["attrs"])
        build = by_name["spmm.plan.build"]["attrs"]
        assert build["orientation"] == "forward" and build["fill_s"] >= 0
        assert by_name["spmm.plan"]["attrs"]["hit"] is False
        # the matrix's own cold site, with the sizes it handled
        made = next(r for r in self._since(mark)
                    if r["name"] == "coo.from_edges")
        assert made["attrs"] == {"entries": 3000, "bytes": 3000 * 20}
