"""Fire-drill for the staged capture batch (VERDICT r5 Next #2).

`tools/tpu_batch.sh --dry` must run the WHOLE staged capture sequence
end-to-end on the CPU backend with rc 0, each step emitting its
expected parseable artifact, and every write redirected away from the
repo's committed capture history. The round-6 introduction of this
drill immediately caught two staged tools that would have crashed on
their first chip run (gram_sym_full / autotune_capture missing their
sys.path setup) — precisely the failure chip time must not be spent
debugging.

One subprocess run shared by every assertion: the batch takes ~30 s on
the CI host and the point is the INTEGRATED sequence.
"""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dry_batch(tmp_path_factory):
    art = tmp_path_factory.mktemp("batch_dry")
    env = dict(os.environ)
    env["MATREL_BATCH_DRY_DIR"] = str(art)
    proc = subprocess.run(
        ["sh", os.path.join(REPO, "tools", "tpu_batch.sh"), "--dry"],
        capture_output=True, text=True, timeout=560, env=env)
    records = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                pytest.fail(f"unparseable artifact line: {line[:200]}")
    return proc, records, art


def test_batch_exits_zero(dry_batch):
    proc, _, _ = dry_batch
    assert proc.returncode == 0, (proc.stdout[-1500:]
                                  + proc.stderr[-1500:])


def _one(records, pred, what):
    got = [r for r in records if pred(r)]
    assert got, f"no {what} artifact in batch stdout"
    return got[0]


def test_headline_bench_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric")
               == "dense_blockmatmul_tflops_per_chip"
               and "vs_baseline" in r, "bench.py headline")
    assert rec["value"] is not None and rec["value"] > 0
    # Weak #5 closure rides along: the interval is recorded, and on a
    # sub-5-ms row the escalation loop must have brought the band
    # half-width inside the target (or exhausted its doublings)
    iv = rec["interval"]
    assert set(iv) >= {"median_ms", "half_width_ms", "half_width_frac",
                       "reps", "escalations", "band_target"}
    if iv["median_ms"] < 5.0 and iv["escalations"] < 4:
        assert iv["half_width_frac"] <= iv["band_target"]


def test_soak_guard_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records, lambda r: r.get("event") == "soak_tpu",
               "soak_guard")
    assert rec["ok"] is True, rec
    assert rec["stage"] == "soak"


def test_spgemm_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "blocksparse_spgemm_100k_1pct"
               and "cmp_speedup" in r, "bench.py --spgemm")
    assert rec["spgemm_full_ms"] > 0
    assert rec["cmp_densify_ms"] > 0


def test_sparse_kernels_row_artifact(dry_batch):
    _, records, _ = dry_batch
    # twice in the dry batch, like its sibling rows: the probed
    # bench.py --sparse-kernels step AND bench_all's dry-enabled row
    recs = [r for r in records
            if r.get("metric") == "sparse_kernel_sweep"
            and "rows" in r]
    assert len(recs) == 2, f"expected 2 sweep artifacts, got {recs}"
    rec = recs[0]
    # the round-11 acceptance on the dry mesh: every structure class
    # classified as generated, every relevant registered kernel
    # measured with its interval, at least one specialized variant
    # >= 1.3x over the fixed pre-registry Pallas kernel on its home
    # class, and the autotuned winner persisted + replayed from the
    # (redirected) table
    assert rec["ok"] is True, rec
    assert rec["baseline_kernel"] == "pallas_generic"
    structures = [r["structure"] for r in rec["rows"]]
    assert structures == ["row_band", "clustered_tile",
                          "powerlaw_coo"], structures
    for row in rec["rows"]:
        assert row["classified"] == row["structure"], row
        assert row["pairs"] > 0
        assert {"xla_gather", "pallas_generic"} <= set(row["kernels"])
        assert row["specialized"] in row["kernels"], row
        for t in row["kernels"].values():
            assert t["ms"] > 0 and "half_width_ms" in t
    assert rec["best_speedup"] >= 1.3, rec["best_speedup"]
    at = rec["autotune"]
    assert at["persisted"] is True and at["replayed"] is True
    assert at["key"].startswith("spgemm|")


def test_fusion_row_artifact(dry_batch):
    _, records, _ = dry_batch
    # twice in the dry batch, like its sibling rows: the probed
    # bench.py --fusion step AND bench_all's dry-enabled row
    recs = [r for r in records
            if r.get("metric") == "fusion_region_sweep"
            and "rows" in r]
    assert len(recs) == 2, f"expected 2 fusion artifacts, got {recs}"
    rec = recs[0]
    # the round-12 acceptance on the dry mesh: both chains measured
    # both ways with intervals, fused >= 1.3x over staged with the
    # dispatch count reduced and recorded, outputs identical, the
    # default (fusion off) path constructing zero region objects, and
    # MV111 quiet on a fresh fused annotation
    assert rec["ok"] is True, rec
    chains = [r["chain"] for r in rec["rows"]]
    assert chains == ["pagerank_step", "linreg_epilogue"], chains
    for row in rec["rows"]:
        assert row["staged_ms"] > 0 and row["fused_ms"] > 0
        assert "staged_half_width_ms" in row \
            and "fused_half_width_ms" in row
        assert row["fused_dispatches"] < row["staged_dispatches"], row
        assert row["regions"] >= 1
        assert row["speedup"] >= 1.3, row
        assert row["outputs_agree"] is True
    assert rec["off_constructs_nothing"] is True
    assert rec["mv111_quiet"] is True, rec["mv111"]


def test_traffic_row_artifact(dry_batch):
    _, records, _ = dry_batch
    # twice in the dry batch, like its sibling rows: the probed
    # tools/traffic.py step AND bench_all's dry-enabled row
    recs = [r for r in records
            if r.get("metric") == "traffic_overload_harness"
            and "tenants" in r]
    assert len(recs) == 2, f"expected 2 traffic artifacts, got {recs}"
    rec = recs[0]
    # the round-13 acceptance at ~2x sustained overload over 3
    # weighted tenants (docs/OVERLOAD.md): goodput holds >= 80% of
    # measured closed-loop capacity, every refusal typed, zero wrong
    # answers, admitted-and-met p99 inside the declared deadline,
    # weighted fairness strict (gold misses less than bronze), and
    # brownout provably enters AND exits
    assert rec["ok"] is True, rec
    assert rec["wrong_answers"] == 0
    assert rec["untyped_errors"] == 0
    assert rec["goodput_ratio"] >= 0.8, rec["goodput_ratio"]
    assert rec["p99_within_deadline"] is True
    assert 0.0 < rec["fairness_jain"] <= 1.0
    tenants = rec["tenants"]
    assert set(tenants) == {"gold", "silver", "bronze"}
    for t, row in tenants.items():
        assert row["arrivals"] > 0
        # per-tenant percentile columns present (p50/p95/p99)
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(row)
        # typed-shed counts present
        assert row["sheds"] >= 0 and row["deadline_misses"] >= 0
    assert tenants["gold"]["miss_rate"] < tenants["bronze"]["miss_rate"]
    assert rec["brownout"]["entered"] is True
    assert rec["brownout"]["exited"] is True
    # overload plus sheds means the typed counts actually fired
    assert sum(t["sheds"] for t in tenants.values()) > 0


def test_traffic_slo_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "traffic_slo_harness"
               and "prometheus" in r, "tools/traffic.py --slo")
    # the round-15 acceptance (docs/OBSERVABILITY.md tier 3): at ~2x
    # sustained overload under declared per-tenant objectives, the
    # violated (lowest-weight) tenant's fast-window burn-rate alert
    # FIRES during saturation and every alert CLEARS after the load
    # drops, with the live Prometheus endpoint strict-parsing clean on
    # every poll throughout and still zero wrong answers
    assert rec["ok"] is True, rec
    assert rec["violated_tenant_fired_in_window"] is True
    assert rec["alerts_fired"] >= 1
    assert rec["uncleared"] == []
    assert rec["alerts_active_final"] == 0
    assert rec["prometheus"]["ok"] is True
    assert rec["prometheus"]["polls"] > 0
    assert rec["prometheus"]["parse_failures"] == 0
    assert rec["wrong_answers"] == 0
    assert rec["untyped_errors"] == 0
    assert "bronze:avail" in rec["fired_objectives"]


def test_serve_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "serve_repeated_traffic_qps"
               and "speedup" in r, "bench.py --serve")
    # the acceptance number: result cache + micro-batched admission
    # must run the repeated-traffic stream at >= 2x the QPS of today's
    # sequential uncached session.run loop, on the CPU backend
    assert rec["speedup"] is not None and rec["speedup"] >= 2.0, rec
    assert rec["seq_uncached_qps"] > 0
    assert rec["batched_cached_qps"] > rec["seq_uncached_qps"]
    for name in ("seq_uncached", "seq_cached", "batched_uncached",
                 "batched_cached"):
        cfg = rec["configs"][name]
        assert cfg["qps"] > 0
        assert set(cfg) >= {"median_ms", "half_width_ms",
                            "half_width_frac", "replays"}


def test_cse_row_artifact(dry_batch):
    _, records, _ = dry_batch
    # twice in the dry batch, like its sibling rows: the probed
    # bench.py --cse step AND bench_all's dry-enabled row
    recs = [r for r in records
            if r.get("metric") == "cse_shared_interior_batch"
            and "speedup" in r]
    assert len(recs) == 2, f"expected 2 cse artifacts, got {recs}"
    rec = recs[0]
    # the round-17 acceptance (docs/SERVING.md): >= 1.5x first-contact
    # wall at k variants over one shared interior, CSE on vs off, with
    # bit-identical answers and exactly one hoisted interior per batch
    assert rec["speedup"] is not None and rec["speedup"] >= 1.5, rec
    assert rec["exact"] is True
    assert rec["hoisted_per_batch"] == 1
    for name in ("cse_off", "cse_on"):
        cfg = rec["configs"][name]
        assert cfg["median_ms"] > 0
        assert set(cfg) >= {"median_ms", "half_width_ms", "trials"}
    # the steady-state coda: a structurally-identical batch over a
    # REBOUND leaf answers through the plan-template path (hoist +
    # consumer probes both hit) with correct answers
    st = rec["steady"]
    assert st["template_hits_delta"] >= 1, st
    assert st["exact"] is True
    assert st["rebind_ms"] < rec["cse_on_ms"]


def test_fleet_row_artifact(dry_batch):
    _, records, _ = dry_batch
    # twice in the dry batch, like its sibling rows: the probed
    # bench.py --fleet step AND bench_all's dry-enabled row
    recs = [r for r in records
            if r.get("metric") == "fleet_scaleout_qps"
            and "speedup" in r]
    assert len(recs) == 2, f"expected 2 fleet artifacts, got {recs}"
    rec = recs[0]
    # the round-16 acceptance (docs/FLEET.md): >= 1.5x aggregate QPS
    # going 1 -> 2 virtual slices on the repeated-traffic stream
    # whose working set only fits the fleet's AGGREGATE cache, with a
    # directory hit on a NON-owning slice answering without recompute
    assert rec["speedup"] is not None and rec["speedup"] >= 1.5, rec
    assert rec["slices1_qps"] > 0
    assert rec["slices2_qps"] > rec["slices1_qps"]
    assert rec["remote_hit_no_recompute"] is True
    s2 = rec["configs"]["slices2"]
    assert s2["directory"]["remote_hits"] >= 1
    assert s2["recompute_free_replays"] is True
    for name in ("slices1", "slices2"):
        cfg = rec["configs"][name]
        assert cfg["qps"] > 0
        assert set(cfg) >= {"median_ms", "half_width_ms", "replays",
                            "directory", "placed"}
    # the mid-stream slice-kill drill: the stream completes with
    # ZERO wrong answers and only typed failures
    kill = rec["kill"]
    assert kill["wrong"] == 0
    assert kill["untyped_failures"] == 0
    assert kill["completed"] + kill["typed_failures"] \
        == kill["submitted"]
    assert kill["completed"] > 0
    assert kill["failovers"] == 1


def test_traffic_slices_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "traffic_fleet_harness"
               and "directory" in r, "tools/traffic.py --slices")
    # the open-loop fleet drill (docs/FLEET.md): placement spreads
    # the stream over both slices, the directory answers repeats,
    # span-pinned pool entries exercise the full-mesh path, and the
    # mid-stream kill completes the stream with zero wrong answers
    # and only typed failures
    assert rec["ok"] is True, rec
    assert rec["wrong_answers"] == 0
    assert rec["untyped_errors"] == 0
    assert rec["failovers"] == 1
    assert rec["completed"] > 0
    assert len(rec["slices_served_before_kill"]) >= 2
    assert rec["directory"]["hits"] >= 1
    assert rec["placed"]["slice"] > 0 and rec["placed"]["span"] > 0


def test_stream_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "stream_update_latency"
               and "speedup" in r, "bench.py --stream")
    # the round-14 acceptance number (docs/IVM.md): delta-patch
    # steady-state update latency >= 3x faster than full recompute on
    # the small-delta stream, CPU backend, with MV113 proving every
    # surviving patched entry and zero wrong answers (the measurement
    # child bit-exact-asserts the integer queries itself — rec["ok"]
    # carries that verdict)
    assert rec["speedup"] is not None and rec["speedup"] >= 3.0, rec
    assert rec["ok"] is True, rec
    assert rec["patch"]["mv113"] == [], rec["patch"]["mv113"]
    assert rec["patch"]["patched_per_update"] > 0
    assert rec["patch"]["reused_plans"] > 0
    assert rec["patch"]["median_ms"] > 0
    assert rec["recompute"]["median_ms"] > rec["patch"]["median_ms"]
    for side in ("patch", "recompute"):
        assert set(rec[side]) >= {"median_ms", "half_width_ms",
                                  "updates"}


def test_precision_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "precision_tier_sweep"
               and "rows" in r, "bench.py --precision")
    # all four tier rows, each with its TFLOPS and max-abs-error
    # columns, every measured error inside its documented bound, and
    # the SLA chooser routing each named level to the tier the cost
    # model's pass/byte billing says it should
    tiers = [row["tier"] for row in rec["rows"]]
    assert tiers == ["f32", "bf16x1", "bf16x3", "int32"], tiers
    for row in rec["rows"]:
        assert row["stamped_tier"] == row["tier"], row
        assert row["tflops_per_chip"] > 0
        assert "max_abs_err" in row and "err_bound" in row
        assert row["within_bound"] is True, row
    int_row = rec["rows"][-1]
    assert int_row["max_abs_err"] == 0.0          # int path is EXACT
    assert rec["chooser_ok"] is True, rec["sla_choices"]
    assert rec["all_within_bound"] is True


def test_reshard_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "reshard_sweep"
               and "rows" in r, "bench.py --reshard")
    # the reshard-planner acceptance on the dry mesh: every move
    # measured both ways with its modelled bytes/peaks, and the staged
    # CROSS plans peak-bounded below the one-shot full-gather model
    assert rec["ok"] is True, rec
    pairs = [row["pair"] for row in rec["rows"]]
    assert pairs == ["row->col", "col->row", "row->2d", "2d->rep"], pairs
    for row in rec["rows"]:
        assert row["staged_ms"] > 0 and row["naive_ms"] > 0, row
        assert row["staged_bytes"] >= 0 and row["peak_bytes"] > 0
        if row["cross"]:
            assert row["steps"] == ["all_to_all", "all_to_all"], row
            assert row["peak_bytes"] < row["naive_peak_bytes"], row


def test_coeffs_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "coeff_planner_sweep"
               and "rows" in r, "bench.py --coeffs")
    # the cost-model-loop acceptance on the dry mesh: every workload
    # class fully covered by calibrated rows (all decisions stamped
    # measured), answers bit-close to the analytic path, and the
    # calibrated ranking never slower beyond the documented guard band
    # (identical picks = identical plans, exempt from the jitter gate)
    assert rec["ok"] is True, rec
    names = [row["workload"] for row in rec["rows"]]
    assert names == ["chain", "pagerank_step", "linreg_epilogue"], names
    assert len(rec["classes"]) == 3, rec["classes"]  # distinct buckets
    for row in rec["rows"]:
        assert row["ok"] is True, row
        assert row["covered"] is True, row
        assert row["outputs_agree"] is True, row
        assert all(c == "measured" for c in row["cost_sources"]), row
        assert row["speedup"] is not None, row


def test_spill_row_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "spill_sweep"
               and "restart" in r, "bench.py --spill")
    # the durability acceptance on the dry mesh: working set larger
    # than the HBM budget sustained by lower-tier promotions with
    # zero wrong answers, and the thawed restart's first hit served
    # from the snapshot (not recomputed)
    assert rec["working_set_over_budget"] is True, rec
    assert rec["wrong"] == 0, rec
    assert rec["sustained"]["promoted"] > 0, rec["sustained"]
    rs = rec["restart"]
    assert rs["restored_entries"] > 0, rs
    assert rs["thawed_served_from_snapshot"] is True, rs
    assert rs["cold_first_hit_ms"] > 0, rs
    assert rs["thawed_first_hit_ms"] > 0, rs
    # per-leg transfer rows (the drift calibration feed): every leg
    # in the reshard vocabulary with positive measured bytes/ms
    assert rec["rows"], rec
    for row in rec["rows"]:
        assert row["leg"] in ("d2h", "h2d", "disk_write",
                              "disk_read"), row
        assert row["bytes"] > 0 and row["ms"] > 0, row


def test_bench_all_rows_artifacts(dry_batch):
    _, records, _ = dry_batch
    # every heavy row emits an explicit, parseable skip record — a
    # silently-missing row would hide a crashed step
    for name in ("bench_linreg", "bench_spmm", "bench_pagerank",
                 "bench_pagerank_10x", "bench_cg", "bench_eigen",
                 "bench_triangles", "bench_north_star"):
        rec = _one(records, lambda r, n=name: r.get("metric") == n,
                   f"bench_all {name}")
        assert rec.get("skipped") == "dry", rec
    chain = _one(records,
                 lambda r: r.get("metric")
                 == "chain_abc_10k_skewed_wallclock", "bench_all chain")
    assert chain["value"] > 0 and "plan" in chain


def test_topology_flip_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "topology_strategy_flip",
               "topology_flip")
    # the weighted-mesh planner provably flips off the slow axis
    # (VERDICT Next #4 "done when"), MV106 flags the hand-stamped
    # slow-axis plan, and the planner's own weighted output is clean
    assert rec["ok"] is True, rec
    assert rec["unweighted"] != rec["weighted"]
    assert rec["mv106_flagged"] is True
    assert rec["clean_plan_quiet"] is True
    assert rec["slow_axis_bytes"] > rec["fast_axis_bytes"]


def test_flight_drill_artifact(dry_batch):
    _, records, art = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "flight_recorder_drill",
               "flight_drill")
    # the obs tier-2 acceptance, end to end on the dry log: the serve
    # batch ran, the compile failure left a parseable flight artifact,
    # the chrome export has parent-linked admission/compile/execute
    # spans, and the drift audit produced calibration rows
    assert rec["ok"] is True, rec
    assert rec["batch_ok"] is True
    assert rec["compile_failure_dumped"] is True
    assert rec["chrome_events"] > 0 and rec["parent_linked"] > 0
    assert {"serve.admit", "serve.batch", "plan.optimize",
            "serve.execute"} <= set(rec["span_names"])
    assert rec["drift_rows"] >= 1
    # the flight-recorder artifact itself parses and carries records
    flight = json.loads((art / "flight.json").read_text())
    assert flight["kind"] == "flight_recorder"
    assert flight["reason"] == "compile_failure"
    assert flight["records"]
    # the drift calibration table parses too
    table = json.loads((art / "drift.json").read_text())
    assert table["schema"] == 1 and table["entries"]


def test_chaos_drill_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records, lambda r: r.get("metric") == "chaos_drill",
               "chaos_drill")
    # the resilience acceptance: >= 50 queries under a seeded fault
    # schedule with every instrumented site firing, 0 wrong answers,
    # 0 unclassified failures, only the deterministic-fault queries
    # failing (typed), the poison batch isolating exactly one future,
    # and zero hangs (the drill itself drains under a timeout)
    assert rec["ok"] is True, rec
    assert rec["queries"] >= 50
    assert rec["wrong_answers"] == 0
    assert rec["untyped_failures"] == 0
    assert rec["poison_isolated"] is True
    assert rec["deadline_typed"] is True
    assert rec["checkpoint_ok"] is True
    assert set(rec["sites_fired"]) == {
        "compile", "lower", "strategy", "execute", "rc_probe",
        "serve_admit", "checkpoint"}
    assert rec["retries"] > 0 and rec["degrades"] > 0


def test_provenance_drill_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records,
               lambda r: r.get("metric") == "provenance_drill",
               "provenance_drill")
    # the obs tier-4 acceptance: every provenance-bearing serve path
    # yields a lineage record (execute / whole hit / interior / IVM
    # patch / fleet directory + replica / rung-4 degrade), the MV115
    # dynamic ledger check is clean, and FULL audit replay proves
    # every served answer against fresh execution
    assert rec["ok"] is True, rec
    assert rec["missing_paths"] == []
    assert 4 in rec["degrade_rungs"]
    assert rec["mv115_findings"] == 0
    for name in ("serve", "fleet", "degrade"):
        verdict = rec["audit"][name]
        assert verdict["ok"] is True, (name, verdict)
        assert verdict["failed"] == 0
        assert verdict["sampled"] == verdict["replayable"] >= 1


def test_race_drill_artifact(dry_batch):
    _, records, _ = dry_batch
    rec = _one(records, lambda r: r.get("metric") == "race_drill",
               "race_drill")
    # the concurrency-sanitizer acceptance (docs/CONCURRENCY.md):
    # every seeded interleaving of the four hairy schedules resolves
    # right-or-typed with runtime lockdep armed, and the observed
    # lock-order graph stays acyclic
    assert rec["ok"] is True, rec
    assert rec["wrong"] == 0
    assert rec["untyped"] == 0
    assert rec["inversions"] == 0
    assert rec["acyclic"] is True
    assert rec["resolved"] >= 1
    assert set(rec["schedules"]) == {
        "submit_close_drain", "kill_replication",
        "rebind_probes", "delta_serve"}


def test_sweep_and_gram_artifacts(dry_batch):
    _, records, _ = dry_batch
    verdict = _one(records, lambda r: "results" in r and "ok" in r,
                   "north_star_sweep verdict")
    assert verdict["ok"] is True
    gram3 = _one(records, lambda r: "manual3_sym_s" in r,
                 "gram_manual3")
    assert gram3["rel_diff_vs_high"] < 1e-4   # numeric sanity intact
    full = _one(records,
                lambda r: r.get("metric") == "linreg_sym2pass_10Mx1k_s",
                "gram_sym_full")
    # theta of the synthetic y = X·1 fit must come back ~1 even dry
    assert all(abs(t - 1.0) < 0.05 for t in full["theta_head"])
    _one(records, lambda r: "side" in r and "best" in r,
         "autotune_capture")


def test_artifacts_redirected_out_of_repo(dry_batch):
    _, _, art = dry_batch
    # every side-effect landed in the dry dir, not the capture history
    for name in ("events.jsonl", "progress.jsonl", "soaklog.jsonl",
                 "cpu_baseline.json",
                 "autotune_dry.json", "spk_autotune.json",
                 "flight.json", "drift.json"):
        assert (art / name).exists(), f"{name} not redirected"
    events = [json.loads(l) for l in (art / "events.jsonl").open()]
    assert any(e.get("kind") == "bench" for e in events)
    progress = [json.loads(l) for l in (art / "progress.jsonl").open()]
    assert any(e.get("event") == "soak_tpu" for e in progress)
    assert any(e.get("event") == "north_star_sweep" for e in progress)
