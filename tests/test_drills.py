"""The operator drills under tools/, each run end to end in tier-1.

Every drill is an integration test of one plane (planner topology,
observability, resilience, provenance, concurrency, overload, SLO
alerts, the fleet) and a documented operator tool, so each runs the way
an operator runs it: its own subprocess on the CPU backend, at a small
size, under its own time limit, printing one JSON record. The asserts
are structural — what the plane did — never how fast the host did it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: A drill's whole budget; alone each takes 3-20 s.
TIMEOUT_S = 150

_TRAFFIC_ENV = {
    "MATREL_TRAFFIC_SECONDS": "5", "MATREL_TRAFFIC_TAIL_SECONDS": "2.5",
    "MATREL_TRAFFIC_CAL": "300", "MATREL_TRAFFIC_N": "48"}


def _check_topology_flip(rec, art):
    # the weighted-mesh planner flips off the slow axis, MV106 flags
    # the hand-stamped slow-axis plan, the planner's own output is clean
    assert rec["unweighted"] != rec["weighted"]
    assert rec["mv106_flagged"] is True
    assert rec["clean_plan_quiet"] is True
    assert rec["slow_axis_bytes"] > rec["fast_axis_bytes"]


def _check_flight_drill(rec, art):
    assert rec["batch_ok"] is True
    assert rec["compile_failure_dumped"] is True
    assert rec["chrome_events"] > 0 and rec["parent_linked"] > 0
    assert {"serve.admit", "serve.batch", "plan.optimize",
            "serve.execute"} <= set(rec["span_names"])
    assert rec["drift_rows"] >= 1
    flight = json.loads((art / "flight.json").read_text())
    assert flight["kind"] == "flight_recorder"
    assert flight["reason"] == "compile_failure"
    assert flight["records"]
    table = json.loads((art / "drift.json").read_text())
    assert table["schema"] == 1 and table["entries"]


def _check_chaos_drill(rec, art):
    # every query correct or failed with a typed error, none hung (the
    # drill drains under its own timeout), every fault site fired
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


def _check_provenance_drill(rec, art):
    assert rec["missing_paths"] == []
    assert 4 in rec["degrade_rungs"]
    assert rec["mv115_findings"] == 0
    for name in ("serve", "fleet", "degrade"):
        verdict = rec["audit"][name]
        assert verdict["ok"] is True, (name, verdict)
        assert verdict["failed"] == 0
        assert verdict["sampled"] == verdict["replayable"] >= 1


def _check_race_drill(rec, art):
    assert rec["wrong"] == 0
    assert rec["untyped"] == 0
    assert rec["inversions"] == 0
    assert rec["acyclic"] is True
    assert rec["resolved"] >= 1
    assert set(rec["schedules"]) == {
        "submit_close_drain", "kill_replication",
        "rebind_probes", "delta_serve"}


def _check_traffic(rec, art):
    assert rec["wrong_answers"] == 0
    assert rec["untyped_errors"] == 0
    tenants = rec["tenants"]
    assert set(tenants) == {"gold", "silver", "bronze"}
    for row in tenants.values():
        assert row["arrivals"] > 0
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(row)
    # overload was shed, and every refusal was typed
    assert sum(t["sheds"] for t in tenants.values()) > 0
    assert tenants["gold"]["miss_rate"] < tenants["bronze"]["miss_rate"]
    assert rec["brownout"]["entered"] is True
    assert rec["brownout"]["exited"] is True


def _check_traffic_slo(rec, art):
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


def _check_traffic_slices(rec, art):
    assert rec["wrong_answers"] == 0
    assert rec["untyped_errors"] == 0
    assert rec["failovers"] == 1
    assert rec["completed"] > 0
    assert len(rec["slices_served_before_kill"]) >= 2
    assert rec["directory"]["hits"] >= 1
    assert rec["placed"]["slice"] > 0 and rec["placed"]["span"] > 0


#: (metric the drill prints, argv after tools/, size environment, check)
DRILLS = [
    ("topology_strategy_flip", ["topology_flip.py"], {},
     _check_topology_flip),
    ("flight_recorder_drill", ["flight_drill.py"], {},
     _check_flight_drill),
    ("chaos_drill", ["chaos_drill.py"], {}, _check_chaos_drill),
    ("provenance_drill", ["provenance_drill.py"], {},
     _check_provenance_drill),
    ("race_drill", ["race_drill.py"],
     {"MATREL_RACE_SEEDS": "2", "MATREL_RACE_QUERIES": "6"},
     _check_race_drill),
    # the goodput floor is a reading of the host's speed: the test
    # turns it off and holds the drill to what the plane did
    ("traffic_overload_harness", ["traffic.py"],
     {**_TRAFFIC_ENV, "MATREL_TRAFFIC_GOODPUT_MIN": "0"},
     _check_traffic),
    ("traffic_slo_harness", ["traffic.py", "--slo"], _TRAFFIC_ENV,
     _check_traffic_slo),
    ("traffic_fleet_harness", ["traffic.py", "--slices"],
     {**_TRAFFIC_ENV, "MATREL_TRAFFIC_SLICES": "2"},
     _check_traffic_slices),
]


@pytest.mark.parametrize(
    "metric,argv,size_env,check", DRILLS,
    ids=[" ".join(d[1]).replace(".py", "") for d in DRILLS])
def test_drill(metric, argv, size_env, check, tmp_path):
    # every artifact a drill writes lands under tmp_path: the config's
    # own environment names redirect the named ones, the working
    # directory catches the cwd-relative defaults
    env = dict(os.environ, **size_env)
    env.update(
        JAX_PLATFORMS="cpu",
        MATREL_OBS_EVENT_LOG=str(tmp_path / "events.jsonl"),
        MATREL_OBS_FLIGHT_RECORDER_PATH=str(tmp_path / "flight.json"),
        MATREL_DRIFT_TABLE_PATH=str(tmp_path / "drift.json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", argv[0]), *argv[1:]],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env,
        cwd=tmp_path)
    tail = proc.stdout[-1500:] + proc.stderr[-1500:]
    records = [json.loads(line) for line in proc.stdout.splitlines()
               if line.startswith("{")]
    recs = [r for r in records if r.get("metric") == metric]
    assert len(recs) == 1, tail
    check(recs[0], tmp_path)
    assert recs[0]["ok"] is True, recs[0]
    assert proc.returncode == 0, tail
