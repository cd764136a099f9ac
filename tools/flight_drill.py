"""Obs tier-2 smoke drill: flight recorder + trace export + drift.

Drives a real session through the round-9 observability surfaces and
asserts each artifact end to end:

  1. a 3-query micro-batched serve admission (``run_many``) plus one
     async ``submit`` — the admission/compile/execute span trail;
  2. a COMPILE FAILURE (mixed-mesh expression) — the flight recorder's
     automatic dump must leave a parseable post-mortem artifact;
  3. ``explain(analyze=True)`` — one ``analyze`` event, the drift
     auditor's measured-vs-estimated feed;
  4. chrome export over the session's event log (span count + at least
     one parent link — the Perfetto-loadable acceptance);
  5. a drift report with the calibration table persisted.

Emits one parseable JSON line (asserted by tests/test_drills.py).
CPU-only by construction — this drills the observability plumbing, not
the chip, so it forces the CPU backend.

Artifact paths follow the config env knobs, so a caller redirects
everything: MATREL_OBS_EVENT_LOG (span/event log),
MATREL_OBS_FLIGHT_RECORDER_PATH (dump artifact),
MATREL_DRIFT_TABLE_PATH (calibration table).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main() -> int:
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.obs import drift, trace as trace_lib
    from matrel_tpu.obs.events import read_events, resolve_path
    from matrel_tpu.session import MatrelSession

    # env (MATREL_*) overrides flow over the drill's base config, so
    # a caller's redirects land every artifact where it says
    cfg = MatrelConfig.from_env(MatrelConfig(
        obs_level="on", obs_flight_recorder=256,
        result_cache_max_bytes=1 << 26))
    mesh = mesh_lib.make_mesh((2, 4))
    sess = MatrelSession(mesh=mesh, config=cfg)
    rng = np.random.default_rng(0)
    A = sess.from_numpy(rng.standard_normal((64, 96)).astype(np.float32))
    B = sess.from_numpy(rng.standard_normal((96, 32)).astype(np.float32))

    # 1. the 3-query serve batch (the chrome-acceptance window) + one
    #    async submit so the admission-worker span trail exists too
    batch = [A.expr().multiply(B.expr()).multiply_scalar(s)
             for s in (1.0, 2.0, 3.0)]
    outs = sess.run_many(batch)
    ok_batch = len(outs) == 3 and outs[0].shape == (64, 32)
    sess.submit(A.expr().multiply(B.expr())).result()
    sess.serve_drain()

    # 2. compile failure → automatic flight-recorder dump. A mixed-mesh
    #    expression fails _check_one_mesh inside compile_expr — a real
    #    compile-path error, not a monkeypatched one.
    from matrel_tpu.core.blockmatrix import BlockMatrix
    other = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    M_other = BlockMatrix.from_numpy(
        rng.standard_normal((96, 32)).astype(np.float32), mesh=other)
    compile_failed = False
    try:
        sess.run(A.expr().multiply(M_other.expr()))
    except ValueError:
        compile_failed = True
    flight_path = (cfg.obs_flight_recorder_path
                   or trace_lib.DEFAULT_FLIGHT_PATH)
    flight = None
    if os.path.exists(flight_path):
        with open(flight_path) as f:
            flight = json.load(f)

    # 3. one analyze event (the drift feed)
    sess.explain(A.expr().multiply(B.expr()), analyze=True)

    # 4. chrome export over the whole log
    log_path = resolve_path(cfg.obs_event_log
                            or os.environ.get("MATREL_OBS_EVENT_LOG"))
    events = read_events(log_path)
    doc = trace_lib.chrome_trace(events)
    names = {ev["name"] for ev in doc["traceEvents"]}
    ids = {ev["args"].get("span_id") for ev in doc["traceEvents"]}
    parent_linked = sum(
        1 for ev in doc["traceEvents"]
        if ev["args"].get("parent_id") in ids
        and ev["args"].get("parent_id") is not None)

    # 5. drift report + persisted table
    table_path = drift.table_path(cfg)
    drift_report = drift.report(events, table_path_str=table_path)
    drift_rows = len(drift.calibrate(list(drift.iter_samples(events))))

    record = {
        "metric": "flight_recorder_drill",
        "batch_ok": ok_batch,
        "compile_failure_dumped": bool(
            compile_failed and flight
            and flight.get("reason") == "compile_failure"
            and flight.get("records")),
        "flight_path": flight_path,
        "flight_records": len((flight or {}).get("records") or ()),
        "chrome_events": len(doc["traceEvents"]),
        "parent_linked": parent_linked,
        "span_names": sorted(names),
        "drift_rows": drift_rows,
        "drift_table": table_path,
        "log": log_path,
    }
    record["ok"] = bool(
        record["batch_ok"] and record["compile_failure_dumped"]
        and record["chrome_events"] > 0 and record["parent_linked"] > 0
        and {"serve.admit", "serve.batch", "plan.optimize",
             "serve.execute"} <= names
        and drift_rows >= 1
        and os.path.exists(table_path)
        and "drift audit" in drift_report)
    print(json.dumps(record))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
