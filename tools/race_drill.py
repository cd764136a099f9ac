"""race_drill — deterministic-seeded thread-interleaving drill for
the serve/fleet concurrency plane, run with runtime lockdep armed
(utils/lockdep.py; the dynamic half of tools/lockcheck.py — see
docs/CONCURRENCY.md).

Four known-hairy schedules, each the scene of a past (or statically
predicted) race, each trial seeded so a failure reproduces by seed:

  submit_close_drain   concurrent submit / close / drain against one
                       pipeline (the PR 8 submit-vs-close window and
                       drain-wedge class)
  kill_replication     kill_slice racing a rebind's re-replication
                       and in-flight directory inserts (PR 15's
                       invalidation-ordering plane)
  rebind_probes        register() rebinds racing identical template
                       queries (plan-template reuse + cross-query CSE
                       probes, the PR 17 sharing planes)
  delta_serve          register_delta IVM maintenance under live
                       serve load (the PR 13 patch-vs-lookup window)

Rebinds and deltas are VALUE-PRESERVING (same numbers, new objects),
so every resolved answer has one oracle regardless of interleaving:
any mismatch is a real race, not an ordering ambiguity.

Contract (the artifact line, asserted by tests/test_drills.py):
  - 0 wrong answers
  - 0 untyped failures (every refusal is ResilienceError-family)
  - lockdep order graph acyclic, 0 inversions recorded, across all
    seeds x schedules

Knobs (env, dry-run friendly):
  MATREL_RACE_SEEDS       trials per schedule     (default 8)
  MATREL_RACE_QUERIES     queries per trial       (default 10)
  MATREL_RACE_SCHEDULES   comma list to run       (default all)

Usage:
  python tools/race_drill.py            # CPU-forced, prints one JSON line
  MATREL_RACE_SEEDS=2 python tools/race_drill.py   # tier-1's size
"""

from __future__ import annotations

import json
import os
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-forced BEFORE jax import (the drill-tool idiom: jax reads the
# environment once, when it is first imported)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from matrel_tpu.config import MatrelConfig  # noqa: E402
from matrel_tpu.resilience.errors import ResilienceError  # noqa: E402
from matrel_tpu.session import MatrelSession  # noqa: E402
from matrel_tpu.utils import lockdep  # noqa: E402

N = 48                  # table side — small: interleaving, not FLOPs
TIMEOUT = 60            # every wait in the drill is bounded (wedge-safe)


def _base_cfg(**kw) -> MatrelConfig:
    """Drill base config; MATREL_* env still flows over it (the
    provenance_drill idiom) so a caller can tighten knobs."""
    base = dict(lockdep_enable=True, lockdep_raise=False,
                serve_max_batch=1,
                result_cache_max_bytes=64 << 20)
    base.update(kw)
    return MatrelConfig.from_env(MatrelConfig(**base))


def _mats(sess, rng, names=("A", "B")):
    mats = {}
    for nm in names:
        arr = rng.standard_normal((N, N)).astype(np.float32)
        mats[nm] = arr
        sess.register(nm, sess.from_numpy(arr))
    return mats


def _score(futs, oracle, tol=3e-3):
    """(wrong, untyped, resolved) over a list of futures sharing one
    oracle. Typed refusals are the contract, not failures."""
    wrong = untyped = resolved = 0
    for fut in futs:
        try:
            got = np.asarray(fut.result(timeout=TIMEOUT).to_numpy())
            err = float(np.abs(got - oracle).max())
            if err > tol * max(float(np.abs(oracle).max()), 1.0):
                wrong += 1
            else:
                resolved += 1
        except ResilienceError:
            pass
        except Exception:  # noqa: BLE001 — untyped IS the finding
            untyped += 1
    return wrong, untyped, resolved


def _close(sess):
    try:
        sess.serve_close(timeout=TIMEOUT)
    except Exception:  # noqa: BLE001 — teardown best-effort
        pass


# -- schedules ---------------------------------------------------------------

def sched_submit_close_drain(seed: int, queries: int) -> dict:
    """Submitter races a drainer and a closer on one pipeline. Late
    submits must refuse TYPED (PipelineClosed/AdmissionShed), resolved
    answers must be right, and nothing may wedge."""
    rng = np.random.default_rng(seed)
    sess = MatrelSession(config=_base_cfg())
    try:
        mats = _mats(sess, rng)
        expr = sess.table("A").expr().multiply(sess.table("B").expr())
        oracle = mats["A"] @ mats["B"]
        close_after = int(rng.integers(1, max(queries - 1, 2)))
        submitted = threading.Semaphore(0)
        futs, errs = [], []

        def _drain():
            submitted.acquire(timeout=TIMEOUT)
            try:
                sess.serve_drain(timeout=TIMEOUT)
            except ResilienceError:
                pass
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def _closer():
            for _ in range(close_after):
                submitted.acquire(timeout=TIMEOUT)
            try:
                sess.serve_close(timeout=TIMEOUT)
            except ResilienceError:
                pass
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=_drain, daemon=True),
              threading.Thread(target=_closer, daemon=True)]
        for t in ts:
            t.start()
        typed_refusals = 0
        for _ in range(queries):
            try:
                futs.append(sess.submit(expr))
            except ResilienceError:
                typed_refusals += 1    # closed/shed mid-race: typed
            submitted.release()
        for _ in range(queries, close_after + 1):
            submitted.release()        # closer never starves
        for t in ts:
            t.join(timeout=TIMEOUT)
        wedged = any(t.is_alive() for t in ts)
        wrong, untyped, resolved = _score(futs, oracle)
        untyped += len(errs) + (1 if wedged else 0)
        return {"wrong": wrong, "untyped": untyped,
                "resolved": resolved, "refused": typed_refusals}
    finally:
        _close(sess)


def sched_kill_replication(seed: int, queries: int) -> dict:
    """kill_slice concurrent with a value-preserving rebind (which
    re-replicates under the registration lock) and a live stream."""
    rng = np.random.default_rng(seed)
    sess = MatrelSession(config=_base_cfg(
        fleet_slices=2, fleet_replicate_hits=0))
    try:
        mats = _mats(sess, rng)
        expr = sess.table("A").expr().multiply(sess.table("B").expr())
        oracle = mats["A"] @ mats["B"]
        victim = int(rng.integers(0, 2))
        kill_at = int(rng.integers(1, max(queries - 1, 2)))
        errs = []

        def _rebind():
            try:
                # same values, new device objects: forces the full
                # on_register surgery + re-replication path
                sess.register("A", sess.from_numpy(mats["A"]))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        futs = []
        rb = threading.Thread(target=_rebind, daemon=True)
        for i in range(queries):
            futs.append(sess.submit(expr))
            if i == kill_at:
                rb.start()
                sess._fleet.kill_slice(victim)
        rb.join(timeout=TIMEOUT)
        try:
            sess.serve_drain(timeout=TIMEOUT)
        except ResilienceError:
            pass
        wrong, untyped, resolved = _score(futs, oracle)
        untyped += len(errs) + (1 if rb.is_alive() else 0)
        return {"wrong": wrong, "untyped": untyped,
                "resolved": resolved, "refused": 0}
    finally:
        _close(sess)


def sched_rebind_probes(seed: int, queries: int) -> dict:
    """register() rebind storm racing identical template queries —
    the plan-template + cross-query-CSE sharing planes must never
    serve a torn binding."""
    rng = np.random.default_rng(seed)
    sess = MatrelSession(config=_base_cfg())
    try:
        mats = _mats(sess, rng)
        expr = (sess.table("A").expr()
                .multiply(sess.table("B").expr()).add_scalar(1.0))
        oracle = mats["A"] @ mats["B"] + 1.0
        stop = threading.Event()
        errs = []

        def _rebinder():
            try:
                while not stop.is_set():
                    sess.register("A", sess.from_numpy(mats["A"]))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        rb = threading.Thread(target=_rebinder, daemon=True)
        rb.start()
        futs = [sess.submit(expr) for _ in range(queries)]
        try:
            sess.serve_drain(timeout=TIMEOUT)
        except ResilienceError:
            pass
        stop.set()
        rb.join(timeout=TIMEOUT)
        wrong, untyped, resolved = _score(futs, oracle)
        untyped += len(errs) + (1 if rb.is_alive() else 0)
        return {"wrong": wrong, "untyped": untyped,
                "resolved": resolved, "refused": 0}
    finally:
        _close(sess)


def sched_delta_serve(seed: int, queries: int) -> dict:
    """register_delta (zero-valued COO delta: IVM machinery runs,
    values stand still) under live serve load."""
    rng = np.random.default_rng(seed)
    sess = MatrelSession(config=_base_cfg())
    try:
        mats = _mats(sess, rng)
        expr = sess.table("A").expr().multiply(sess.table("B").expr())
        oracle = mats["A"] @ mats["B"]
        errs = []
        k = 8
        rows = rng.integers(0, N, size=k)
        cols = rng.integers(0, N, size=k)
        vals = np.zeros(k, dtype=np.float32)

        futs = []
        for i in range(queries):
            futs.append(sess.submit(expr))
            if i % 3 == 1:
                try:
                    sess.register_delta("A", (rows, cols, vals),
                                        kind="coo")
                except ResilienceError:
                    pass
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
        try:
            sess.serve_drain(timeout=TIMEOUT)
        except ResilienceError:
            pass
        wrong, untyped, resolved = _score(futs, oracle)
        untyped += len(errs)
        return {"wrong": wrong, "untyped": untyped,
                "resolved": resolved, "refused": 0}
    finally:
        _close(sess)


SCHEDULES = {
    "submit_close_drain": sched_submit_close_drain,
    "kill_replication": sched_kill_replication,
    "rebind_probes": sched_rebind_probes,
    "delta_serve": sched_delta_serve,
}


def main() -> int:
    seeds = int(os.environ.get("MATREL_RACE_SEEDS", "8"))
    queries = int(os.environ.get("MATREL_RACE_QUERIES", "10"))
    picked = os.environ.get("MATREL_RACE_SCHEDULES", "")
    names = ([s for s in picked.split(",") if s in SCHEDULES]
             if picked else list(SCHEDULES))

    totals = {"wrong": 0, "untyped": 0, "resolved": 0, "refused": 0}
    per_sched = {}
    inversions = 0
    dispatch_holds = 0
    acyclic = True
    trials = 0
    for name in names:
        fn = SCHEDULES[name]
        agg = {k: 0 for k in totals}
        for seed in range(seeds):
            # fresh order graph per trial: a cycle reproduces by
            # (schedule, seed), not by whatever ran before it
            lockdep.reset()
            res = fn(1000 * (list(SCHEDULES).index(name) + 1) + seed,
                     queries)
            trials += 1
            for key in totals:
                agg[key] += res[key]
                totals[key] += res[key]
            diags = lockdep.diagnostics()
            inversions += sum(1 for d in diags
                              if d["diag"] in ("inversion",
                                               "self_deadlock"))
            dispatch_holds += sum(
                1 for d in diags
                if d["diag"] == "held_across_dispatch")
            if not lockdep.is_acyclic():
                acyclic = False
            print(f"  {name} seed {seed}: {res}", file=sys.stderr,
                  flush=True)
        per_sched[name] = agg
    lockdep.reset()
    lockdep.disable()

    ok = (totals["wrong"] == 0 and totals["untyped"] == 0
          and inversions == 0 and acyclic
          and totals["resolved"] > 0)
    artifact = {
        "metric": "race_drill",
        "seeds": seeds,
        "queries": queries,
        "trials": trials,
        "schedules": per_sched,
        "wrong": totals["wrong"],
        "untyped": totals["untyped"],
        "resolved": totals["resolved"],
        "refused": totals["refused"],
        "inversions": inversions,
        "held_across_dispatch": dispatch_holds,
        "acyclic": acyclic,
        "ok": ok,
    }
    print(json.dumps(artifact), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
