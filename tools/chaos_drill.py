"""Chaos drill: a mixed serve stream under a seeded fault schedule.

The resilience layer's acceptance harness (docs/RESILIENCE.md): drive >= 50 queries — direct
``run``, micro-batched ``run_many``, async ``submit`` — through a
session whose EVERY instrumented choke point (compile, lower,
strategy, execute, rc_probe, serve_admit, checkpoint) injects
transient faults on a deterministic seeded schedule, plus deliberate
poison queries and an impossible deadline, and assert
converge-to-correct-or-typed-failure:

  - every healthy query's result matches its numpy oracle
    (0 wrong answers — retries + the degradation ladder absorb every
    transient);
  - ONLY the deterministic-fault queries fail, each with a TYPED
    error (the mixed-mesh poisons raise ValueError and fail exactly
    their own futures — batch-bisection isolation; the impossible
    deadline raises DeadlineExceeded);
  - zero hangs: the whole stream drains under an explicit timeout
    (``serve_drain(timeout=...)`` — a wedge raises the typed
    DrainTimeout instead of wedging this script);
  - every instrumented site actually CHECKED and actually FIRED under
    the schedule (the injector's own stats — a silently-unwired site
    would pass vacuously);
  - a checkpoint save/restore cycle survives its injected IO faults
    and round-trips the catalog exactly.

Emits one parseable JSON line (asserted by tests/test_drills.py).
CPU-only by construction — this drills the recovery plumbing, not the
chip, so it forces the CPU backend.
MATREL_CHAOS_SEED varies the schedule; any fixed seed is bit-for-bit
reproducible.
"""

import json
import os
import shutil
import sys
import tempfile

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

#: Transient faults at EVERY instrumented site: one guaranteed nth-call
#: fire per site (coverage cannot depend on luck) plus capped random
#: fires (max= bounds total fires, so the stream provably converges —
#: retries outnumber the worst-case fire budget).
FAULT_SPEC = (
    "compile:transient:n=3;compile:transient:p=0.05:max=2;"
    "lower:transient:n=40;lower:transient:p=0.002:max=2;"
    "strategy:transient:n=5;strategy:transient:p=0.02:max=2;"
    "execute:transient:n=4;execute:transient:p=0.05:max=2;"
    "rc_probe:transient:n=6;rc_probe:transient:p=0.03:max=2;"
    "serve_admit:transient:n=2;serve_admit:transient:p=0.1:max=2;"
    "checkpoint:transient:n=1"
)


def main() -> int:
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.obs.events import read_events, resolve_path
    from matrel_tpu.obs.history import summarize
    from matrel_tpu.resilience import errors as rerrors, faults
    from matrel_tpu.session import MatrelSession
    from matrel_tpu.utils.checkpoint import CheckpointManager

    seed = int(os.environ.get("MATREL_CHAOS_SEED", "0"))
    faults.reset()
    # env (MATREL_*) overrides flow over the drill's base config, so
    # a caller's redirects land every artifact where it says
    cfg = MatrelConfig.from_env(MatrelConfig(
        fault_inject=FAULT_SPEC,
        fault_inject_seed=seed,
        retry_max_attempts=6,
        retry_backoff_ms=1.0,
        retry_jitter=0.5,
        obs_level="on",
        result_cache_max_bytes=1 << 26,
        serve_max_batch=5,
    ))
    mesh = mesh_lib.make_mesh((2, 4))
    sess = MatrelSession(mesh=mesh, config=cfg)
    rng = np.random.default_rng(seed)
    an, bn = (rng.standard_normal((48, 64)).astype(np.float32),
              rng.standard_normal((64, 24)).astype(np.float32))
    A, B = sess.from_numpy(an), sess.from_numpy(bn)
    other = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    M_other = BlockMatrix.from_numpy(bn, mesh=other)

    wrong = 0
    typed_failures = []
    untyped_failures = []
    n_queries = 0

    def check(got, want, tag):
        nonlocal wrong
        if not np.allclose(got, want, rtol=3e-4, atol=3e-4):
            wrong += 1
            print(f"# WRONG ANSWER: {tag}", file=sys.stderr)

    def expr_oracle(i):
        s = float(i % 7 + 1)
        if i % 3 == 0:
            return (A.expr().t().multiply(A.expr())
                    .multiply_scalar(s), (an.T @ an) * s)
        if i % 3 == 1:
            return (A.expr().multiply(B.expr())
                    .multiply_scalar(s), (an @ bn) * s)
        return (A.expr().multiply(B.expr()).add(
            A.expr().multiply(B.expr())), 2 * (an @ bn))

    # -- 1. direct session.run stream (20 queries) ------------------------
    for i in range(20):
        e, want = expr_oracle(i)
        n_queries += 1
        try:
            check(sess.run(e).to_numpy(), want, f"run[{i}]")
        except Exception as ex:  # noqa: BLE001 — tallied below
            (typed_failures if isinstance(ex, rerrors.ResilienceError)
             else untyped_failures).append(
                 (f"run[{i}]", type(ex).__name__))

    # -- 2. micro-batched run_many (4 batches x 4 = 16 queries) -----------
    for b in range(4):
        batch, wants = zip(*(expr_oracle(b * 4 + j) for j in range(4)))
        n_queries += len(batch)
        try:
            outs = sess.run_many(list(batch))
            for j, (o, w) in enumerate(zip(outs, wants)):
                check(o.to_numpy(), w, f"run_many[{b}][{j}]")
        except Exception as ex:  # noqa: BLE001 — tallied below
            (typed_failures if isinstance(ex, rerrors.ResilienceError)
             else untyped_failures).append(
                 (f"run_many[{b}]", type(ex).__name__))

    # -- 3. async submit stream incl. ONE poison in a 5-query batch -------
    # (batch bisection: exactly the poison's future may fail, typed)
    futs, wants = [], []
    for i in range(4):
        e, want = expr_oracle(10 + i)
        futs.append(sess.submit(e))
        wants.append(want)
    poison_fut = sess.submit(A.expr().multiply(M_other.expr()))
    n_queries += 5
    for i in range(9):          # a second wave keeps the worker busy
        e, want = expr_oracle(20 + i)
        futs.append(sess.submit(e))
        wants.append(want)
        n_queries += 1
    try:
        sess.serve_drain(timeout=300.0)
    except rerrors.DrainTimeout as ex:
        print(f"# DRAIN TIMEOUT: {ex}", file=sys.stderr)
        untyped_failures.append(("serve_drain", "DrainTimeout"))
    sibling_failures = 0
    for i, (f, w) in enumerate(zip(futs, wants)):
        ex = f.exception(timeout=60)
        if ex is not None:
            sibling_failures += 1
            untyped_failures.append((f"submit[{i}]",
                                     type(ex).__name__))
        else:
            check(f.result().to_numpy(), w, f"submit[{i}]")
    poison_ex = poison_fut.exception(timeout=60)
    poison_isolated = (isinstance(poison_ex, ValueError)
                      and sibling_failures == 0)
    if poison_ex is not None:
        typed_failures.append(("poison", type(poison_ex).__name__))

    # -- 4. an impossible deadline fails TYPED ----------------------------
    n_queries += 1
    deadline_typed = False
    try:
        sess.run(expr_oracle(0)[0], deadline_ms=1e-6)
    except rerrors.DeadlineExceeded:
        deadline_typed = True
        typed_failures.append(("deadline", "DeadlineExceeded"))
    except Exception as ex:  # noqa: BLE001 — wrong type = drill failure
        untyped_failures.append(("deadline", type(ex).__name__))

    # -- 5. checkpoint round-trip under injected IO faults ----------------
    ckpt_ok = False
    d = tempfile.mkdtemp(prefix="matrel_chaos_ckpt_")
    try:
        sess.register("A", A)
        mgr = CheckpointManager(d, config=cfg)
        for attempt in range(6):
            try:
                mgr.save(attempt, matrices={"A": A})
                got = mgr.restore(mesh)
                ckpt_ok = (got is not None and np.allclose(
                    got[1]["A"].to_numpy(), an, rtol=1e-6, atol=1e-6))
                break
            except rerrors.InjectedFault:
                continue        # the drill's own driver-level retry
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # -- verdict ----------------------------------------------------------
    stats = faults.injector_for(cfg).stats()
    sites_checked = sorted(s for s, v in stats.items()
                           if v["calls"] > 0)
    sites_fired = sorted(s for s, v in stats.items() if v["fires"] > 0)
    log_path = resolve_path(cfg.obs_event_log
                            or os.environ.get("MATREL_OBS_EVENT_LOG"))
    rollup = summarize(read_events(log_path)).get("resilience", {})
    record = {
        "metric": "chaos_drill",
        "seed": seed,
        "queries": n_queries,
        "wrong_answers": wrong,
        "typed_failures": len(typed_failures),
        "untyped_failures": len(untyped_failures),
        "failure_heads": (typed_failures + untyped_failures)[:8],
        "poison_isolated": poison_isolated,
        "deadline_typed": deadline_typed,
        "checkpoint_ok": ckpt_ok,
        "sites_checked": sites_checked,
        "sites_fired": sites_fired,
        "fault_stats": stats,
        "retries": rollup.get("retries", 0),
        "degrades": rollup.get("degrades", 0),
        "log": log_path,
    }
    record["ok"] = bool(
        n_queries >= 50
        and wrong == 0
        and not untyped_failures
        and poison_isolated
        and deadline_typed
        and ckpt_ok
        and set(sites_checked) == set(faults.SITES)
        and set(sites_fired) == set(faults.SITES)
        and record["retries"] > 0)
    print(json.dumps(record))
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
