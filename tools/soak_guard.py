"""On-chip soak runner behind a probe and hard timeouts — `make soak-tpu`.

The real-chip soak (tools/soak.py --tpu) has caught Mosaic bugs that
interpret-mode CI structurally cannot (bf16 rounding is elided in
interpret mode — docs/INTERNALS.md). This watchdog stays off jax (a
chip belongs to one process at a time: the probe, then the soak) and
makes the soak safe to run on a cadence:

1. probe the backend first (tiny matmul in a subprocess under a hard
   timeout — bench.py --_probe),
2. run the soak batteries in their own session/process group under a
   hard timeout (killpg on expiry, so a hung helper can't orphan),
3. append a structured result line to PROGRESS.jsonl either way.

Exit codes: 0 = clean soak; 2 = backend unavailable (probe failed —
not a code failure); 3 = soak timed out; 4 = soak failed (failure
count / signal details are in the PROGRESS.jsonl line).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# $MATREL_PROGRESS_PATH redirects the append target — the dry batch
# fire-drill (tools/tpu_batch.sh --dry) must not write toy-scale CPU
# records into the repo's real capture history
PROGRESS = os.environ.get("MATREL_PROGRESS_PATH",
                          os.path.join(REPO, "PROGRESS.jsonl"))


def _log(event: dict) -> None:
    event = {"ts": time.time(), "event": "soak_tpu", **event}
    try:
        with open(PROGRESS, "a") as f:
            f.write(json.dumps(event) + "\n")
    except OSError as e:
        print(f"# could not append to PROGRESS.jsonl: {e}",
              file=sys.stderr)
    # mirror into the obs/ event log ("soak" kind) so `python -m
    # matrel_tpu history --summary` sees soak outcomes next to query
    # and bench records. obs/events.py loaded by FILE PATH: importing
    # the matrel_tpu package would pull jax into this watchdog, which
    # must stay off jax (the chip is its children's). Never fails the soak.
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_matrel_obs_events",
            os.path.join(REPO, "matrel_tpu", "obs", "events.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.emit_tool_event("soak",
                            {k: v for k, v in event.items() if k != "ts"},
                            anchor_dir=REPO)
    except Exception as e:
        print(f"# soak event not logged: {e}", file=sys.stderr)
    print(json.dumps(event))


def _run_pg(cmd, timeout_s: int):
    """Run cmd in its own session; killpg on timeout. Returns
    (rc, tail) with rc None on timeout."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            cwd=REPO, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        return proc.returncode, "\n".join(out.strip().splitlines()[-8:])
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        out, _ = proc.communicate()
        return None, "\n".join((out or "").strip().splitlines()[-8:])


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, default=10,
                   help="seeds per battery (keep small: deep compiles "
                        "take minutes each)")
    p.add_argument("--battery", default="all")
    p.add_argument("--probe-timeout", type=int, default=180)
    p.add_argument("--soak-timeout", type=int, default=3600)
    args = p.parse_args()

    rc, tail = _run_pg([sys.executable,
                        os.path.join(REPO, "bench.py"), "--_probe"],
                       args.probe_timeout)
    if rc != 0:
        _log({"ok": False, "stage": "probe",
              "detail": "backend probe "
              + ("timed out" if rc is None
                 else f"failed rc={rc}"),
              "tail": tail[-300:]})
        return 2

    t0 = time.time()
    # the dry fire-drill (tools/tpu_batch.sh --dry) soaks the CPU
    # backend, where --tpu's non-interpret Pallas batteries cannot run
    # ("Only interpret mode is supported on CPU backend") — drop the
    # flag there; the harness (probe, process groups, logging) is what
    # the drill proves
    soak_cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py"),
                args.battery, "--seeds", str(args.seeds)]
    if not os.environ.get("MATREL_DRY"):
        soak_cmd.append("--tpu")
    rc, tail = _run_pg(soak_cmd, args.soak_timeout)
    ok = rc == 0
    _log({"ok": ok, "stage": "soak", "battery": args.battery,
          "seeds": args.seeds, "rc": rc,
          "wall_s": round(time.time() - t0, 1),
          "tail": tail[-500:]})
    if ok:
        return 0
    return 3 if rc is None else 4


if __name__ == "__main__":
    sys.exit(main())
