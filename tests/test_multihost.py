"""Multi-process (multi-host analogue) validation: collectives over a
real process boundary via jax.distributed + Gloo — the DCN shape of a
TPU pod (SURVEY.md §5 "Distributed comm backend"). Heavier than the
in-process mesh tests; one spawn of tools/multihost_check.py."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_check(nproc: int, tool_timeout: int, outer_timeout: int) -> str:
    # ephemeral coordinator port; the tool's own --timeout fires before
    # this test's cap, and it kills its worker process group, so a hang
    # cannot orphan coordinator-holding workers on the machine
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "multihost_check.py"),
         "--nproc", str(nproc), "--timeout", str(tool_timeout)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd="/tmp", start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=outer_timeout)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        out, _ = proc.communicate()
        raise AssertionError(f"multihost check hung:\n{out}")
    assert proc.returncode == 0, out
    assert "MULTIHOST CHECK: OK" in out
    return out


def test_two_process_collectives():
    out = _run_check(nproc=2, tool_timeout=120, outer_timeout=240)
    assert "over 8 devices" in out


def test_four_process_collectives():
    """4 processes x 4 virtual devices each — the DCN shape of a 4-host
    pod slice (docs/INTERNALS.md's manual run, folded into CI per
    round-1 VERDICT #8). Heavier than the 2-process test; its own
    generous timeout keeps a Gloo stall from wedging the suite."""
    out = _run_check(nproc=4, tool_timeout=240, outer_timeout=420)
    assert "over 16 devices" in out   # 4x4 global mesh actually formed
