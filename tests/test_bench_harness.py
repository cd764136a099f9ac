"""bench.py harness CI: the capture path must emit ONE parseable JSON
line stamped with the device it ran on, exit non-zero when the probe or
the measurement fails or no chip is found, within bounded wall-clock,
with no leaked processes."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _preserve_bench_caches():
    """bench.py's CPU-reference cache (cpu_baseline.json) lives at the
    repo root and would be overwritten by the N=256 runs — snapshot and
    restore it."""
    path = os.path.join(REPO, "cpu_baseline.json")
    saved = open(path).read() if os.path.exists(path) else None
    try:
        yield
    finally:
        if saved is None:
            if os.path.exists(path):
                os.remove(path)
        else:
            with open(path, "w") as f:
                f.write(saved)


def _run_bench(env_extra, timeout):
    env = dict(os.environ)
    # the children run on the CPU backend end-to-end: the tests run
    # where there is no chip
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("MATREL_DRY", None)
    env.update(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    return proc.returncode, lines, time.monotonic() - t0


def test_success_path_emits_metric_json(tmp_path):
    rc, lines, _ = _run_bench({
        "MATREL_BENCH_N": "256", "MATREL_BENCH_REPEATS": "3",
        "MATREL_DRY": "1",        # the CPU drill: the only CPU measurement
    }, timeout=240)
    assert rc == 0, lines
    out = json.loads(lines[-1])
    assert out["metric"] == "dense_blockmatmul_tflops_per_chip"
    assert out["value"] is not None and out["value"] > 0
    assert out["unit"] == "TFLOPS" and out["vs_baseline"] is not None
    # the line names the device it ran on — a CPU drill says so
    assert out["platform"] == "cpu" and out["device_count"] == 8


def _assert_failed(rc, lines):
    assert rc != 0, lines                  # a failure is not exit 0
    out = json.loads(lines[-1])            # ...and still one JSON line
    assert out["metric"] == "dense_blockmatmul_tflops_per_chip"
    assert out["value"] is None
    assert out["vs_baseline"] is None
    assert out["error"]
    assert "last_known_good" not in out    # no stale number rides along
    return out


def test_dead_backend_exits_nonzero_with_error_json():
    rc, lines, dt = _run_bench({
        # unloadable platform in the CHILDREN: the one probe fails
        "JAX_PLATFORMS": "nosuchplatform",
        "MATREL_BENCH_PROBE_TIMEOUT": "60",
    }, timeout=180)
    _assert_failed(rc, lines)
    assert dt < 120                        # one probe, no retry ladder


def test_no_chip_refuses_to_measure():
    """Off the TPU and outside the dry drill the probe refuses: a CPU
    time is never filed as a per-chip number."""
    rc, lines, _ = _run_bench({"MATREL_BENCH_N": "256",
                               "MATREL_BENCH_REPEATS": "3"}, timeout=180)
    out = _assert_failed(rc, lines)
    assert "no TPU found" in out["error"]
