"""Examples smoke battery: every examples/*.py must run clean on the
CPU mesh — worked examples are documentation and rot silently without
this (each runs in its own subprocess so platform env is hermetic)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    f[:-3] for f in os.listdir(os.path.join(REPO, "examples"))
    if f.endswith(".py"))


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs_clean(name):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the examples run on the CPU: prepend the repo to the inherited path
    prev = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO, *prev])
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", f"{name}.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert r.returncode == 0, (name, r.stdout[-800:], r.stderr[-800:])
    assert r.stdout.strip(), f"{name} printed nothing"
