"""Examples smoke battery: every examples/*.py must run clean on the
CPU mesh — worked examples are documentation and rot silently without
this (each runs in its own subprocess so platform env is hermetic)."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    f[:-3] for f in os.listdir(os.path.join(REPO, "examples"))
    if f.endswith(".py"))


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs_clean(name, run_at_root):
    out = run_at_root([os.path.join("examples", f"{name}.py")])
    assert out, f"{name} printed nothing"
