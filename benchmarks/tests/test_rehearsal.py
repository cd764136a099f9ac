"""CPU rehearsals of every cell of BENCHMARK.json (rows cut, Pallas
interpreted), run by hand:

    python -m pytest benchmarks/tests -q -p no:cacheprovider

* each cell runs end to end, untraced and traced, and is correct;
* the timed path broken underneath (every answer altered where it is
  produced) comes out as not correct;
* the control — the reference with its operands rounded to bfloat16, in
  the program's place — breaks a limit of every cell, and the sound
  program breaks none;
* a named device operation that did not run (a silent fallback from a
  Pallas kernel) comes out as not correct;
* a traffic file with a key the generator does not read is refused.

Not part of the repo's tier-1 tests. A rehearsal never prints a metric
value: times come from the chip alone."""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as harness  # noqa: E402
import control  # noqa: E402

SPEC = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = "2147483999"     # more than 31 bits hold


def scale_of(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    return str(harness.load_json(os.path.join(
        BENCH, "workloads", w["traffic"] + ".json"))["rehearse_scale"])


def names(kind, cell):
    return {m["name"] for m in SPEC[kind]
            if cell in m.get("workloads", [cell])}


def args(cell, trace):
    return ["--workload", cell, "--seed", SEED, "--seconds", "1",
            "--trace", str(trace), "--rehearse", scale_of(cell)]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_is_correct(cell, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args(cell, trace),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, out.stdout[-2000:]
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    if trace == 0:
        assert set(last["metric_names"]) == names("end_to_end", cell)
    else:       # no device plane on the CPU: only the host-side readers read
        assert {"first_call_s", "session_host_ms"} <= set(last["metric_names"])
        assert set(last["metric_names"]) <= names("per_layer", cell)


def test_without_a_chip_there_is_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in out.stdout.splitlines())


@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, monkeypatch, capsys):
    real = harness.load_module

    def load_broken(path):
        mod = real(path)
        if hasattr(mod, "Deployment"):
            run = mod.Deployment.run
            mod.Deployment.run = \
                lambda self, q, span: run(self, q, span) * 1.001
        return mod

    monkeypatch.setattr(harness, "load_module", load_broken)
    assert harness.main(args(cell, 0)) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["attempted"] > 0


def test_a_device_operation_that_did_not_run_is_not_correct(monkeypatch,
                                                            capsys):
    """The device plane of a chip run in which plain XLA answered: the
    check that reads it is the harness's, whatever the program says."""
    cell = next(w["name"] for w in SPEC["workloads"]
                if w["config"] == "matrel_sparse_graph")
    monkeypatch.setattr(
        harness, "device_op_checks", lambda dep, spec, queries: [
            (q + ".device_op", False, "ran 0 times") for q in queries
            if spec["queries"][q].get("device_op")])
    assert harness.main(args(cell, 0)) == 0
    out = capsys.readouterr().out
    assert ".device_op ran 0 times FAILED" in out
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False


def test_a_traffic_key_nothing_reads_is_refused(monkeypatch, capsys):
    real = harness.load_json

    def with_clients(path):
        d = real(path)
        if os.sep + "workloads" + os.sep in path:
            d["clients"] = 8
        return d

    monkeypatch.setattr(harness, "load_json", with_clients)
    assert harness.main(args(CELLS[0], 0)) != 0
    captured = capsys.readouterr()
    assert "clients" in captured.err
    assert not any(ln.startswith("{") for ln in captured.out.splitlines())


@pytest.mark.parametrize("cell", CELLS)
def test_control_breaks_a_limit_and_the_program_none(cell):
    summary = control.readings(cell, [1, 2, 3000000003],
                               rehearse=float(scale_of(cell)),
                               out=lambda line: None)
    assert all(r["sound_max"] <= r["limit"] for r in summary.values()), summary
    assert any(r["control_min"] > r["limit"] for r in summary.values()), summary
