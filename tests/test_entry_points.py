"""The root scripts the driver and a builder start by hand, each in its
own subprocess on the CPU: ``__graft_entry__.dryrun_multichip`` (every
collective path on virtual devices; since PR 44 its chain and regression
stages are the cells' own ``session.sql`` + ``compute``) and
``chip_smoke.py --rehearse`` (the chip script's control flow in
interpret mode, which never prints ``"ok": true``)."""

import json

import pytest


@pytest.mark.parametrize("devices, mesh", [(8, "{'x': 2, 'y': 4}"),
                                           (4, "{'x': 2, 'y': 2}")])
def test_dryrun_multichip(devices, mesh, run_at_root):
    out = run_at_root(["-c", "import __graft_entry__ as g; "
                       f"g.dryrun_multichip({devices})"])
    assert out.splitlines()[-1] == (
        f"dryrun_multichip({devices}) OK — mesh {mesh}, "
        "chain plan (A·(B·C))")


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal(chips, run_at_root):
    lines = [json.loads(line) for line in run_at_root(
        ["chip_smoke.py", "--rehearse", "--scale", "0.02",
         "--chips", str(chips)], devices=chips).splitlines()]
    assert lines[-1] == {"rehearsal": "passed", "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    assert all(rec["pass"] for rec in lines[:-1]), lines
    assert any(rec["query"].startswith("pagerank.edges")
               for rec in lines[:-1])
