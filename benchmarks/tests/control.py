#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at the cell's own
size on the chip (``--rehearse`` for the CPU):

    python3 benchmarks/tests/control.py --workload <cell> --seeds 11,12,13

For each seed, in one process: the deployment is built as a run builds it,
every query of the cell's mix is sent through the timed path's own entry
(``Deployment.run``) and compared with the plain reference (the sound
reading); the control — the reference computed with its operands rounded
to bfloat16, put in the program's place — is compared the same way; and
so is the program with each lower-precision path of its own switched on
(``Deployment.program_controls``: ``matmul_precision`` high and default,
PageRank's ``passes`` 2 and 1, bfloat16 SpMM operands). The benchmark's own
runs never run this."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


def readings(workload, seeds, rehearse=0.0, repeats=2, out=print):
    from benchmarks import run as harness
    if rehearse > 0:
        # as in a rehearsed run: nothing of a CPU rehearsal goes to the
        # checkout's compile cache (a warm one changes what tier-1's
        # first-contact timing tests measure)
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
    _, _, config, spec, traffic = harness.load_cell(workload)
    queries = sorted({m["query"] for m in traffic["mix"]})
    sound, control, limits, knobs = {}, {}, {}, {}
    for seed in seeds:
        dep = harness.build_deployment(config, spec, seed, queries, rehearse)
        for q in queries:
            want = dep.reference(q)
            for _ in range(repeats):
                ans = dep.run(q, harness.no_span)
            for label, value, limit in dep.compare(q, ans, want):
                sound.setdefault(label, []).append(value)
            for label, value, limit in dep.compare(q, dep.control(q), want):
                control.setdefault(label, []).append(value)
                limits[label] = limit
                out(f"seed={seed} {label} sound={sound[label][-1]!r} "
                    f"control={value!r} limit={limit!r}")
            for knob, got in dep.program_controls(q):
                for label, value, limit in dep.compare(q, got, want):
                    knobs.setdefault(label, {}).setdefault(knob, []) \
                        .append(value)
                    out(f"seed={seed} {label} program[{knob}]={value!r}")
        for label, good, detail in harness.device_op_checks(dep, spec,
                                                            queries):
            out(f"seed={seed} {label} {detail} {'ok' if good else 'FAILED'}")
        del dep
    summary = {label: {"sound_max": max(sound[label]),
                       "control_min": min(control[label]),
                       "program_min": {k: min(v) for k, v in
                                       knobs.get(label, {}).items()},
                       "limit": limits[label]}
               for label in sound}
    out("summary " + json.dumps(summary))
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", type=float, default=0.0)
    args = ap.parse_args()
    if args.rehearse > 0:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if args.rehearse <= 0 and jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             rehearse=args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
