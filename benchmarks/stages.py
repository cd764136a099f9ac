#!/usr/bin/env python3
"""Where the device idled, by the program's own stage — a tool, not a
metric:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 30 \\
        --trace 1 --keep-trace DIR
    python3 benchmarks/stages.py DIR

Loads the kept trace with the host events of both prefixes — the
benchmark's ``bench.*`` and the program's ``matrel.*``
(``matrel_tpu/obs/trace.py``; both are ``TraceAnnotation``s on one
thread's line, so they nest) — and runs ``trace_reduce.reduce`` on it
unchanged: ``idle_gaps`` then names each gap by the innermost span,
which is the program's wherever the program has one. Also prints the
median of every span, and how each ``matrel.*`` span lies in the
``bench.*`` span around it. For PERF.md section 5.

    python3 benchmarks/stages.py DIR RING.json

also holds the program's ring (``obs.trace.profile_spans()`` of the same
process, as JSON) against the trace: the trace's times count from the
session's start and the ring's from the epoch, so the two clocks are
one if the differences of matching spans are one constant; prints the
largest departure from their median.
"""

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

PREFIXES = ("bench.", "matrel.")


def load(path: str) -> dict:
    """``trace_reduce.load`` with the program's spans kept too."""
    was = trace_reduce.SPAN_PREFIX
    trace_reduce.SPAN_PREFIX = PREFIXES     # str.startswith takes a tuple
    try:
        return trace_reduce.load(path)
    finally:
        trace_reduce.SPAN_PREFIX = was


def containment(host) -> dict:
    """{program span name: {enclosing benchmark span name: count}} — the
    innermost ``bench.*`` span (not a ``bench.query:``) each ``matrel.*``
    span lies inside, or ``outside``."""
    bench = [s for s in host if s[0].startswith("bench.")
             and not s[0].startswith(trace_reduce.QUERY_SPAN)]
    out = {}
    for name, start, dur in host:
        if not name.startswith("matrel."):
            continue
        inside = [b for b in bench
                  if b[1] <= start and start + dur <= b[1] + b[2]]
        outer = min(inside, key=lambda b: b[2])[0] if inside else "outside"
        counts = out.setdefault(name, {})
        counts[outer] = counts.get(outer, 0) + 1
    return out


def clock_residual_ns(ring, host):
    """(largest |difference - median difference|, median difference,
    spans matched) over ring records and trace events of the same name,
    paired in order of their start. The ring may go on after the trace
    ended (a later session's spans): the first of each name pair up."""
    diffs = []
    for name in {r["name"] for r in ring}:
        mine = sorted(r["start_ns"] for r in ring if r["name"] == name)
        theirs = sorted(start for n, start, _ in host if n == name)
        # whole nanoseconds: a float near 1.8e18 is 256 ns coarse
        diffs += [a - round(b) for a, b in zip(mine, theirs)]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    return max(abs(d - offset) for d in diffs), offset, len(diffs)


def report(trace: dict, out=sys.stdout) -> dict:
    reduced = trace_reduce.reduce(trace)
    n = len(reduced["queries"])
    print(f"queries={n} window_s={reduced['window_s']:.6f} "
          f"busy_s={reduced['busy_s']:.6f}", file=out)
    print("idle gaps, by the innermost span (seconds, share of idle):",
          file=out)
    idle = sum(t for _, t in reduced["idle_gaps"]) or 1.0
    for name, t in reduced["idle_gaps"]:
        print(f"  {t:10.6f}  {100 * t / idle:5.1f}%  {name}", file=out)
    print("spans (count, a query, median ms):", file=out)
    for name, lengths in sorted(reduced["spans"].items()):
        print(f"  {len(lengths):6d}  {len(lengths) / n:5.2f}  "
              f"{statistics.median(lengths) * 1e3:10.4f}  {name}", file=out)
    inside = containment(trace["host"])
    print("program spans inside benchmark spans:", file=out)
    for name, counts in sorted(inside.items()):
        print(f"  {name}: " + ", ".join(
            f"{k} {v}" for k, v in sorted(counts.items())), file=out)
    return {"reduced": reduced, "containment": inside}


if __name__ == "__main__":
    target = sys.argv[1]
    loaded = load(trace_reduce.find_xplane(target)
                  if os.path.isdir(target) else target)
    report(loaded)
    if len(sys.argv) > 2:
        with open(sys.argv[2]) as f:
            found = clock_residual_ns(json.load(f), loaded["host"])
        print("ring against trace: " + (
            "no span of the ring is in the trace" if found is None else
            f"largest residual {found[0]:.0f} ns over {found[2]} spans "
            f"(session start {found[1]:.0f} ns after the epoch)"))
