"""Reduction of one profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's busy intervals, the benchmark's own host spans
on the same clock, per-query device time, the top device operations and
the idle gaps named by the benchmark span they fall in.

The reduction works on a neutral structure so that it can be checked on a
synthetic trace (``benchmarks/tests/test_trace_reduce.py``):

    {"device": {plane_name: {"ops": [(name, start_ns, dur_ns), ...],
                             "modules": [(name, start_ns, dur_ns), ...]}},
     "host":   [(span_name, start_ns, dur_ns), ...]}        # bench.* only

``load`` builds it from a file with ``jax.profiler.ProfileData``. Run as
a script it prints what a trace holds, for the look by hand that comes
before any change to the reducer.

What the first trace from the chip showed (PR 24): a device plane
``/device:TPU:0`` with the lines ``XLA Modules`` (one event per program
run), ``XLA Ops`` (its operations, a ``while`` containing its body) and
``Async XLA Ops`` (copies in flight, overlapping the others); the
benchmark's TraceAnnotations on the host plane's ``python3`` line; and a
device clock that ran about 1.4 ms ahead of the host's. So the trace is
taken over the window and nothing else (every device event in it belongs
to a traced query), a query's device time is read from its own program
runs where these pair up with the queries by count, and the device clock
is shifted onto the host's by the bounds the spans give (``clock_shift``).
"""

from __future__ import annotations

import glob
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH_SPAN = "bench.compute"
SPAN_PREFIX = "bench."
QUERY_SPAN = "bench.query:"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            device[plane.name] = {
                key: sorted(((ev.name, float(ev.start_ns),
                              float(ev.duration_ns))
                             for line in plane.lines if line.name == name
                             for ev in line.events), key=lambda e: e[1])
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, float(ev.start_ns),
                             float(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith(SPAN_PREFIX))
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host}


def ops_named(trace: dict, needle: str) -> int:
    """Device operations of the trace whose name — the HLO text, which for
    a custom call ends in its ``custom_call_target`` — holds ``needle``."""
    return sum(1 for plane in trace["device"].values()
               for name, _, _ in plane["ops"] if needle in name)


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi] that the disjoint sorted intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def self_times(ops):
    """Exclusive time per op name on one line: an op that contains others
    (a ``while`` and its body) is charged only what its children leave."""
    total = {}
    stack = []          # [name, end, child_time, dur]

    def close(item):
        name, _, child, dur = item
        total[name] = total.get(name, 0.0) + max(0.0, dur - child)

    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0.0, dur])
    while stack:
        close(stack.pop())
    return total


def segments(spans, lo, hi):
    """[lo, hi] cut into disjoint (start, end, name) pieces, each named by
    the innermost benchmark span that covers it (the spans of one client
    nest), or ``between_queries``."""
    out, stack = [], []
    t = lo

    def emit(upto):
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            out.append((t, upto,
                        stack[-1][0] if stack else "between_queries"))
            t = upto

    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        stack.append((name, start + dur))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def short_name(op: str) -> str:
    """``%fusion.3 f32[4096]`` from the HLO text the profiler names an
    operation by."""
    lhs, _, rhs = op.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return (lhs + " " + shape).strip()[:96]


def clock_shift(queries, launches, runs_by_query, first_op, last_op):
    """Nanoseconds to add to the device clock. In a closed loop a query's
    device work starts after the query was handed over (the start of its
    ``bench.compute`` span, or of the query span), starts before that
    hand-over call returns, and ends before the query does. Each gives a
    bound; where the program runs pair up with the queries
    (``runs_by_query``: each query's (first start, last end) on the device
    clock) every query gives them, otherwise only the first and the last
    do. The shift is the latest the upper bounds allow — the device then
    finishes some tens of microseconds before the host sees it — unless
    that contradicts the lower bound, which then holds."""
    launch = launches if len(launches) == len(queries) else queries
    if runs_by_query is None:
        lower = launch[0][1] - first_op[1]
        upper = queries[-1][1] + queries[-1][2] - (last_op[1] + last_op[2])
        return max(lower, upper)
    lower = max(l[1] - r[0] for l, r in zip(launch, runs_by_query))
    upper = min(q[1] + q[2] - r[1] for q, r in zip(queries, runs_by_query))
    if launch is launches:
        upper = min(upper, min(l[1] + l[2] - r[0]
                               for l, r in zip(launch, runs_by_query)))
    return max(lower, upper)


def reduce(trace: dict) -> dict:
    """All times in seconds. The trace covers the window and nothing else;
    the window runs from the start of the first ``bench.query:*`` span to
    the end of the last."""
    spans = trace["host"]
    queries = [s for s in spans if s[0].startswith(QUERY_SPAN)]
    if not queries:
        raise ValueError("trace holds no bench.query span")
    launches = [s for s in spans if s[0] == LAUNCH_SPAN]
    lo = queries[0][1]
    hi = max(s + d for _, s, d in queries)
    ns = 1e-9
    n = len(queries)
    segs = segments(spans, lo, hi)
    busy, per_query, op_time, gap_time, paired = [], [], {}, {}, []
    for plane in sorted(trace["device"]):
        ops = trace["device"][plane]["ops"]
        mods = trace["device"][plane]["modules"]
        if not ops:
            continue
        merged = merge((s, s + d) for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged) * ns)
        for name, t in self_times(ops).items():
            name = short_name(name)
            op_time[name] = op_time.get(name, 0.0) + t * ns
        runs = len(mods) // n if mods and len(mods) % n == 0 else 0
        paired.append(bool(runs))
        if runs:
            groups = [mods[k * runs:(k + 1) * runs] for k in range(n)]
            per_query.append([sum(covered(merged, s, s + d)
                                  for _, s, d in g) * ns for g in groups])
            shift = clock_shift(
                queries, launches,
                [(g[0][1], g[-1][1] + g[-1][2]) for g in groups], None, None)
        else:
            shift = clock_shift(queries, launches, None, ops[0],
                                max(ops, key=lambda o: o[1] + o[2]))
            per_query.append([covered(merged, s - shift, s + d - shift) * ns
                              for _, s, d in queries])
        edges = [lo] + [x + shift for iv in merged for x in iv] + [hi]
        gaps = [(max(a, lo), min(b, hi))
                for a, b in zip(edges[0::2], edges[1::2])]
        i = 0
        for a, b in gaps:       # both lists are sorted and disjoint
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                part = min(b, segs[j][1]) - max(a, segs[j][0])
                if part > 0:
                    gap_time[segs[j][2]] = \
                        gap_time.get(segs[j][2], 0.0) + part * ns
                j += 1
    chips = max(len(busy), 1)
    device_s = [sum(col) / chips for col in zip(*per_query)] \
        if per_query else [0.0] * n

    def top(d):
        return [[k, v / chips] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / chips,
        "chips_traced": len(busy),
        "runs_paired_with_queries": bool(paired) and all(paired),
        "n_device_ops": sum(len(v["ops"]) for v in trace["device"].values()),
        "queries": [{"template": q[0][len(QUERY_SPAN):], "span_s": q[2] * ns,
                     "device_s": dev}
                    for q, dev in zip(queries, device_s)],
        "spans": {name: [d * ns for m, _, d in spans if m == name]
                  for name in {s[0] for s in spans
                               if not s[0].startswith(QUERY_SPAN)}},
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }


def describe(path: str, out=sys.stdout, events: int = 12) -> None:
    """What the file holds: planes, lines, event counts, first events with
    their stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}", file=out)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0.0) + ev.duration_ns
            for name, ns_ in sorted(names.items(),
                                    key=lambda kv: -kv[1])[:events]:
                print(f"    sum {ns_ / 1e6:10.3f} ms  {name[:120]}", file=out)
            for ev in evs[:4]:
                stats = {k: str(v)[:80] for k, v in ev.stats}
                print(f"    first {ev.name[:60]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}", file=out)


if __name__ == "__main__":
    target = sys.argv[1]
    describe(find_xplane(target) if os.path.isdir(target) else target)
