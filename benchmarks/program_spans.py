"""What the span readers under ``benchmarks/metrics/`` share: the
program's own spans of the traced window.

The program records a span while any profiler session runs
(``matrel_tpu.obs.trace``: one record ``{name, start_ns, end_ns,
span_id, parent_id, qid, tid, attrs}`` in a process-wide ring, read
with ``profile_spans()``), so in a ``--trace 1`` run the ring holds the
traced window, after it the one more call per query of the harness's
``device_op`` check, and before it whatever an earlier session of the
process left. The ring's clock is the trace's plus the session's start,
which the reduced trace does not keep, so the window is found by its
length: its query roots (``matrel.compute``, ``matrel.pagerank``: one
inside each ``bench.query`` span) are the first run of N consecutive
roots that fits into the trace's ``window_s``, N the trace's count of
queries, and its records those that start no further outside that run
than the time the run leaves of the window, and before the root that
follows it. A program without the ring (a parent commit), a ring with
no such run, or a run without a reduced trace gives None, with a line
saying which: the metric is then left out of the result.
"""

import statistics

QUERY_ROOTS = ("matrel.compute", "matrel.pagerank")


def ring():
    """The program's records, oldest start first; None where the
    program has no such ring."""
    try:
        from matrel_tpu.obs.trace import profile_spans
    except ImportError:
        return None
    return sorted(profile_spans(), key=lambda r: r["start_ns"])


def window(run, records=None):
    """(records of the window, its query roots), or None. ``records``
    stands in for the ring in tests."""
    if records is None:
        records = ring()
        if records is None:
            run.say("program spans: the program records none "
                    "(no obs.trace.profile_spans)")
            return None
    if not run.reduced or not run.reduced["queries"]:
        run.say("program spans: no reduced trace to count the queries by")
        return None
    n = len(run.reduced["queries"])
    window_ns = run.reduced["window_s"] * 1e9
    roots = [r for r in records if r["name"] in QUERY_ROOTS]
    for k in range(len(roots) - n + 1):
        spare = window_ns - (roots[k + n - 1]["end_ns"]
                             - roots[k]["start_ns"])
        if spare >= 0:
            break
    else:
        run.say(f"program spans: {len(roots)} query roots in the ring, "
                f"no {n} in a row within the trace's "
                f"{run.reduced['window_s']:.4f} s")
        return None
    lo = roots[k]["start_ns"] - spare
    hi = roots[k + n - 1]["end_ns"] + spare
    if k + n < len(roots):
        hi = min(hi, roots[k + n]["start_ns"])
    return ([r for r in records if lo <= r["start_ns"] < hi],
            roots[k:k + n])


def ms(record) -> float:
    return (record["end_ns"] - record["start_ns"]) * 1e-6


def median_ms(run, name):
    """Median length of the window's spans of one name."""
    found = window(run)
    if found is None:
        return None
    lengths = [ms(r) for r in found[0] if r["name"] == name]
    if not lengths:
        run.say(f"program spans: no {name} in the window")
        return None
    return statistics.median(lengths)


def self_ms(root, records) -> float:
    """A span's length less what its children cover (the children of one
    thread's span lie one after the other)."""
    return ms(root) - sum(ms(r) for r in records
                          if r["parent_id"] == root["span_id"])
