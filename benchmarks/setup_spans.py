"""What the six set-up readers under ``benchmarks/metrics/`` share
(``setup_jit_trace_s``, ``setup_jit_lower_s``, ``setup_backend_compile_s``,
``setup_cache_misses``, ``setup_plan_build_s``, ``setup_upload_s``): the
program's COLD records of a run's set-up, split by what they timed.

The program keeps a record of every site that runs only where a plan is
made, and of every function jax traces, lowers, compiles or loads from
its persistent cache, whether or not anything traces
(``matrel_tpu.obs.trace.cold_spans()``: ``{name, start_ns, end_ns,
span_id, parent_id, qid, tid, attrs}`` on ``time.time_ns()``, bare
names). Set-up's records are those that START before the traced
window's first query root (``program_spans.window``: the profiler
tier's ring is on the same clock); those inside the window are what
``compiles_in_window`` counts, those after it the ``device_op`` check's
and the reference's.

Every metric is a SELF time: a record's length less what the cold
records it contains on its thread cover (containment by time, since
jax tells of a trace only when it is over and so names no parent
among its own events). The six then add up to the length of the
outermost records, and a compile inside the slab's fill is counted
once, as a compile. A record goes by its name:

    jit.trace / jit.lower / jit.backend   the three ``setup_jit_*`` /
                                          ``setup_backend_compile_s``
    jit.cache (zero length)               ``setup_cache_misses`` counts
                                          those with ``hit`` false
    spmm.plan.upload, pagerank.plan.upload, coo.slab.fill, and
    spmm.plan / sampled.plan / semiring.plan (``hit`` false)
                                          ``setup_upload_s``
    every other name of the program's (plan.optimize, plan.verify,
    plan.trace, spmm.plan.build, pagerank.plan.build, coo.from_edges,
    coo.entry_view, compile's own remainder, and whatever span ended
    beneath one of them)                  ``setup_plan_build_s``

What the inside does not cover (imports, ``jax.devices()``, the cell's
generator on the host, the warm calls after each first one) is the
difference between the one line :func:`split` prints a run (the six's
sum, the time from the first record to the window's first root,
``first_call_s``) and ``setup_s``. A program without the ring (a parent
commit), or a run whose window is not found, gives None with a line
saying which: the metric is then left out of the result.
"""

import json

from benchmarks import program_spans

UPLOADS = {"spmm.plan.upload", "pagerank.plan.upload", "coo.slab.fill",
           "spmm.plan", "sampled.plan", "semiring.plan"}
JIT = {"jit.trace": "setup_jit_trace_s", "jit.lower": "setup_jit_lower_s",
       "jit.backend": "setup_backend_compile_s"}
SECONDS = tuple(JIT.values()) + ("setup_plan_build_s", "setup_upload_s")
MISSES = "setup_cache_misses"


def ring():
    """(the program's cold records, oldest start first; the ring's
    capacity), or None where the program has no such ring."""
    try:
        from matrel_tpu.obs.trace import COLD_RING_CAPACITY, cold_spans
    except ImportError:
        return None
    return (sorted(cold_spans(), key=lambda r: r["start_ns"]),
            COLD_RING_CAPACITY)


def metric_of(record) -> str:
    """The metric a record's self time counts in (``jit.cache``: the
    count's; it has no length)."""
    name = record["name"]
    if name == "jit.cache":
        return MISSES
    return JIT.get(name) or ("setup_upload_s" if name in UPLOADS
                             else "setup_plan_build_s")


def self_seconds(records) -> dict:
    """``span_id`` -> self seconds: a record's length less the part of
    it that the records it contains on its thread cover (those lie one
    after the other; a record that starts inside another and ends
    after it, a clock's rounding, counts up to the outer's end)."""
    out = {}
    by_thread = {}
    for r in records:
        by_thread.setdefault(r["tid"], []).append(r)
    for mine in by_thread.values():
        mine.sort(key=lambda r: (r["start_ns"], -r["end_ns"]))
        open_ = []      # the records that contain the next, outermost first
        for r in mine:
            while open_ and open_[-1]["end_ns"] <= r["start_ns"]:
                open_.pop()
            out[r["span_id"]] = (r["end_ns"] - r["start_ns"]) * 1e-9
            if open_:
                outer = open_[-1]
                out[outer["span_id"]] -= (
                    min(r["end_ns"], outer["end_ns"]) - r["start_ns"]) * 1e-9
            open_.append(r)
    return out


def split(run, cold=None, spans=None):
    """``{metric: value, "by": {metric: {label: seconds}}}`` of the
    run's set-up, or None; reckoned once a run and said once, on one
    line. ``cold`` and ``spans`` stand in for the two rings in
    tests."""
    kept = getattr(run, "_setup_split", False)
    if kept is not False:
        return kept
    run._setup_split = found = _split(run, cold, spans)
    return found


def _split(run, cold, spans):
    full = False
    if cold is None:
        found = ring()
        if found is None:
            run.say("setup spans: the program records none "
                    "(no obs.trace.cold_spans)")
            return None
        cold, capacity = found
        full = len(cold) >= capacity
    found = program_spans.window(run, spans)
    if found is None:
        return None
    first_root = found[1][0]["start_ns"]
    mine = [r for r in cold if r["start_ns"] < first_root]
    if not mine:
        run.say("setup spans: no cold record before the window's first "
                "query root")
        return None
    selfs = self_seconds(mine)
    values = dict.fromkeys(SECONDS, 0.0)
    values[MISSES] = 0
    by = {m: {} for m in values}
    hits = 0
    for r in mine:
        metric, attrs = metric_of(r), r["attrs"]
        if metric == MISSES:
            if attrs.get("hit") is False:
                values[MISSES] += 1
                label = str(attrs.get("fun_name"))
                by[MISSES][label] = by[MISSES].get(label, 0) + 1
            else:
                hits += 1
            continue
        s = selfs[r["span_id"]]
        values[metric] += s
        if r["name"] in JIT:        # by function; the program's by record
            label = str(attrs.get("fun_name"))
        else:
            label = (f"{r['name']}#{r['span_id']} "
                     + json.dumps(attrs, default=str)[:160])
        by[metric][label] = by[metric].get(label, 0.0) + s
    first_call_s = sum(max(first - warm, 0.0) for first, warm
                       in (getattr(run, "first_calls", None) or {}).values())
    run.say(f"setup inside sum_s={sum(values[m] for m in SECONDS):.3f} "
            f"covers_s={(first_root - mine[0]['start_ns']) * 1e-9:.3f} "
            f"(first cold record to the window's first query root) "
            f"first_call_s={first_call_s:.3f} records={len(mine)} "
            f"cache_hits={hits} cache_misses={values[MISSES]}"
            + (" RING FULL: the oldest records fell out, the sums are "
               "short" if full else ""))
    values["by"] = by
    return values


def read(run, metric, cold=None, spans=None):
    """One metric of the split, its five largest contributors on an
    earlier line (jax's by ``fun_name``, the program's by record: its
    name, id and attributes)."""
    found = split(run, cold, spans)
    if found is None:
        return None
    top = sorted(found["by"][metric].items(), key=lambda kv: -kv[1])[:5]
    unit = "" if metric == MISSES else " s"
    run.say(f"{metric} largest: " + ("; ".join(
        f"{label} {v if metric == MISSES else format(v, '.3f')}{unit}"
        for label, v in top) or "none"))
    return found[metric]
