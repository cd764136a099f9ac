"""Session and executor dispatch: the time of the window's
``matrel.gc`` spans (a pause of Python's collector, wherever it fell:
inside a span of the program's or between two queries) over the
window's count of queries: a mean, since the median query holds none.
An earlier line gives the count and the longest pause by generation.
0.0 is a window without a collection; a program that records none
(no ``obs.trace.GC_SPAN``) gives None."""

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    try:
        from matrel_tpu.obs.trace import GC_SPAN
    except ImportError:
        run.say("program spans: the program records no collector pause "
                "(no obs.trace.GC_SPAN)")
        return None
    records, roots = found
    by_generation = {}
    for r in records:
        if r["name"] == "matrel." + GC_SPAN:
            by_generation.setdefault(r["attrs"].get("generation"),
                                     []).append(program_spans.ms(r))
    run.say("gc " + (" ".join(
        f"generation={g} count={len(p)} longest_ms={max(p):.4f}"
        for g, p in sorted(by_generation.items()))
        or "no collection in the window"))
    return sum(map(sum, by_generation.values())) / len(roots)
