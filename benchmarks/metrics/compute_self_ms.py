"""Session and executor dispatch: per query, the program's
``matrel.compute`` span less what its child spans (``matrel.plan``,
``matrel.compile``, ``matrel.dispatch``, ``matrel.rc.probe``) cover —
its self time: precision and retry-policy resolution, the breaker and
fast-path gates; the median."""

import statistics

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    records, roots = found
    selfs = [program_spans.self_ms(r, records) for r in roots
             if r["name"] == "matrel.compute"]
    if not selfs:
        run.say("program spans: no matrel.compute in the window")
        return None
    return statistics.median(selfs)
