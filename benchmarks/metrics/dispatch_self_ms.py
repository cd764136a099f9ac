"""Session and executor dispatch: per ``matrel.dispatch`` span, its
length less what its children (``matrel.dispatch.launch``, a
``matrel.gc``) cover: the leaf walk, the result's wrapper and its spec,
the fleet's lock: the program's own share of ``dispatch_ms``; the
median. A program without ``matrel.dispatch.launch`` gives None, not
the whole of ``dispatch``."""

import statistics

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    records = found[0]
    if not any(r["name"] == "matrel.dispatch.launch" for r in records):
        run.say("program spans: no matrel.dispatch.launch in the window")
        return None
    return statistics.median(
        program_spans.self_ms(r, records) for r in records
        if r["name"] == "matrel.dispatch")
