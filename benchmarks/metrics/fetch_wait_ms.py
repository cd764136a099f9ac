"""Session and executor dispatch: median length of the program's own
``matrel.fetch.wait`` span (``BlockMatrix.to_numpy``: the wait for the
device's answer, ahead of the copy). An earlier line gives the share of
the window's fetches whose answer was there at entry (``ready`` on
``matrel.fetch``): where it is true the wait is a flag read."""

import statistics

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    records = found[0]
    waits = [program_spans.ms(r) for r in records
             if r["name"] == "matrel.fetch.wait"]
    if not waits:
        run.say("program spans: no matrel.fetch.wait in the window")
        return None
    ready = [r["attrs"]["ready"] for r in records
             if r["name"] == "matrel.fetch" and "ready" in r["attrs"]]
    run.say(f"fetch ready_at_entry={sum(ready)} of {len(ready)} "
            f"({100 * sum(ready) / max(len(ready), 1):.1f}%)")
    return statistics.median(waits)
