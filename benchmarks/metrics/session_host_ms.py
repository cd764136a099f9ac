"""Session and executor dispatch: per query, the benchmark's span around
the whole call minus the time the device was busy inside that span;
the median over the traced queries."""

import statistics


def read(run):
    if not run.reduced or not run.reduced["queries"]:
        return None
    return statistics.median(
        q["span_s"] - q["device_s"] for q in run.reduced["queries"]) * 1e3
