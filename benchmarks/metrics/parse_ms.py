"""Entry layer: median length of the benchmark's own span around
``session.sql(q)``, from the profiler's host events."""

import statistics


def read(run):
    spans = run.reduced and run.reduced["spans"].get("bench.parse")
    return statistics.median(spans) * 1e3 if spans else None
