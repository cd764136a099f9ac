"""Optimizer, planner, compile: ``compiles_in_window.py``'s reader on the
WCC cell's spans, every compute a query root (wcc_spans.per_compute):
the ``matrel.compile`` spans of the traced window. The labels are a new
array every round, so 0 says that the plan templates answered every
round after the first query's first."""

from benchmarks.metrics import wcc_spans


def read(run, records=None):
    return wcc_spans.accepted(run, "compiles_in_window").read(
        wcc_spans.per_compute(run), records)
