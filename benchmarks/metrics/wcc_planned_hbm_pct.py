"""Optimizer, planner, compile: what the planner reckoned the round's
peak on the chip to be (``hbm_plan_bytes`` on the window's
``matrel.dispatch`` spans: the labels, the compact plan's tables and slot
weights and one panel's gathered rows — not the (n x n) join) over the
device's ``bytes_limit``: ``planned_hbm_pct.py``'s reader on this cell's
spans, every compute a query root (wcc_spans.per_compute). PERF.md sets
it beside the measured ``memory_peak_bytes``."""

from benchmarks.metrics import wcc_spans


def read(run, records=None, bytes_limit=None):
    return wcc_spans.accepted(run, "planned_hbm_pct").read(
        wcc_spans.per_compute(run), records, bytes_limit)
