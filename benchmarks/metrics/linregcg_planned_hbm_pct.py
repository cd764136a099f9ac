"""Optimizer, planner, compile: what the planner reckoned a statement's
peak on the chip to be (``hbm_plan_bytes`` on the window's
``matrel.dispatch`` spans, the largest: the chain's — the resident
table, the vectors and the kernel's lanes of partial sums, no second
table) over the device's ``bytes_limit``: ``planned_hbm_pct.py``'s
reader on this cell's spans, every statement a query root
(linregcg_spans.per_statement). PERF.md sets it beside the measured
``memory_peak_bytes``."""

from benchmarks.metrics import linregcg_spans


def read(run, records=None, bytes_limit=None):
    return linregcg_spans.accepted(run, "planned_hbm_pct").read(
        linregcg_spans.per_statement(run), records, bytes_limit)
