"""Optimizer, planner, compile: what the planner reckoned the two update
plans' peak on the chip to be (``hbm_plan_bytes`` on the window's
``matrel.dispatch`` spans: the factors, the intermediates alive, the
compact tables and one panel's gathered rows, the slab and one panel of
its quotient — not ``W * H``, not the quotient whole) over the device's
``bytes_limit``: ``planned_hbm_pct.py``'s reader on this cell's spans,
every update a query root (gnmf_spans.per_update). PERF.md sets it beside
the measured ``memory_peak_bytes``."""

from benchmarks.metrics import gnmf_spans


def read(run, records=None, bytes_limit=None):
    return gnmf_spans.accepted(run, "planned_hbm_pct").read(
        gnmf_spans.per_update(run), records, bytes_limit)
