"""Optimizer, planner, compile: ``compiles_in_window.py``'s reader on the
Poisson NMF cell's spans, every update a query root
(gnmf_spans.per_update): the ``matrel.compile`` spans of the traced
window. W and H are new arrays every update, so 0 says that the plan
templates answered every update after the first fit."""

from benchmarks.metrics import gnmf_spans


def read(run, records=None):
    return gnmf_spans.accepted(run, "compiles_in_window").read(
        gnmf_spans.per_update(run), records)
