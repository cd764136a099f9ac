"""Optimizer, planner, compile: ``compiles_in_window.py``'s reader on
the LinearRegCG cell's spans, every statement a query root
(linregcg_spans.per_statement): the ``matrel.compile`` spans of the
traced window. ``p``, ``r``, ``beta`` and the scalars are new arrays
every round, so 0 says that the plan templates answered every statement
after the first query's."""

from benchmarks.metrics import linregcg_spans


def read(run, records=None):
    return linregcg_spans.accepted(run, "compiles_in_window").read(
        linregcg_spans.per_statement(run), records)
