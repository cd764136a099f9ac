"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), the program's own
host builds (``plan.optimize``, ``plan.verify``, ``plan.trace``,
``spmm.plan.build``, ``pagerank.plan.build``, ``coo.from_edges``,
``coo.entry_view``, ``compile``'s own remainder), self seconds summed. Read
from the program's own ring on the host's clock
(``benchmarks/setup_spans.py``); its five largest contributors on an earlier
line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_plan_build_s", cold, spans)
