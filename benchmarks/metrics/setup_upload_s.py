"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), the plans' way to
the device (``spmm.plan.upload``, ``pagerank.plan.upload``,
``coo.slab.fill``, and the ``spmm.plan`` / ``sampled.plan`` /
``semiring.plan`` records with ``hit`` false: a product's lowering around
its upload), self seconds summed. Read from the program's own ring on the
host's clock (``benchmarks/setup_spans.py``); its five largest contributors
on an earlier line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_upload_s", cold, spans)
