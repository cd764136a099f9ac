"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), the lowering of
every traced function to an MLIR module (``jit.lower`` records: a Pallas
kernel's Mosaic lowering among it, paid in every process whatever the
compile cache holds), self seconds summed. Read from the program's own ring
on the host's clock (``benchmarks/setup_spans.py``); its five largest
contributors on an earlier line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_jit_lower_s", cold, spans)
