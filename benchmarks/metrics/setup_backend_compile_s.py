"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), the backend's
compile of every lowered function (``jit.backend`` records: XLA's compile on
a persistent-cache miss, the cache's load on a hit), self seconds summed.
Read from the program's own ring on the host's clock
(``benchmarks/setup_spans.py``); its five largest contributors on an earlier
line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_backend_compile_s", cold, spans)
