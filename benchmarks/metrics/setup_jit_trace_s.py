"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), jax's trace of
every function the set-up jitted (``jit.trace`` records: jaxpr tracing, the
lowered plan's and the generator's alike), self seconds summed. Read from
the program's own ring on the host's clock (``benchmarks/setup_spans.py``);
its five largest contributors on an earlier line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_jit_trace_s", cold, spans)
