"""Optimizer, planner, compile: over a run's set-up (the program's cold records
that start before the traced window's first query root), how many functions
the set-up compiled and WROTE to the persistent cache (``jit.cache`` records
with ``hit`` false): 0 in every run after a checkout's first, and a reading
above 0 says this run's ``setup_s`` was a cold one. Read from the program's
own ring on the host's clock (``benchmarks/setup_spans.py``); its five
largest contributors on an earlier line."""

from benchmarks import setup_spans


def read(run, cold=None, spans=None):
    return setup_spans.read(run, "setup_cache_misses", cold, spans)
