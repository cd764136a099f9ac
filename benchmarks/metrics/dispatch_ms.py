"""Session and executor dispatch: median length of the program's own
``matrel.dispatch`` span (``plan.run``: leaf gather, the jitted call
until it returns, the result's wrapper)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "matrel.dispatch")
