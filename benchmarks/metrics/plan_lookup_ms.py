"""Session and executor dispatch: median length of the program's own
``matrel.plan`` span (``session._compile_entry`` up to the plan cache's
answer: key walk, prefixes, the probe under the lock)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "matrel.plan")
