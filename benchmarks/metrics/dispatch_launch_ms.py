"""Session and executor dispatch: median length of the program's own
``matrel.dispatch.launch`` span (``plan.run``: the jitted call until it
returns and nothing else: jax's and the runtime's share of
``dispatch_ms``)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "matrel.dispatch.launch")
