"""Session and executor dispatch: median length of the program's own
``matrel.fetch`` span (``BlockMatrix.to_numpy``: the read-back)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "matrel.fetch")
