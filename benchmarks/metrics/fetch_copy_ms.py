"""Session and executor dispatch: median length of the program's own
``matrel.fetch.copy`` span (``BlockMatrix.to_numpy``: the copy of an
answer that is ready to the host, ``np.asarray(jax.device_get(...))``)."""

from benchmarks import program_spans


def read(run):
    return program_spans.median_ms(run, "matrel.fetch.copy")
