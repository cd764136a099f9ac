"""View maintenance: the time a tick spends in ``session.register_delta``
— its two ``matrel.delta`` entry spans (upload, the in-place update, the
views' patches, the cache's re-keying), the median over the traced
ticks, on the host's clock. What moves when a patch is re-used or not,
when a view re-bases, or when the plane's bookkeeping grows."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    return window_spans.median_a_tick(run, "matrel.delta", records)
