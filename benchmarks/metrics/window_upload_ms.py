"""View maintenance: the time a tick spends handing its batch to the
device — its two ``matrel.delta.upload`` spans (32.8 MB of rows, 32 KB
of responses; ``device_put`` returns when the copy is queued, so this
is the host's share of the ingest), the median over the traced ticks."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    return window_spans.median_a_tick(run, "matrel.delta.upload", records)
