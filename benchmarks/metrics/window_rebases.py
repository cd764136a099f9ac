"""View maintenance: how many times a view was RE-BASED in the traced
window — recomputed from the table because its composed error bound
would have passed ``ROWS_REBASE_BOUND`` (``matrel.delta.rebase``
spans). Each is a pass over the table inside a tick; the configuration
holds them under 1 tick in 100."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    found = window_spans.ticks(run, records)
    if found is None or window_spans.named(
            run, "matrel.delta", records, say=False) is None:
        return None
    return sum(1 for r in found[0] if r["name"] == "matrel.delta.rebase")
