"""View maintenance: passes over the table a traced tick, outside a
re-base — a ``matrel.delta.patch`` span that says ``table_pass``, or a
statement whose result-cache consult (``matrel.rc.probe``) left a
catalog table among the leaves of what was dispatched. 0 in a steady
tick: the writes touch c rows, theta is a solve over two cached views,
``t(X) * y`` is the cached view."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    found = window_spans.ticks(run, records)
    if found is None or window_spans.named(
            run, "matrel.delta", records, say=False) is None:
        return None
    window, n = found
    passes = sum(1 for r in window
                 if r["name"] in ("matrel.delta.patch", "matrel.rc.probe")
                 and r["attrs"].get("table_pass"))
    return passes / n
