"""Optimizer, planner, compile: what compiled inside the traced window
— its ``matrel.compile`` spans (a statement's plan-cache miss) and its
``matrel.delta.patch`` spans that did not re-use their view's compiled
patch. The table, the views and the batch's shape are the same objects
and shapes every tick, so 0 says that a steady tick compiles nothing."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    found = window_spans.ticks(run, records)
    if found is None or window_spans.named(
            run, "matrel.delta", records, say=False) is None:
        return None
    return sum(1 for r in found[0]
               if r["name"] == "matrel.compile"
               or (r["name"] == "matrel.delta.patch"
                   and r["attrs"].get("reused") is False))
