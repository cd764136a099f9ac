"""Optimizer, planner, compile: how many times the program compiled or
built a plan inside the traced window — its ``matrel.compile`` spans
(opened on a plan-cache miss only) and its ``matrel.pagerank.plan``
spans with ``hit`` false. Set-up warms every query, so 0 is expected."""

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    return sum(1 for r in found[0]
               if r["name"] == "matrel.compile"
               or (r["name"] == "matrel.pagerank.plan"
                   and r["attrs"].get("hit") is False))
