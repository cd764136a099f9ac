"""Optimizer, planner: the rounds a query took — the semiring products
(``matrel.semiring.plan`` spans, one a round at its dispatch) of the
traced window over its queries. The graph's own number, not a
parameter: the loop is the client's, and every round it saves or adds
moves the query by a round's time. A program whose spans carry none (a
parent commit) gives None."""

from benchmarks import program_spans
from benchmarks.metrics import wcc_spans


def read(run, records=None):
    found = program_spans.window(wcc_spans.per_compute(run), records)
    if found is None:
        return None
    products = sum(1 for r in found[0]
                   if r["name"] == "matrel.semiring.plan"
                   and r["attrs"].get("hit"))
    if not products:
        run.say("wcc_rounds: no matrel.semiring.plan span in the window")
        return None
    return products / len(run.reduced["queries"])
