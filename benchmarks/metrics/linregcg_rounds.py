"""Optimizer, planner: the rounds a query took — the chains
(``matrel.mmchain.plan`` spans, one a round at its dispatch) of the
traced window over its queries. The data's own number, not a
parameter: the loop is the client's and stops by SystemML's rule, and
every round it saves or adds moves the query by a round's time. A
program whose spans carry none (a parent commit) gives None."""

from benchmarks import program_spans
from benchmarks.metrics import linregcg_spans


def read(run, records=None):
    found = program_spans.window(linregcg_spans.per_statement(run), records)
    if found is None:
        return None
    chains = sum(1 for r in found[0]
                 if r["name"] == "matrel.mmchain.plan"
                 and r["attrs"].get("hit"))
    if not chains:
        run.say("linregcg_rounds: no matrel.mmchain.plan span in the window")
        return None
    return chains / len(run.reduced["queries"])
