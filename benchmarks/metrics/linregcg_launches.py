"""Session and executor dispatch: the programs a query launches — the
``matrel.dispatch.launch`` spans (``plan.run``: one jitted call each)
of the traced window over its queries: 3 before the loop and 6 a round
as the deployment groups LinearRegCG's lines into statements, two of
them a round (and one before) the long passes over X and the others
tiny and dependent. What an iterative query planned as ONE program
(ROADMAP M1) would bring to 1. A program without the ring (a parent
commit) gives None."""

from benchmarks import program_spans
from benchmarks.metrics import linregcg_spans


def read(run, records=None):
    found = program_spans.window(linregcg_spans.per_statement(run), records)
    if found is None:
        return None
    launches = sum(1 for r in found[0]
                   if r["name"] == "matrel.dispatch.launch")
    if not launches:
        run.say("linregcg_launches: no matrel.dispatch.launch span in the "
                "window")
        return None
    return launches / len(run.reduced["queries"])
