"""Optimizer, planner, compile: how far the prepared plan's layout pads
the graph — 100 * (``slots`` / ``edges`` - 1) of the window's
``matrel.pagerank.plan`` spans, which carry both on a hit as on a
build; the largest of the window. A program whose spans carry neither
(a parent commit) gives None."""

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    pads = [100.0 * (r["attrs"]["slots"] / r["attrs"]["edges"] - 1.0)
            for r in found[0] if r["name"] == "matrel.pagerank.plan"
            and r["attrs"].get("slots") and r["attrs"].get("edges")]
    if not pads:
        run.say("g500_slot_padding_pct: no matrel.pagerank.plan span of "
                "the window carries slots and edges")
        return None
    return max(pads)
