"""Optimizer, planner, compile: how far the two SpMV plans' layouts pad
the ratings — 100 * (slots / entries - 1) over both orientations, from the
``matrel.spmm.plan`` spans of the traced window (one a coo_leaf product at
every dispatch; the newest of each orientation). A program whose spans
carry neither (a parent commit) gives None."""

from benchmarks import program_spans
from benchmarks.metrics import gnmf_spans


def read(run, records=None):
    found = program_spans.window(gnmf_spans.per_update(run), records)
    if found is None:
        return None
    newest = {r["attrs"].get("orientation"): r["attrs"] for r in found[0]
              if r["name"] == "matrel.spmm.plan"
              and r["attrs"].get("slots") and r["attrs"].get("entries")}
    if not newest:
        run.say("gnmf_slot_padding_pct: no matrel.spmm.plan span of the "
                "window carries slots and entries")
        return None
    return 100.0 * (sum(a["slots"] for a in newest.values())
                    / sum(a["entries"] for a in newest.values()) - 1.0)
