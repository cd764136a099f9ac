"""Optimizer, planner, compile: the share of the ratings whose quotient
the MXU makes a panel at a time — ``dense_entries`` over ``entries`` of
the ``matrel.sampled.plan`` spans of the traced window (one a sampled
product at every dispatch; the newest of each orientation): the dense
lines' share of the sampled products, the rest gathered by the entry. A
program whose spans carry neither (a parent commit) gives None."""

from benchmarks import program_spans
from benchmarks.metrics import gnmf_spans


def read(run, records=None):
    found = program_spans.window(gnmf_spans.per_update(run), records)
    if found is None:
        return None
    newest = {r["attrs"].get("orientation"): r["attrs"] for r in found[0]
              if r["name"] == "matrel.sampled.plan"
              and r["attrs"].get("entries")}
    if not newest:
        run.say("pnmf_mxu_entries_pct: no matrel.sampled.plan span of the "
                "window carries entries")
        return None
    return 100.0 * (sum(a.get("dense_entries", 0) for a in newest.values())
                    / sum(a["entries"] for a in newest.values()))
