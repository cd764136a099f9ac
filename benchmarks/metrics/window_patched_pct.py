"""View maintenance: the share of traced ticks in which every dependent
view was PATCHED — each of the tick's ``matrel.delta`` spans says
``patched`` at least 1, no view ``killed`` that had a rule (``killed``
less ``no_rule``: the tick's own theta, cached a tick ago and without a
rule, is dropped by design) and none ``rebased``. 100 is the steady
state; a kill-and-recompute program reads 0."""

from benchmarks.metrics import window_spans


def read(run, records=None):
    found = window_spans.named(run, "matrel.delta", records)
    if found is None:
        return None
    mine, n = found
    per = window_spans.DELTAS_A_TICK
    if len(mine) != per * n or any("patched" not in r["attrs"]
                                   for r in mine):
        run.say(f"window_patched_pct: {len(mine)} matrel.delta spans for "
                f"{n} ticks, or none says what it patched")
        return None

    def patched(r):
        a = r["attrs"]
        return (a["patched"] or 0) >= 1 and not a.get("rebased") \
            and (a.get("killed") or 0) - (a.get("no_rule") or 0) <= 0

    good = sum(all(patched(r) for r in mine[i:i + per])
               for i in range(0, len(mine), per))
    return 100.0 * good / n
