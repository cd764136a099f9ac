"""What the sliding-window cell's span readers share. A tick is two
``session.register_delta`` calls (entry spans ``matrel.delta``, with
``matrel.delta.upload`` / ``.update`` / ``.patch`` / ``.rebase`` inside
them) and two statements, each a ``session.compute`` and so a query
root of its own in the program's ring, where ``program_spans.window``
looks for ONE root a traced query: the run is handed to it with each
traced tick counted once a statement (``gnmf_spans.py``'s idiom). A
program without these spans (a parent commit) gives None."""

import types

from benchmarks import program_spans

STATEMENTS_A_TICK = 2
DELTAS_A_TICK = 2


def per_statement(run):
    """``run`` with every traced tick once a statement; ``run`` itself
    where there is no reduced trace (the readers then say so)."""
    if not run.reduced or not run.reduced["queries"]:
        return run
    reduced = dict(run.reduced,
                   queries=[q for q in run.reduced["queries"]
                            for _ in range(STATEMENTS_A_TICK)])
    return types.SimpleNamespace(**{**vars(run), "reduced": reduced})


def ticks(run, records=None):
    """(the window's records, its traced ticks), or None."""
    found = program_spans.window(per_statement(run), records)
    if found is None:
        return None
    return found[0], len(run.reduced["queries"])


def named(run, name, records=None, say=True):
    """(spans of one name in the window, ticks), or None where the
    window holds none (a program without the span)."""
    found = ticks(run, records)
    if found is None:
        return None
    mine = [r for r in found[0] if r["name"] == name]
    if not mine:
        if say:
            run.say(f"window spans: no {name} in the window")
        return None
    return mine, found[1]


def median_a_tick(run, name, records=None):
    """The median over the window's ticks of the time its spans of one
    name took together (two a tick, in the order they ran: a tick's are
    neighbours); where the count is no whole number a tick, their sum
    over the ticks."""
    import statistics
    found = named(run, name, records)
    if found is None:
        return None
    mine, n = found
    lengths = [program_spans.ms(r) for r in mine]
    if len(lengths) != DELTAS_A_TICK * n:
        return sum(lengths) / n
    return statistics.median(
        sum(lengths[i:i + DELTAS_A_TICK])
        for i in range(0, len(lengths), DELTAS_A_TICK))
