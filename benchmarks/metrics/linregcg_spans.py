"""What the LinearRegCG cell's span readers share. A query is 3
statements (``p0``, ``r0``, ``rr``) and 6 a round, each a
``session.compute`` and so a query root of its own in the program's
ring, where ``program_spans.window`` looks for ONE root a traced query:
the run is handed to the accepted readers with each traced query
counted once a statement, so that their window is the whole traced
window and not its first rounds (``wcc_spans.py``'s idiom; the tables
are fixed, so every query takes the same rounds)."""

import types

from benchmarks.metrics.gnmf_spans import accepted  # noqa: F401 (the readers' import)

STATEMENTS_BEFORE = 3
STATEMENTS_A_ROUND = 6


def statements(rounds):
    return STATEMENTS_BEFORE + STATEMENTS_A_ROUND * rounds


def per_statement(run):
    """``run`` with every traced query once a statement; ``run`` itself
    where there is no reduced trace or no round count (the readers then
    say so)."""
    rounds = max((s.get("rounds", 0) for s in run.shapes.values()),
                 default=0)
    if not run.reduced or not run.reduced["queries"] or not rounds:
        return run
    reduced = dict(run.reduced,
                   queries=[q for q in run.reduced["queries"]
                            for _ in range(statements(rounds))])
    return types.SimpleNamespace(**{**vars(run), "reduced": reduced})
