"""What the WCC cell's span readers share. A query is ``rounds`` rounds
of two ``session.compute`` calls (the round, then ``count(Lnew - L)``),
each a query root of its own in the program's ring, where
``program_spans.window`` looks for ONE root a traced query: the run is
handed to the accepted readers with each traced query counted once a
compute, so that their window is the whole traced window and not its
first rounds (``gnmf_spans.py``'s idiom; the graph is fixed, so every
query takes the same rounds)."""

import types

from benchmarks.metrics.gnmf_spans import accepted  # noqa: F401 (the readers' import)

COMPUTES_A_ROUND = 2


def per_compute(run):
    """``run`` with every traced query once a compute; ``run`` itself
    where there is no reduced trace or no round count (the readers then
    say so)."""
    rounds = max((s.get("rounds", 0) for s in run.shapes.values()),
                 default=0)
    if not run.reduced or not run.reduced["queries"] or not rounds:
        return run
    reduced = dict(run.reduced,
                   queries=[q for q in run.reduced["queries"]
                            for _ in range(COMPUTES_A_ROUND * rounds)])
    return types.SimpleNamespace(**{**vars(run), "reduced": reduced})

