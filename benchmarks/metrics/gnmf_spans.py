"""What the GNMF cell's span readers share. A fit is ``2 * iterations``
updates, each a ``session.compute`` and so a query root of its own in the
program's ring, where ``program_spans.window`` looks for ONE root a traced
query: the run is handed to the accepted readers with each traced query
counted once an update, so that their window is the whole traced window
and not its first updates."""

import os
import types


def per_update(run):
    """``run`` with every traced query once an update; ``run`` itself
    where there is no reduced trace (the readers then say so)."""
    if not run.reduced or not run.reduced["queries"]:
        return run
    updates = 2 * max(s["iterations"] for s in run.shapes.values())
    reduced = dict(run.reduced,
                   queries=[q for q in run.reduced["queries"]
                            for _ in range(updates)])
    return types.SimpleNamespace(**{**vars(run), "reduced": reduced})


def accepted(run, metric):
    """The accepted reader ``benchmarks/metrics/<metric>.py``."""
    return run.load_module(os.path.join(run.here, "metrics", metric + ".py"))
