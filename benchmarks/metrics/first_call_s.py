"""Optimizer, planner and compile (or compile-cache load): over the
cell's queries, the first call minus the warm median, summed. Taken by
the harness during set-up, on the host's clock."""


def read(run):
    if not run.first_calls:
        return None
    return sum(max(first - warm, 0.0)
               for first, warm in run.first_calls.values())
