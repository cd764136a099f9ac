"""Kernels: the time in which a device operation ran inside a query's
span (union of the operations' intervals, so nested ones count once),
as the mean over the traced queries."""


def read(run):
    qs = run.reduced["queries"] if run.reduced else []
    if not qs or not run.reduced["n_device_ops"]:
        return None
    return sum(q["device_s"] for q in qs) / len(qs) * 1e3
