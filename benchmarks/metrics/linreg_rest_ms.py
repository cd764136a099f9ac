"""Kernels: per query, the device's busy time less that of the window's
longest operation (the Gram's dot): what is left is ``t(X) * y``'s pass
over X, the factorisation and the substitutions of the solve, and
copies. The longest operation is the first of the reduced trace's
``device_ops`` (exclusive time, summed over the window); the line names
it, so a window whose longest operation is not the dot shows."""


def read(run):
    r = run.reduced
    if not r or not r["n_device_ops"] or not r["queries"] \
            or not r["device_ops"]:
        return None
    n = len(r["queries"])
    busy = sum(q["device_s"] for q in r["queries"]) / n
    name, total = r["device_ops"][0]
    run.say(f"linreg longest device operation {name!r} "
            f"s_per_query={total / n!r} busy_s_per_query={busy!r}")
    return (busy - total / n) * 1e3
