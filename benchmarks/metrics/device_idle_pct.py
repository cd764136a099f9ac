"""Device: the share of the traced window in which no operation ran."""


def read(run):
    r = run.reduced
    if not r or not r["n_device_ops"] or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
