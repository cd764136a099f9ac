"""Kernels: the whole regression's share of its roofline over all the
chips the trace shows: ``chain_matmul_roofline.py``'s reckoning
(``roofline.share`` answers for one chip alone) on counts/linreg.py's
count at the whole table's rows. The least time is the larger of
operations over (chips x the bf16 peak over the six MXU passes of
``highest``) and bytes over (chips x the peak bandwidth); the time is the
query's device time, which the reduced trace gives as the mean over the
chips' planes. The count is the algorithm's (the symmetric Gram), so a
block triangle of 10 of 16 blocks reads about where ``linreg_gram_roofline``
does on one chip, and nothing can read over 100. No clamp."""

import os

QUERY = "theta"


def read(run):
    r = run.reduced
    if not r or not r["n_device_ops"] or not run.peaks:
        return None
    times = [q["device_s"] for q in r["queries"] if q["template"] == QUERY]
    chips = r["chips_traced"]
    if not times or not chips or QUERY not in run.shapes:
        return None
    c = run.load_module(os.path.join(run.here, "counts", "linreg.py")) \
        .counts(**run.shapes[QUERY])
    passes = run.peaks["mxu_passes"][c["precision"]]
    t_flops = c["flops"] * passes / (chips * run.peaks["bf16_flops_per_s"])
    t_bytes = c["bytes"] / (chips * run.peaks["hbm_bytes_per_s"])
    least = max(t_flops, t_bytes)
    mean = sum(times) / len(times)
    run.say(f"roofline linreg whole chips={chips} flops={c['flops']} "
            f"bytes={c['bytes']} precision={c['precision']} "
            f"mxu_passes={passes} t_flops_s={t_flops!r} "
            f"t_bytes_s={t_bytes!r} least_s={least!r} "
            f"bound={'hbm' if t_bytes >= t_flops else 'mxu'} "
            f"device_s_per_query={mean!r}")
    return 100.0 * least / mean
