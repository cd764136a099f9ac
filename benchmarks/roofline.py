"""A kernel's share of its roofline in a traced run: the least time the
chip could take for the counted work — the larger of operations over the
peak rate at the kernel's precision and bytes over the peak bandwidth —
over the device time the trace shows for the query. The peak rate is the
bf16 peak over the MXU passes a product costs at that precision
(``peaks.json`` ``mxu_passes``): float32 at ``highest`` is six passes, so a
sixth of the bf16 peak. No clamp: a share over 100% means the counts are
too high or the time leaves work out."""

from __future__ import annotations

import os


def share(run, kernel: str, query: str):
    if not run.reduced or not run.reduced["n_device_ops"] or not run.peaks:
        return None
    times = [q["device_s"] for q in run.reduced["queries"]
             if q["template"] == query]
    if not times or run.reduced["chips_traced"] != 1:
        return None
    c = run.load_module(os.path.join(run.here, "counts", kernel + ".py")) \
        .counts(**run.shapes[query])
    passes = run.peaks["mxu_passes"][c["precision"]]
    t_flops = c["flops"] * passes / run.peaks["bf16_flops_per_s"]
    t_bytes = c["bytes"] / run.peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    mean = sum(times) / len(times)
    run.say(f"roofline {kernel} flops={c['flops']} bytes={c['bytes']} "
            f"precision={c['precision']} mxu_passes={passes} "
            f"t_flops_s={t_flops!r} t_bytes_s={t_bytes!r} least_s={least!r} "
            f"bound={'hbm' if t_bytes >= t_flops else 'mxu'} "
            f"device_s_per_query={mean!r}")
    return 100.0 * least / mean
