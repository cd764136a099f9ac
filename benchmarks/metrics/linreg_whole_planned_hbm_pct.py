"""Optimizer, planner, compile: what the planner reckoned the mesh
plan's peak on ONE device to be (``hbm_plan_bytes`` on the window's
``matrel.dispatch`` spans: the device's rows of X and y, the Gram's
accumulators and the all-reduce's result, the solve's copies) over the
device's ``bytes_limit``: ``planned_hbm_pct.py``'s reader on this cell's
spans. PERF.md sets it beside the measured ``memory_peak_bytes``."""

import os


def read(run, records=None, bytes_limit=None):
    reader = run.load_module(os.path.join(run.here, "metrics",
                                          "planned_hbm_pct.py"))
    return reader.read(run, records, bytes_limit)
