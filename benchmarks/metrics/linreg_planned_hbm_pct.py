"""Optimizer, planner, compile: what the planner reckoned this one-chip
plan's peak to be (``hbm_plan_bytes`` on the window's ``matrel.dispatch``
spans) over the device's ``bytes_limit``: ``planned_hbm_pct.py``'s reader
on this cell's spans. A program that reckons no one-device plan (a
parent commit) gives None."""

import os


def read(run, records=None, bytes_limit=None):
    reader = run.load_module(os.path.join(run.here, "metrics",
                                          "planned_hbm_pct.py"))
    return reader.read(run, records, bytes_limit)
