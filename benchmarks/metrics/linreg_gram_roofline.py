"""Kernels: the normal-equations regression's share of its roofline
(counts/linreg.py, the symmetric count, over the query's device time)."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="linreg", query="theta")
