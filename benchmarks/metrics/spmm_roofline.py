"""Kernels: the block-sparse x dense product's share of its roofline
(counts/spmm.py over the query's device time)."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="spmm", query="spmm_sd")
