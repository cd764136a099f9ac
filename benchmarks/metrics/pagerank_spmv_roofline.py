"""Kernels: the 30-round PageRank program's share of its roofline
(counts/pagerank_spmv.py over the query's device time)."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="pagerank_spmv", query="pagerank_30")
