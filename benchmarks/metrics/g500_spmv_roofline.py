"""Kernels: the 10-round PageRank program's share of its roofline on the
Graph500 graph (counts/pagerank_spmv.py, 8 B an edge and 12 B a node a
round, over the query's device time; no clamp)."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="pagerank_spmv", query="pagerank_g500")
