"""Kernels: the WCC query's share of its roofline (counts/wcc.py: the
layout-free floor, 8 B an edge and 8 B a vertex a round, over the
query's device time; no clamp). Small by construction, as the PageRank
cell's on this graph is: a round gathers a label a slot through XLA's
row engine and walks 13 B a padded slot of tables."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="wcc", query="wcc_g500")
