"""Kernels: the LinearRegCG query's share of its roofline
(counts/linregcg.py: the layout-free floor, X read once a chain and
once for ``t(X) * y``, over the query's device time; no clamp). HBM
bound: at 2,555,904 x 1000 and 4 rounds the floor is 51 GB, 62.4 ms at
the peak bandwidth. A plan that reads X twice a chain can reach 5 / 9
of it."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="linregcg", query="beta_cg")
