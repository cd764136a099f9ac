"""Kernels: the GNMF fit's share of its roofline (counts/gnmf.py: the
layout-free floor, 12 B an entry and 2 k operations an entry a sparse
product, over the query's device time; no clamp). Small by construction,
as the PageRank cells' are: the one-hot scatter spends 512 MXU rows an
entry's row, and the gathered rows pass through HBM."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="gnmf", query="gnmf_fit")
