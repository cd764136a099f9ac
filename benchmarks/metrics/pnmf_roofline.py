"""Kernels: the Poisson NMF fit's share of its roofline (counts/pnmf.py:
the layout-free floor, 12 B and 4 k operations an entry an update, over
the query's device time; no clamp). Small by construction, as the GNMF
cell's is: the residual's rows pass through HBM three times an update,
and a float32 quotient costs the MXU six passes."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="pnmf", query="pnmf_fit")
