"""Kernels: a tick's maintenance as a share of its roofline
(counts/window.py: the two Grams of a batch's rows and their right-hand
sides at six passes, the batch in and the old rows out, a re-base
charged a whole Gram by the share of ticks that re-based) over the
tick's WHOLE device time, no clamp. The tick's device time also holds
what the floor leaves out — the read-out and overwrite of the rows, the
1000^2 factorisation that reads theta back (0.94 ms, ledger PR 55
``linreg_rest_ms``) — so the share is the patches' at most, and cannot
pass 100."""

from benchmarks import roofline


def read(run):
    return roofline.share(run, kernel="window", query="tick")
