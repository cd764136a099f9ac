"""Strategies and collectives: per query, the device time of the whole
regression's all-reduce (the four partial Grams and right-hand sides,
2.5 MB a chip), mean over the chips: ``collective_wait_ms.py``'s reader
on this cell's trace. Its earlier line says whether the all-reduce was
among the ten longest operations it can see; where it was not, this
reads 0 and the line says "none"."""

import os


def read(run):
    reader = run.load_module(os.path.join(run.here, "metrics",
                                          "collective_wait_ms.py"))
    return reader.read(run)
