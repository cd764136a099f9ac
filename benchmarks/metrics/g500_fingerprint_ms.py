"""Session and executor dispatch: per PageRank query on the Graph500
graph, the time in the program's ``matrel.pagerank.fingerprint`` spans
(1 GB of edge arrays compared with the prepared plan's kept copies),
summed; the median. ``fingerprint_ms.py``'s reader on this cell's
spans."""

import os


def read(run, records=None):
    reader = run.load_module(os.path.join(run.here, "metrics",
                                          "fingerprint_ms.py"))
    return reader.read(run, records)
