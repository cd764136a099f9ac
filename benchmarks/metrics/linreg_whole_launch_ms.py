"""Session and executor dispatch: median length of the program's
``matrel.dispatch.launch`` span in this cell: what handing one program
to four devices costs (``dispatch_launch_ms.py``'s reader; the one-chip
cell reads 0.25 ms, the chain's four-device launch 0.65 to 0.75)."""

import os


def read(run):
    reader = run.load_module(os.path.join(run.here, "metrics",
                                          "dispatch_launch_ms.py"))
    return reader.read(run)
