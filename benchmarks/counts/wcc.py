"""Weakly connected components by synchronous label propagation over an
edge list, all rounds of one query: what the algorithm needs, from the
graph's shapes alone and free of any layout, so that it reads the same
work whatever implements it. A round reads both int32 endpoints of every
directed edge (8 B an edge: the layout-free floor; the compact tables the
kernel really reads are ~13 B a padded slot), reads the labels and
writes the new ones (8 B a vertex), and does one compare an edge. The
operations are held to the MXU's rate at ``highest`` as the other graph
cell's are (a compare on the vector unit is at least as dear); the
bytes bound it all the same."""


def counts(nodes, edges, rounds):
    return {"flops": rounds * edges,
            "bytes": rounds * (8 * edges + 8 * nodes),
            "precision": "highest"}
