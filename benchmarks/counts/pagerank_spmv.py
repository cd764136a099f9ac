"""PageRank over an edge list, all rounds of one query: what the
algorithm needs, from shapes. A round reads both int32 endpoints of every
edge (8 B an edge: the layout-free floor; the compact tables the kernel
really reads are ~13 B a padded slot), reads the rank vector and the
inverse out-degrees and writes the new ranks (12 B a node), and does one
multiply-add an edge. The operations are held to the MXU's rate at
``highest`` (the executor's three-part split of a float32 weight is at
least as dear); bytes bound it by a factor of 180 all the same."""


def counts(nodes, edges, rounds):
    return {"flops": rounds * 2 * edges,
            "bytes": rounds * (8 * edges + 12 * nodes),
            "precision": "highest"}
