"""A GNMF fit (Lee and Seung's multiplicative updates), all iterations of
one query: what the algorithm needs, from shapes and free of any layout,
so that it reads the same work whatever implements it. An iteration is

    H <- H .* (t(W) V) / (t(W) W H)        W <- W .* (V t(H)) / (W H t(H))

Two sparse products: a multiply-add an entry and a column (2 nnz k
operations), an entry read as two int32 coordinates and a float32 value
(12 B: the layout-free floor; the compact tables the kernel really reads
are 13 B a padded slot and the gathered rows 4 k B a slot more), the
dense side's rows read and the output's written once (4 k B a row).
Four dense products (the two k x k Grams and their applications) and two
element-wise passes (three operands read, one written) from their
shapes. The operations are held to the MXU's rate at ``highest`` (the
executor's three-part split of a float32 row is at least as dear); the
bytes bound it all the same."""


def counts(users, movies, entries, rank, iterations, **said_of_the_plans):
    k = rank
    sparse_flops = 2 * (2 * entries * k)
    sparse_bytes = 2 * (12 * entries + 4 * k * (users + movies))
    dense_flops = 2 * (2 * users * k * k) + 2 * (2 * movies * k * k)
    dense_bytes = 4 * k * (2 * users + 2 * movies) * 2
    elementwise_bytes = 4 * 4 * k * (users + movies)
    return {"flops": iterations * (sparse_flops + dense_flops),
            "bytes": iterations * (sparse_bytes + dense_bytes
                                   + elementwise_bytes),
            "precision": "highest"}
