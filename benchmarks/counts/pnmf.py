"""A Poisson (KL-divergence) NMF fit (Lee and Seung's second
multiplicative algorithm), all iterations of one query: what the
algorithm needs, from shapes and free of any layout, so that it reads the
same work whatever implements it. An iteration is

    H <- H .* (t(W) (V ./ (W H))) ./ t(colsum(W))
    W <- W .* ((V ./ (W H)) t(H)) ./ t(rowsum(H))

with ``V ./ (W H)`` wanted only at V's entries. An update is one sampled
quotient (a dot of k terms an entry: 2 nnz k operations) and one product
of it (a multiply-add an entry and a column: 2 nnz k); an entry is read
once as two int32 coordinates and a float32 value (12 B: the quotient is
made and used on the way, not stored); both factors' rows are read and
the result's written once (4 k B a row); the element-wise pass reads
three operands and writes one, and the column or row sum reads the other
factor. The operations are held to the MXU's rate at ``highest`` (the
dense lines' quotient and products are float32 dots of six passes; the
rest runs on the vector unit and the one-hot scatter, at least as
dear); the bytes bound it all the same."""


def counts(users, movies, entries, rank, iterations, **said_of_the_plans):
    k = rank
    sampled_flops = 2 * (2 * entries * k + 2 * entries * k)
    sampled_bytes = 2 * (12 * entries + 4 * k * (users + movies)) \
        + 4 * k * (movies + users)
    elementwise_bytes = 4 * 4 * k * (users + movies)
    return {"flops": iterations * sampled_flops,
            "bytes": iterations * (sampled_bytes + elementwise_bytes),
            "precision": "highest"}
