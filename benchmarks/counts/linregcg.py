"""LinearRegCG (conjugate gradient on the normal equations, SystemML's
``LinearRegCG.dml``) over an n x k table, all rounds of one query: what
the ALGORITHM needs, from shapes and the round count alone and free of
any layout or plan, so that it reads the same work whatever implements
it and no later PR can read over 100%.

Bytes: X read once a chain ``t(X) * (X * p)`` and once for ``t(X) *
y``, (rounds + 1) n k; y read once (n); a round reads ``p`` and writes
``q`` (2 k), ``t(X) * y`` writes k. The other vector updates of a round
(``beta``, ``r``, ``p``: a dozen reads and writes of k) are the
program's choice of statements and are left out: the floor stays a
floor. Operations: 4 n k a chain (two multiply-adds an entry of X) and
2 n k for ``t(X) * y``, at the jax.lax.Precision the products run at.

A plan that reads X TWICE a chain (``X * p`` stored, then ``t(X) *
q``: what every program without a fused chain does) can reach about
half of this count's share, (rounds + 1) / (2 rounds + 1), and no
more; no plan can read over 100%."""


def counts(n, k, itemsize, rounds, precision):
    flops = rounds * 4 * n * k + 2 * n * k
    nbytes = itemsize * ((rounds + 1) * n * k + n + (2 * rounds + 1) * k)
    return {"flops": flops, "bytes": nbytes, "precision": precision}
