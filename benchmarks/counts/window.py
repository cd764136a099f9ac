"""One tick of the sliding-window regression (a batch of c rows replaces
the c oldest of an n x k table; ``t(X) * X`` and ``t(X) * y`` follow):
what the MAINTENANCE needs, from shapes alone and free of any plan, so
that it reads the same work whatever implements it and no later PR can
read over 100%.

Operations: the Gram of the rows that come and of the rows that leave,
each symmetric, c k (k + 1) apiece; their two right-hand sides, 2 c k
apiece; at the jax.lax.Precision the products run at. Bytes: the batch
in and the rows that leave out (2 c (k + 1)), the two views read and
written (2 (k k + k)). The k x k solve that reads theta back is the
query's and not the maintenance's, and is left out: the floor stays a
floor. A re-base recomputes a view from the table — counts/linreg.py's
Gram and right-hand side over all n rows — and is charged by the share
of ticks that re-based (``rebases_a_tick``).

A plan that recomputes the views every tick does ``n / (2 c)`` times
these operations (156 times at 2,555,904 rows and 8,192 a batch)."""


def counts(c, k, n, itemsize, precision, rebases_a_tick=0.0):
    flops = 2 * c * k * (k + 1) + 4 * c * k
    nbytes = itemsize * (2 * c * (k + 1) + 2 * (k * k + k))
    flops += rebases_a_tick * (n * k * (k + 1) + 2 * n * k)
    nbytes += rebases_a_tick * itemsize * (n * k + n)
    return {"flops": flops, "bytes": nbytes, "precision": precision}
