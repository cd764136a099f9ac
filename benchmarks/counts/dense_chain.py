"""The dense chain ``A . B . C`` of three n x n tables: what the
algorithm needs, from shapes. Two products of 2 n^3 operations each; each
operand and each product is moved once (A, B, C read, the intermediate
and the answer written: five tables; the intermediate's read-back is the
same bytes again and is not counted, as the least). ``precision`` is the
jax.lax.Precision the products run at: bfloat16 tables take one MXU pass
(``peaks.json`` ``mxu_passes`` "default")."""


def counts(n, itemsize, precision):
    return {"flops": 2 * 2 * n ** 3, "bytes": 5 * n * n * itemsize,
            "precision": precision}
