"""Normal-equations regression ``inv(t(X) * X) * t(X) * y`` over an
n x k table: what the ALGORITHM needs, from shapes, so that no later
PR can read over 100%.

Operations: the Gram ``t(X) * X`` is symmetric, so its k (k + 1) / 2
distinct entries are n multiply-adds each, n k (k + 1) operations;
``t(X) * y`` is 2 n k; the k x k solve is one factorisation and two
substitutions, k^3 / 3 + 2 k^2 (Cholesky's count: the left side is a
Gram). Bytes: X read once (n k), y read once (n), the k x k Gram written
and read once, theta written.

Today's product is one full-square dot (2 n k^2 operations, both
triangles), which can reach about half of this count's share and no
more: a kernel that computes one triangle can reach all of it. A scheme
that also shares transposed cross passes among the six bfloat16 passes of
``highest`` (``ops/gram.py`` does it for the three of ``high``) would
need fewer MXU passes than ``peaks.json`` ``mxu_passes`` charges this
count with, and would read over 100%: the count has to be lowered by a
``benchmark`` PR before such a scheme is measured. ``precision`` is the
jax.lax.Precision the products run at."""


def counts(n, k, itemsize, precision):
    flops = n * k * (k + 1) + 2 * n * k + k ** 3 // 3 + 2 * k * k
    nbytes = itemsize * (n * k + n + 2 * k * k + k)
    return {"flops": flops, "bytes": nbytes, "precision": precision}
