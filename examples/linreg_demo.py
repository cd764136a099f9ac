"""Normal-equations linear regression end-to-end — the reference's flagship
workload, asked as the query it is: ``inv(t(X) * X) * t(X) * y`` through
session + SQL + optimizer + one jitted program (the path cells
``linreg_10m_1c`` and ``linreg_10m_2x2`` measure).

Run: python examples/linreg_demo.py        (single chip or CPU mesh)
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     JAX_PLATFORMS=cpu python examples/linreg_demo.py   (simulated mesh)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
from jax.sharding import PartitionSpec as P

from matrel_tpu import MatrelSession
from matrel_tpu.core.blockmatrix import BlockMatrix


def main():
    sess = MatrelSession.builder().get_or_create()
    mesh = sess.mesh
    print(f"mesh: {dict(mesh.shape)}")

    rng = np.random.default_rng(0)
    n, k = 160_000, 128
    x = rng.standard_normal((n, k)).astype(np.float32)
    theta_true = rng.standard_normal((k, 1)).astype(np.float32)
    y = x @ theta_true + 0.01 * rng.standard_normal((n, 1)).astype(np.float32)

    # a tall table lies by rows over every device (upstream's
    # RowPartitioner): each device then multiplies its own rows
    by_rows = P(tuple(mesh.axis_names), None)
    sess.register("X", BlockMatrix.from_numpy(x, mesh=mesh, spec=by_rows))
    sess.register("y", BlockMatrix.from_numpy(y, mesh=mesh, spec=by_rows))

    # The optimizer at work on the formula as upstream writes it: the
    # chain DP brackets the inverse as a solve against t(X) * y (k x 1)
    query = sess.sql("inv(t(X) * X) * t(X) * y")
    print(query.explain())
    plan = sess.compile(query)
    print("rules that fired:", {r: c for r, c in
                                plan.meta["rule_hits"].items() if c})

    theta = sess.compute(query).to_numpy()
    # what ran: a Gram over 160,000 float32 rows is accumulated in panels
    # of 8,192 rows, the upper block triangle of each (gram_tiles:
    # block products computed, of the full square's); on a mesh every
    # device over its own rows (rows_a_device) and one all-reduce
    for rec in sess.last_plan()["products"]:
        said = {f: rec[f] for f in ("chosen", "gram_tiles",
                                    "rows_a_device") if f in rec}
        print(f"  {rec['node']} {rec['shape']}: {said}")
    err = np.linalg.norm(theta - theta_true) / np.linalg.norm(theta_true)
    print(f"relative parameter error: {err:.2e}")
    assert err < 1e-3, err


if __name__ == "__main__":
    main()
