"""Poisson (KL-divergence) non-negative matrix factorization — Lee and
Seung's second multiplicative algorithm as SystemML's ``PNMF.dml`` writes
it, asked as the two queries it is (the path cell ``pnmf_netflix_r128_1c``
measures):

    H <- H .* (t(W) * (V / (W * H))) / t(colsum(W))
    W <- W .* ((V / (W * H)) * t(H)) / t(rowsum(H))

``V`` is an element-sparse matrix, so ``V / (W * H)`` is wanted only at
V's entries. The optimizer writes it as a ``sampled`` node, and under the
product that reads it the executor makes the quotient on the way: neither
``W * H`` nor the quotient is ever stored whole (on the chip; here the
same kernels run interpreted).

Run: python examples/pnmf_demo.py          (single chip, or the CPU)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from matrel_tpu import MatrelSession
from matrel_tpu.config import MatrelConfig, on_tpu, set_default_config
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.coo import COOMatrix

SQL_H = "H .* (t(W) * (V / (W * H))) / t(colsum(W))"
SQL_W = "W .* ((V / (W * H)) * t(H)) / t(rowsum(H))"


def divergence(v, w, h):
    """D(V || W H) over V's entries plus sum(W H): the objective the
    updates do not increase (Lee & Seung, Theorem 2)."""
    wh = w @ h
    at = v > 0
    return float(np.sum(v[at] * np.log(v[at] / wh[at])) - v.sum() + wh.sum())


def main():
    # the sampled product runs on the compact-table executor of ONE
    # device; off the chip its kernels are interpreted
    cfg = MatrelConfig(pallas_interpret=not on_tpu())
    set_default_config(cfg)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    sess = MatrelSession(mesh=mesh, config=cfg)

    rng = np.random.default_rng(0)
    users, movies, rank = 600, 200, 8
    at = np.flatnonzero(rng.random(users * movies) < 0.05)
    V = COOMatrix.from_edges(at // movies, at % movies,
                             rng.integers(1, 6, at.size), shape=(users, movies))
    w = rng.uniform(0.1, 1.0, (users, rank)).astype(np.float32)
    h = rng.uniform(0.1, 1.0, (rank, movies)).astype(np.float32)
    sess.register("V", V)
    sess.register("W", BlockMatrix.from_numpy(w, mesh=mesh))
    sess.register("H", BlockMatrix.from_numpy(h, mesh=mesh))
    print("plan of the H update:")
    print(sess.sql(SQL_H).explain())

    v = V.to_dense().astype(np.float64)
    before = divergence(v, w.astype(np.float64), h.astype(np.float64))
    W, H = sess.catalog["W"], sess.catalog["H"]
    for it in range(3):
        for name, sql in (("H", SQL_H), ("W", SQL_W)):
            sess.register("W", W)
            sess.register("H", H)
            out = sess.compute(sess.sql(sql))
            said = sess.last_plan()
            for rec in said["sampled"]:
                shown = {f: rec[f] for f in (
                    "orientation", "op", "entries", "dense_entries", "lines",
                    "lines_by", "panel_rows", "slab_dtype", "shared_gather",
                    "dot",
                    "hbm_plan_bytes")}
                print(f"  iteration {it} {name}: sampled {shown}")
            assert said["sampled"] and not said["densified_products"], said
            if name == "H":
                H = out
            else:
                W = out
    after = divergence(v, W.to_numpy().astype(np.float64),
                       H.to_numpy().astype(np.float64))
    print(f"divergence: {before:.1f} -> {after:.1f}")
    assert after < before and np.all(H.to_numpy() >= 0)


if __name__ == "__main__":
    main()
