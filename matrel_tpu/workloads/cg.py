"""Conjugate gradient — iterative SPD solve as ONE jitted program.

The reference solves normal equations with a direct driver-side solve
(Cholesky; here the query ``inv(t(X) * X) * t(X) * y``). CG is the
iterative alternative when the system is large or the operator is only available as a matvec: each
step is one distributed matvec + a few vector reductions, compiled
into a single ``lax.while_loop`` (tolerance- AND iteration-bounded —
compiler-friendly control flow, no host round-trips).

``cg_solve`` takes a dense BlockMatrix / expression; ``cg_solve_linop``
takes any traceable matvec closure (e.g. a planned SpMV or the
never-materialised Gram operator v ↦ Aᵀ(Av)).

This is a side function with a loop and a ``jit`` of its own, NOT the
session's path: conjugate gradient through ``session.sql`` + ``compute``
(SystemML's LinearRegCG, a statement a line, its ``t(X) * (X * p)`` the
``mmchain`` node answered in one pass over X) is the benchmark's cell
``linregcg_10m_1c`` (``benchmarks/configs/systemml_linregcg_10m.py``),
and the two share no code.
"""

from __future__ import annotations

from typing import Callable, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import expr as E


def cg_runner(matvec: Callable, tol: float = 1e-6,
              maxiter: int = 1000) -> Callable:
    """Reusable JITTED solver ``run(b) -> (x, iterations)`` for one SPD
    operator. ``cg_solve_linop`` builds a fresh runner per call (and so
    re-traces); repeated solves and benchmarks should hold ONE runner
    so the compiled program is cached across calls. ``b`` may be any
    float array shaped (n,) or (n, 1) — coerced like cg_solve_linop."""

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def run(b):
        b = jnp.asarray(b, jnp.float32).reshape(-1)
        bnorm = jnp.maximum(jnp.linalg.norm(b), 1e-30)

        def cond(state):
            _, r, _, rs, it = state
            return (jnp.sqrt(rs) > tol * bnorm) & (it < maxiter)

        def body(state):
            x, r, p, rs, it = state
            ap = matvec(p)
            alpha = rs / jnp.maximum(p @ ap, 1e-30)
            x = x + alpha * p
            r = r - alpha * ap
            rs_new = r @ r
            p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
            return x, r, p, rs_new, it + 1

        x0 = jnp.zeros_like(b)
        state = (x0, b, b, b @ b, jnp.int32(0))
        x, _, _, _, it = jax.lax.while_loop(cond, body, state)
        return x, it

    return run


def cg_solve_linop(matvec: Callable, b: jax.Array,
                   tol: float = 1e-6, maxiter: int = 1000
                   ) -> Tuple[jax.Array, jax.Array]:
    """Solve A·x = b for SPD operator ``matvec`` (traceable). Returns
    (x, iterations). Stops at ‖r‖ ≤ tol·‖b‖ or maxiter."""
    b = jnp.asarray(b, jnp.float32).reshape(-1)
    return cg_runner(matvec, tol, maxiter)(b)


def cg_solve(A: Union[BlockMatrix, E.MatExpr], b,
             tol: float = 1e-6, maxiter: int = 1000
             ) -> Tuple[jax.Array, int]:
    """CG on a dense SPD matrix (padded region is exactly zero, so the
    padded system decouples: padded residual entries stay 0)."""
    from matrel_tpu.workloads.eigen import _dense_data
    e = E.as_expr(A)
    n, m = e.shape
    if n != m:
        raise ValueError(f"CG needs a square (SPD) matrix, got {e.shape}")
    data = _dense_data(A, e)
    bb = np.zeros(data.shape[0], np.float32)
    bb[:n] = np.asarray(b, np.float32).reshape(-1)
    x, it = cg_solve_linop(lambda v: data @ v, jnp.asarray(bb),
                           tol=tol, maxiter=maxiter)
    return x[:n], int(it)


def cg_least_squares(X: Union[BlockMatrix, E.MatExpr], y,
                     l2: float = 0.0, tol: float = 1e-6,
                     maxiter: int = 1000) -> Tuple[jax.Array, int]:
    """argmin ‖Xθ − y‖² (+ l2‖θ‖²) by CG on the NORMAL EQUATIONS
    operator v ↦ Xᵀ(Xv) + l2·v — the Gram matrix never materialises
    (two matvecs per iteration; the iterative face of the
    normal-equations query)."""
    from matrel_tpu.workloads.eigen import _dense_data
    e = E.as_expr(X)
    k = e.shape[1]
    data = _dense_data(X, e)
    yy = np.zeros(data.shape[0], np.float32)
    yy[: e.shape[0]] = np.asarray(y, np.float32).reshape(-1)
    rhs = jnp.asarray(data.T @ jnp.asarray(yy))

    def gram_op(v):
        return data.T @ (data @ v) + l2 * v

    theta, it = cg_solve_linop(gram_op, rhs, tol=tol, maxiter=maxiter)
    return theta[:k], int(it)
