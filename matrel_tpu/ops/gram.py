"""Symmetric 2-pass bf16 Gram split — the ONE implementation of the
round-3 identity (docs/ROUND3.md floor analysis) behind the
executor's AᵀA/AAᵀ lowering.

For f32 x split as x = hi + lo (bf16 each), the three products XLA's
precision=HIGH keeps (hi·hi, hi·lo, lo·hi; lo·lo dropped) collapse in a
GRAM to two MXU passes plus a k×k transpose, because the cross terms
are transposes of each other: xᵀx ≈ hiᵀhi + hiᵀlo + (hiᵀlo)ᵀ. Same
three products, 33% fewer matmul FLOPs — an optimization XLA's generic
dot cannot apply because it does not know both operands are the same
matrix.

That is the algebra. On a v5e it is NOT what runs (chip readings, PR
31; PERF.md section 7): the compiler drops the round trip through
bfloat16, so ``x - f32(bf16(x))`` comes out exactly 0, ``lo`` is 0,
and the split computes bit for bit what ``matmul_precision="default"``
computes (one bf16 pass: the regression's theta 3.19–4.70e-5 from its
reference, ``default`` 3.14–4.70e-5, ``highest`` 5.4e-7–1.43e-6), for
more time than ``default`` takes (75.1 against 70.1 ms a query). No
benchmark cell runs it; its fate is ROADMAP's D-queue (delete it, or
build ``hi`` with ``lax.reduce_precision``).
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp


def hi_lo_split(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """f32 → (hi, lo) bf16 pair with x ≈ hi + lo (standard bf16x3
    residual construction)."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def symmetric_gram(x: jax.Array,
                   mm: Callable[[jax.Array, jax.Array], jax.Array]
                   ) -> jax.Array:
    """The 2-pass symmetric Gram of f32 ``x``.

    ``mm(p, q)`` is the caller's (possibly distributed) product of the
    two bf16 operand PAYLOADS — it owns the orientation (xᵀ·x via
    einsum or explicit transposes, x·xᵀ likewise) and must accumulate
    in f32 (preferred_element_type / _acc_dtype). The result of
    ``mm(hi, lo)`` must be the cross term whose TRANSPOSE is the other
    cross term — true for both Gram orientations.
    """
    hi, lo = hi_lo_split(x)
    hihi = mm(hi, hi)
    hilo = mm(hi, lo)
    return hihi + hilo + hilo.T
