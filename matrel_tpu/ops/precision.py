"""Precision-tiered matmul lowering — the multi-pass decompositions
behind the planner's tier vocabulary (parallel/planner.PRECISION_TIERS;
docs/PRECISION.md).

The scheme is arXiv:2112.09017's split summation: decompose each f32
operand into bf16 slices (hi = bf16(x), lo = bf16(x − hi) — the same
residual construction as ops/gram.hi_lo_split and pallas_spmv's
``_bf16_split``) and accumulate the significant cross-products in f32
on the MXU. Keeping hi·hi + hi·lo + lo·hi (3 passes) drops only the
lo·lo term, whose relative magnitude is ~2^-16 — f32-class accuracy at
bf16 MXU rate. The int tiers cast integer-valued f32 operands onto the
integer MXU paths (int8 inputs, int32 accumulate) and keep the int32
result, so integer algebra (triangle counts, PageRank iteration
counts, boolean semiring joins) stays EXACT end to end.

Every pass goes through the caller-supplied ``mm`` — the planner's
chosen shard_map strategy recipe (strategies.run_matmul) — so tiering
composes with distribution: a bf16x3 cpmm is three cpmm passes, each
moving half-width operand bytes over the same collective schedule.
"""

from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp

Array = jax.Array


def bf16_slices(x: Array, k: int) -> List[Array]:
    """f32 → k bf16 residual slices with Σ slices ≈ x (error ~2^(-8k)
    relative). k=2 delegates to :func:`ops.gram.hi_lo_split` — the ONE
    cast-and-subtract residual construction (two copies of the split
    numerics would drift; cf. pallas_spmv._bf16_split's interpret-mode
    caveat, which masks mantissas for exactly that reason)."""
    from matrel_tpu.ops.gram import hi_lo_split
    if k == 2:
        return list(hi_lo_split(x))
    parts: List[Array] = []
    r = x.astype(jnp.float32)
    for _ in range(k):
        p = r.astype(jnp.bfloat16)
        parts.append(p)
        r = r - p.astype(jnp.float32)
    return parts


def tiered_matmul(tier: str, a: Array, b: Array,
                  mm: Callable[[Array, Array], Array]) -> Array:
    """One matmul at a stamped precision tier.

    ``mm(p, q)`` is the strategy's product of two operand PAYLOADS; it
    must accumulate wide (strategies._acc_dtype: bf16 inputs → f32,
    integer inputs → int32) — true for every run_matmul recipe. The
    bf16 tiers return the f32 accumulation; the int tiers return the
    int32 result (exact while products/sums fit int32).
    """
    if tier == "bf16x1":
        return mm(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if tier == "bf16x3":
        a_hi, a_lo = bf16_slices(a, 2)
        b_hi, b_lo = bf16_slices(b, 2)
        # the three significant cross-products, f32-accumulated; lo·lo
        # (~2^-16 relative) is the dropped term
        return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)
    if tier in ("int32", "int8"):
        cast = jnp.int8 if tier == "int8" else jnp.int32
        # integral operands hold exact integers in f32, so the cast is
        # exact; the chooser only stamps int tiers on proven-integral
        # operands (stats.infer_integral) or an explicit dtype ask
        return mm(a.astype(cast), b.astype(cast))
    if tier == "f32":
        return mm(a, b)
    raise ValueError(f"unknown precision tier {tier!r} "
                     f"(vocabulary: parallel/planner.PRECISION_TIERS)")
