"""Physical matmul strategies — the TPU rebuild of MatRel's strategy trio
(SURVEY.md §2 "Physical: Broadcast-MM / Cross-Product-MM / Replication-MM").

Reference semantics → collective duality (SURVEY.md §5 "Distributed comm
backend"):

  BMM  (broadcast small operand; map-side multiply, zero shuffle of the big
        side)            →  replicate small operand across the mesh; big side
                            row-sharded over ALL devices; local dot; no
                            execution-time collective.
  CPMM (outer-product: co-shuffle A's k-blocks with B's k-blocks, multiply,
        reduceByKey sums partial C blocks — reduce-scatter-shaped)
                         →  contraction dim sharded on mesh axis y; local
                            partial C; `psum_scatter` over y.
  RMM  (replicate blocks so each reducer owns every input of its C block;
        one cogroup shuffle — all-gather-shaped)
                         →  A replicated along y, B replicated along x
                            (the resharding IS the all-gather); local full-k
                            dot produces C sharded P(x, y) with no further
                            comm.
  SUMMA/Cannon (not in the reference; the long-context/ring analogue,
        SURVEY.md §5 "Long-context")
                         →  A, B, C all stay P(x, y); k advances by a
                            `ppermute` ring; memory O(N²/P) per chip.

Each strategy is a function (a, b, mesh, precision) -> c over the full padded
arrays, implemented with `shard_map` so the collective schedule is explicit
and assertable from HLO (SURVEY.md §4 "plan shape" tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from matrel_tpu.utils.compat import shard_map

from matrel_tpu.config import MatrelConfig, default_config

STRATEGIES = ("bmm_left", "bmm_right", "cpmm", "rmm", "summa", "xla")


def _precision(cfg: Optional[MatrelConfig]):
    cfg = cfg or default_config()
    return getattr(jax.lax.Precision, cfg.matmul_precision.upper(),
                   jax.lax.Precision.HIGHEST)


def _acc_dtype(a, b):
    # accumulate bf16 inputs in f32 on the MXU
    if a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16:
        return jnp.float32
    # integer inputs accumulate at least int32 (the MXU's int8×int8→
    # int32 contract; an int8 accumulator would wrap on the first k>1
    # contraction) — the precision-tier int paths rely on this
    if (jnp.issubdtype(a.dtype, jnp.integer)
            and jnp.issubdtype(b.dtype, jnp.integer)):
        return jnp.result_type(a.dtype, b.dtype, jnp.int32)
    return jnp.result_type(a.dtype, b.dtype)


def _local_dot(a, b, prec, out_dtype):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=prec, preferred_element_type=out_dtype)


def matmul_xla(a: jax.Array, b: jax.Array, mesh: Mesh,
               config: Optional[MatrelConfig] = None) -> jax.Array:
    """Fallback: one einsum, XLA SPMD chooses the collectives.

    Output constrained to the canonical 2D sharding so downstream ops
    compose; inputs keep whatever sharding they arrived with.
    """
    x, y = mesh.axis_names
    out = jnp.einsum("nk,km->nm", a, b, precision=_precision(config),
                     preferred_element_type=_acc_dtype(a, b))
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P(x, y)))


def matmul_bmm(a: jax.Array, b: jax.Array, mesh: Mesh,
               config: Optional[MatrelConfig] = None,
               broadcast_side: str = "right") -> jax.Array:
    """Broadcast-MM: replicate the small operand, row-shard the big one over
    the whole mesh, multiply map-side. Zero execution-time collectives —
    the broadcast happens once in input resharding, like Spark's torrent
    broadcast of the small matrix (SURVEY.md §2 BMM)."""
    x, y = mesh.axis_names
    prec = _precision(config)
    out_dtype = _acc_dtype(a, b)
    if broadcast_side == "right":
        in_specs = (P((x, y), None), P())   # big A row-sharded, B everywhere
        out_specs = P((x, y), None)

        def kernel(ab, bb):
            return _local_dot(ab, bb, prec, out_dtype)
    else:
        in_specs = (P(), P(None, (x, y)))   # A everywhere, big B col-sharded
        out_specs = P(None, (x, y))

        def kernel(ab, bb):
            return _local_dot(ab, bb, prec, out_dtype)

    f = shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return f(a, b)


def matmul_cpmm(a: jax.Array, b: jax.Array, mesh: Mesh,
                config: Optional[MatrelConfig] = None) -> jax.Array:
    """Cross-Product-MM: contraction dim sharded over mesh axis y.

    Each device holds A[n/gx, k/gy] and B[k/gy, m]; the local outer-product
    partial C[n/gx, m] is summed-and-scattered over y with `psum_scatter` —
    the direct analogue of the reference's reduceByKey over partial C blocks
    (SURVEY.md §2 CPMM)."""
    x, y = mesh.axis_names
    prec = _precision(config)
    out_dtype = _acc_dtype(a, b)

    def kernel(ab, bb):
        partial = _local_dot(ab, bb, prec, out_dtype)  # (n/gx, m) partial
        # reduce-scatter partial C over the contraction axis; scatter cols
        return jax.lax.psum_scatter(partial, y, scatter_dimension=1,
                                    tiled=True)

    f = shard_map(kernel, mesh=mesh,
                  in_specs=(P(x, y), P(y, None)),
                  out_specs=P(x, y))
    return f(a, b)


def matmul_rmm(a: jax.Array, b: jax.Array, mesh: Mesh,
               config: Optional[MatrelConfig] = None) -> jax.Array:
    """Replication-MM: A replicated along y, B replicated along x; each
    device owns every input of its C tile and computes it in one local dot.
    The input resharding is the all-gather-shaped cogroup of the reference
    (SURVEY.md §2 RMM)."""
    x, y = mesh.axis_names
    prec = _precision(config)
    out_dtype = _acc_dtype(a, b)

    def kernel(ab, bb):
        return _local_dot(ab, bb, prec, out_dtype)

    f = shard_map(kernel, mesh=mesh,
                  in_specs=(P(x, None), P(None, y)),
                  out_specs=P(x, y))
    return f(a, b)


def matmul_summa(a: jax.Array, b: jax.Array, mesh: Mesh,
                 config: Optional[MatrelConfig] = None) -> jax.Array:
    """Cannon-style ring matmul: A, B, C all stay fully 2D-sharded P(x, y);
    the contraction advances by ppermute rings, so per-chip memory stays
    O(N²/P) with no replication. This is the SUMMA/ring component SURVEY.md
    §5 maps to ring-attention's role in the template.

    Requires a mesh where gx == gy (square grid); callers fall back to CPMM
    otherwise. Block-aligned: k must divide evenly over both axes (true for
    BlockMatrix padding).
    """
    x, y = mesh.axis_names
    gx, gy = mesh.shape[x], mesh.shape[y]
    if gx != gy:
        return matmul_cpmm(a, b, mesh, config)
    prec = _precision(config)
    out_dtype = _acc_dtype(a, b)
    g = gx

    def kernel(ab, bb):
        # Cannon's initial skew: rotate A left by its row index i along y,
        # and B up by its column index j along x, so step t multiplies
        # A[i, i+j+t] with B[i+j+t, j]. The shift amount is device-varying,
        # so every device runs the SAME g-1 ppermute steps (collectives must
        # be uniform across the mesh) and commits the shifted value only
        # while t < i (resp. t < j) via a local `where` — no divergent
        # control flow around collectives.
        i = jax.lax.axis_index(x)
        j = jax.lax.axis_index(y)

        def shift_a(arr):  # rotate one step left along mesh axis y
            return jax.lax.ppermute(
                arr, y, [(c, (c - 1) % g) for c in range(g)])

        def shift_b(arr):  # rotate one step up along mesh axis x
            return jax.lax.ppermute(
                arr, x, [(r, (r - 1) % g) for r in range(g)])

        def skew(t, carry):
            aa, bb_ = carry
            aa = jnp.where(t < i, shift_a(aa), aa)
            bb_ = jnp.where(t < j, shift_b(bb_), bb_)
            return aa, bb_

        if g > 1:
            ab, bb = jax.lax.fori_loop(0, g - 1, skew, (ab, bb))

        def step(t, carry):
            aa, bb_, acc = carry
            acc = acc + _local_dot(aa, bb_, prec, out_dtype)
            aa = shift_a(aa)
            bb_ = shift_b(bb_)
            return aa, bb_, acc

        acc0 = jnp.zeros((ab.shape[0], bb.shape[1]), dtype=out_dtype)
        # mark the fresh accumulator as varying over the mesh axes so the
        # fori_loop carry types line up with the per-device dot results
        acc0 = compat.pvary(acc0, (x, y))
        if g == 1:
            return _local_dot(ab, bb, prec, out_dtype)
        _, _, acc = jax.lax.fori_loop(0, g, step, (ab, bb, acc0))
        return acc

    f = shard_map(kernel, mesh=mesh,
                  in_specs=(P(x, y), P(x, y)),
                  out_specs=P(x, y))
    return f(a, b)


MATMUL_IMPLS = {
    "bmm_left": functools.partial(matmul_bmm, broadcast_side="left"),
    "bmm_right": functools.partial(matmul_bmm, broadcast_side="right"),
    "cpmm": matmul_cpmm,
    "rmm": matmul_rmm,
    "summa": matmul_summa,
    "xla": matmul_xla,
}


def run_matmul(strategy: str, a: jax.Array, b: jax.Array, mesh: Mesh,
               config: Optional[MatrelConfig] = None,
               epilogue=None) -> jax.Array:
    """``epilogue`` is the fused-region slot (ir/fusion.py /
    docs/FUSION.md): a traceable callable applied to the strategy's
    output INSIDE the same traced computation, so an absorbed
    elementwise/scalar/reduction chain compiles as the contraction's
    epilogue instead of its own dispatch. None (the default) is the
    historical path, bit-identically."""
    # fault site "strategy": the resilience harness's hook at strategy
    # execution (trace time). One truthiness test when injection is off.
    from matrel_tpu.resilience import faults as faults_lib
    faults_lib.check("strategy", config)
    impl = MATMUL_IMPLS[strategy]
    if strategy.startswith("bmm"):
        side = "left" if strategy == "bmm_left" else "right"
        out = matmul_bmm(a, b, mesh, config, broadcast_side=side)
    else:
        out = impl(a, b, mesh, config)
    return out if epilogue is None else epilogue(out)
