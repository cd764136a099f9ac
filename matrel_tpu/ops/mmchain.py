"""The fused chain ``t(X) * (w .* (X * v))`` over a tall dense float32
table, ONE read of X (SystemML's ``mmchain``: the product every
iterative solver over a tall table turns on — LinearRegCG, GLM,
MLogreg, L2SVM).

A grid over row tiles of X: a tile comes from HBM once, ``q_t = X_t ·
v`` (times ``w_t``), ``acc += t(X_t) · q_t``, ``v`` and ``acc``
resident in VMEM. With one column the MXU would waste 127 of its 128
output lanes, so both products run on the vector unit, in float32 with
no bfloat16 pass at all.

The table is taken AS IT LIES. A tall ``f32[n, k]`` lies on a v5e with
its long dimension on the 128 lanes (``major_to_minor=(1, 0)``: what
``device_put`` and a jitted generator both give, PR 31), which is the
row-major ``(k, n)`` array ``x.T`` names: under ``jit`` that transpose
is a bitcast, and the kernel's tile is ``(k, TILE_ROWS)``, rows on the
lanes. A table that lies the other way would be copied by that
transpose (a second table: ``planner.mmchain_plan`` declines it).

Accumulation: a lane of ``acc`` is added to once a tile (1,248 float32
additions at 2,555,904 rows, after 16 within the tile), round to
nearest on the vector unit, and the 128 lanes' sums are added once at
the end; no dot's accumulator runs over millions of rows
(``strategies.ACC_PANEL_ROWS``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from matrel_tpu.utils import compat

LANE = 128
SUBLANE = 8
#: Rows of X (lanes) a grid step reads: 16 lane chunks, so a step's
#: sixteen accumulators, its slice of ``v`` and the products in flight
#: fit the 64 vector registers. Double-buffered at k = 1000 the tile
#: takes 16.4 MB of VMEM (:data:`VMEM_LIMIT`).
TILE_ROWS = 2048
#: What the kernel may take of the v5e's 128 MiB of VMEM: the scoped
#: default (16 MiB) is less than two tiles at k = 1000.
VMEM_LIMIT = 64 << 20
#: Widest table whose two tiles, ``v`` and ``acc`` fit :data:`VMEM_LIMIT`.
COLS_MAX = 3072


def tile_rows(n: int) -> int:
    """Rows a grid step reads of a table of ``n`` rows: a whole number
    of lane chunks, :data:`TILE_ROWS` at most; 0 where the table has
    fewer than 128 rows (nothing for the grid to do)."""
    return min(TILE_ROWS, n // LANE * LANE)


def _kernel(chunks: int, groups: int, weighted: bool):
    """One grid step over a ``(k, chunks * 128)`` tile: ``groups`` = k /
    8 sublane groups, a ``fori_loop`` each for the two products."""

    def body(*refs):
        if weighted:
            x_ref, v_ref, w_ref, o_ref = refs
        else:
            x_ref, v_ref, o_ref = refs

        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        def rows(g):
            return pl.ds(pl.multiple_of(g * SUBLANE, SUBLANE), SUBLANE)

        def lanes(c):
            return pl.ds(c * LANE, LANE)

        # q[c] = sum over the k rows of x[:, chunk c] * v: sixteen
        # (8, 128) accumulators, a sublane group of the tile a trip
        def first(g, acc):
            vg = v_ref[rows(g), :]
            return tuple(a + x_ref[rows(g), lanes(c)] * vg
                         for c, a in enumerate(acc))

        zero = jnp.zeros((SUBLANE, LANE), jnp.float32)
        acc = jax.lax.fori_loop(0, groups, first, (zero,) * chunks)
        q = []
        for c, a in enumerate(acc):
            qc = jnp.sum(a, axis=0, keepdims=True)
            if weighted:
                qc = qc * w_ref[:, lanes(c)]
            q.append(jnp.broadcast_to(qc, (SUBLANE, LANE)))

        # acc[rows g] += sum over the chunks of x[rows g, chunk c] * q[c]
        def second(g, carry):
            s = x_ref[rows(g), lanes(0)] * q[0]
            for c in range(1, chunks):
                s = s + x_ref[rows(g), lanes(c)] * q[c]
            o_ref[rows(g), :] += s
            return carry

        jax.lax.fori_loop(0, groups, second, 0)

    return body


@functools.lru_cache(maxsize=32)
def _runner(k: int, tiles: int, tile: int, weighted: bool, interpret: bool):
    """call(xt, vb[, w]) -> (k, 128): the lanes' partial sums of
    ``t(X) * (w .* (X * v))`` over the first ``tiles * tile`` rows."""
    x_spec = pl.BlockSpec((k, tile), lambda i: (0, i))
    whole = pl.BlockSpec((k, LANE), lambda i: (0, 0))
    in_specs = [x_spec, whole]
    if weighted:
        in_specs.append(pl.BlockSpec((1, tile), lambda i: (0, i)))
    return pl.pallas_call(  # matlint: disable=ML009 a dense chain's kernel: the registry is the sparse S x S family's seam
        _kernel(tile // LANE, k // SUBLANE, weighted),
        name="matrel_mmchain",
        grid=(tiles,),
        in_specs=in_specs,
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((k, LANE), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )


def mmchain(x, v, w=None, *, tile: int, interpret: bool = False):
    """``t(x) * (w .* (x * v))`` (``w`` None: ``t(x) * (x * v)``) for a
    float32 ``x`` (n, k), ``v`` (k, 1) and ``w`` (n, 1), as (k, 1): the
    kernel over the whole tiles of ``tile`` rows, and the ragged tail
    (fewer than ``tile`` rows, sliced from the table where it lies) as
    two plain products after it."""
    n, k = x.shape
    tiles = n // tile if tile else 0
    head = tiles * tile
    parts = []
    if tiles:
        operands = [x.T, jnp.broadcast_to(v, (k, LANE))]
        if w is not None:
            operands.append(w.reshape(1, n))
        parts.append(jnp.sum(
            _runner(k, tiles, tile, w is not None, interpret)(*operands),
            axis=1, keepdims=True))
    if head < n:
        xt = jax.lax.slice_in_dim(x, head, n, axis=0)
        q = jnp.dot(xt, v, precision="highest")
        if w is not None:
            q = q * jax.lax.slice_in_dim(w, head, n, axis=0)
        parts.append(jax.lax.dot_general(
            xt, q, (((0,), (0,)), ((), ())), precision="highest"))
    return functools.reduce(jnp.add, parts)
