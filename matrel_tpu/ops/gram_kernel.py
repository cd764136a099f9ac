"""The Gram ``t(X) * X`` of a tall dense float32 table as ONE kernel
over the table where it lies: the upper triangle in blocks of 128 (36
of 64 tiles at k = 1000), X read once, a second product ``t(X) * Y``
over the same rows inside it.

The table is taken as ``ops/mmchain.py`` takes it: a tall ``f32[n, k]``
lies on a v5e with its rows on the 128 lanes, ``x.T`` under ``jit`` is a
bitcast, and a grid step's tile is ``A = (k, TILE_ROWS)``. The Gram of
the tile is ``A · t(A)``: every block product contracts over the LANES
of both operands, the ``q · t(k)`` form the MXU multiplies natively —
no transposed copy, no second table.

A step multiplies block row ``i`` (128 sublanes of the tile) with block
rows ``j >= i`` and adds the ``(128, 128)`` products into an accumulator
that stays in VMEM for the whole grid, laid out a tile after the other
(``(blocks², 128, 128)`` flattened: a tile is addressed by its leading
index alone). Blocks below the diagonal are never multiplied; the caller
mirrors the upper triangle once.

The LAST block is ragged where k is no multiple of 128 (104 rows at k =
1000). It is copied once a step into a ``(128, TILE_ROWS)`` scratch
whose spare rows hold the riders — the rows of ``t(Y)`` for the same
lanes — and zeros, so every block product has ONE shape, the products
with the last block yield ``t(X) * Y`` beside the Gram's last block
column at no operation of their own (a block of 104 columns fills the
MXU's 128 as a block of 128 does), and the body is three short loops
over one product, not 36 unrolled ones.

Accumulation: a product sums ``TILE_ROWS`` = 2,048 rows on the MXU in
float32 (shorter than ``strategies.ACC_PANEL_ROWS``) and the accumulator
takes one float32 addition a step (1,248 at 2,555,904 rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from matrel_tpu.ops.mmchain import VMEM_LIMIT, tile_rows  # noqa: F401 — the planner asks both here
from matrel_tpu.utils import compat

#: Rows and columns of a block product: the MXU's own tile.
BLOCK = 128
#: Contraction over the lanes of both operands: ``a · t(b)``.
_OVER_LANES = (((1,), (1,)), ((), ()))


def blocks(k: int) -> int:
    """Block rows a Gram of ``k`` columns is cut into, the last ragged."""
    return -(-k // BLOCK)


def tiles(k: int) -> tuple:
    """(computed, of): the block products a step multiplies, of those
    the square holds."""
    nb = blocks(k)
    return nb * (nb + 1) // 2, nb * nb


def rider_room(k: int) -> int:
    """Rows the ragged last block leaves spare for riders: 24 at k =
    1000, none where k is a multiple of 128."""
    return -k % BLOCK


def vmem_bytes(k: int, tile: int) -> int:
    """What the kernel keeps in VMEM: the table's tile twice (the
    pipeline's two buffers), the accumulator (twice: an output block
    has two as well), the last block's scratch and the riders' tile."""
    nb = blocks(k)
    return 4 * (2 * k * tile + 2 * nb * nb * BLOCK * BLOCK
                + BLOCK * tile + 2 * 8 * tile)


def _kernel(k: int, m: int, precision):
    """One grid step over a ``(k, tile)`` tile of ``x.T`` and, with
    ``m`` riders, the ``(m, tile)`` tile of ``y.T``."""
    nb = blocks(k)
    last = k - (nb - 1) * BLOCK          # rows of the ragged block

    def body(*refs):
        if m:
            x_ref, y_ref, o_ref, last_ref = refs
        else:
            x_ref, o_ref, last_ref = refs

        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)
            last_ref[...] = jnp.zeros_like(last_ref)    # the spare rows

        last_ref[0:last, :] = x_ref[(nb - 1) * BLOCK:k, :]
        if m:
            last_ref[last:last + m, :] = y_ref[...]

        def block(i):
            return x_ref[pl.ds(pl.multiple_of(i * BLOCK, BLOCK), BLOCK), :]

        def add(i, j, a, b):
            at = pl.ds(pl.multiple_of((i * nb + j) * BLOCK, BLOCK), BLOCK)
            o_ref[at, :] += jax.lax.dot_general(
                a, b, _OVER_LANES, precision=precision,
                preferred_element_type=jnp.float32)

        def column(j, carry):
            def row(i, carry):
                add(i, j, block(i), block(j))
                return carry
            return jax.lax.fori_loop(0, j + 1, row, carry)

        def last_column(i, carry):
            add(i, nb - 1, block(i), last_ref[...])
            return carry

        jax.lax.fori_loop(0, nb - 1, column, 0)
        jax.lax.fori_loop(0, nb - 1, last_column, 0)
        add(nb - 1, nb - 1, last_ref[...], last_ref[...])

    return body


@functools.lru_cache(maxsize=32)
def _runner(k: int, m: int, steps: int, tile: int, precision,
            interpret: bool):
    """call(xt[, yt]) -> (blocks² · 128, 128): the block products' sums
    over the first ``steps * tile`` rows, a tile after the other."""
    nb = blocks(k)
    in_specs = [pl.BlockSpec((k, tile), lambda s: (0, s))]
    if m:
        in_specs.append(pl.BlockSpec((m, tile), lambda s: (0, s)))
    return pl.pallas_call(  # matlint: disable=ML009 a dense Gram's kernel: the registry is the sparse S x S family's seam
        _kernel(k, m, precision),
        name="matrel_gram",
        grid=(steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nb * nb * BLOCK, BLOCK), lambda s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * nb * BLOCK, BLOCK),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((BLOCK, tile), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )


def gram_upper(x, rhs=None, *, tile: int, precision, interpret=False):
    """(upper, rode) over the first ``n // tile * tile`` rows of a
    float32 ``x`` (n, k): ``upper`` (k, k) holds ``t(x) * x`` on and
    above the diagonal of its blocks of 128 (zeros in the blocks below:
    the caller's mirror fills them) and ``rode`` is ``t(x) * rhs`` (k,
    m) for ``rhs`` (n, m), ``m`` at most :func:`rider_room`, else
    None."""
    n, k = x.shape
    nb = blocks(k)
    m = 0 if rhs is None else rhs.shape[1]
    operands = [x.T]
    if m:
        operands.append(rhs.T)
    out = _runner(k, m, n // tile, tile, precision, interpret)(*operands)
    # (i, j, row, column) -> (i, row, j, column): the square in blocks
    square = out.reshape(nb, nb, BLOCK, BLOCK).transpose(0, 2, 1, 3) \
        .reshape(nb * BLOCK, nb * BLOCK)
    upper = square[:k, :k]
    rode = square[:k, k:k + m] if m else None
    return upper, rode
