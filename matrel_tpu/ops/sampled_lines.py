"""The dense lines' share of a sampled product ``(S op (P·t(R))) · Z``
as ONE kernel an update, ``matrel_sampled_lines``: the slab's cells, the
dense product at those cells, the sampled values ``Q`` and ``Q``'s own
product in one grid step a row tile; ``Q`` exists only in VMEM.

A step takes ``tile`` rows of the slab (their cells at the slab's whole
width, bfloat16 or float32 as they lie) and of ``P``, the factor whose
rows the slab's rows name, beside ``RL = R[lines]``, which stays in VMEM
for the whole grid:

1. ``D = P_tile · t(RL)``, ``(tile, k') x (width, k')`` contracted over
   the lanes of both (``gram_kernel._OVER_LANES``: no operand is
   transposed), float32 at ``Precision.HIGHEST``;
2. ``Q = sampled_values(op, cells, D)`` on the vector unit, the rule of
   the XLA path to the letter: the slab is the STRUCTURE (its zero is a
   cell without an entry) and ``x / 0 = 0``;
3. the product of ``Q`` that the slab's ROLE names — where the lines are
   the product's ``"sources"``, ``Y_tile += Q · ZL`` with ``ZL =
   Z[lines]`` resident like ``RL`` and ``Y``'s row tile the output block
   of its own input (aliased: ``Y`` is updated where it lies); where its
   ``"destinations"``, ``sums += t(Q) · Z_tile``, ``(width, tile) x
   (tile, k)`` contracted over the ROWS of both, ``Z``'s row tile as it
   lies (no ``t(Z)`` is handed in: at the chip's size that would be a
   245 MB copy an update). Mosaic turns ``Q`` on the transpose unit,
   under the MXU's time, and ``Z``'s tile is the MXU's weights: four
   weight tiles a step where ``t(Z_tile) · Q`` (the same sums the other
   way round, ``Z``'s tile turned, ``Q``'s 132 blocks the weights) loads
   132 — 51,549 scheduled bundles a step for 54,665, and 33.76 ms an
   update for 35.05 on the chip (PR 57).

Both roles are ONE body; the static role switches the last product.

The ragged last tile is the kernel's own: a step's rows past the slab's
last (whatever a partial block holds there) are zeroed in ``Q`` and in
``Z``'s tile for ``"destinations"``, where they would be summed; for
``"sources"`` they stay in rows of ``Y`` that are never written back.

Accumulation is two-level for ``"destinations"``, whose sum runs over
every row of the slab: a product sums ``tile`` rows on the MXU, a panel
of ``strategies.ACC_PANEL_ROWS`` rows adds up in a VMEM scratch, and the
scratch is folded into ``sums`` once a panel, as the XLA loop folds its
panels' dots. For ``"sources"`` a row of ``Y`` takes ONE dot over the
lines, as there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from matrel_tpu.ops.gram_kernel import _OVER_LANES
from matrel_tpu.ops.mmchain import LANE, VMEM_LIMIT
from matrel_tpu.ops.pallas_spmv import lines_rows, sampled_values
from matrel_tpu.utils import compat

#: Row tiles the kernel is built for, the tallest that fits first: a
#: weight tile of the MXU is streamed ``tile`` rows in the first product
#: and in the ``"sources"`` one.
TILES = (512, 256, 128)


def vmem_bytes(tile: int, width: int, cell_bytes: int) -> int:
    """What a step keeps in VMEM, reckoned from above: the cells twice
    (the pipeline's two buffers), ``D`` and ``Q`` in float32 and three
    bfloat16 parts of one of them for the MXU, ``RL`` and ``ZL`` twice
    (the sums, their panel's scratch and ``RL`` take as much and a
    quarter), and the row operands' tiles. Mosaic has the last word:
    the widest slab this admits at a tile compiles for a described v5e
    in both roles (tests/test_chip_compile.py)."""
    return (tile * width * (2 * cell_bytes + 2 * 4 + 3 * 2)
            + 4 * 4 * width * LANE + 6 * 4 * tile * LANE)


def plan(width: int, cell_bytes: int) -> dict:
    """Who multiplies a plan's dense lines under a sampled product, from
    what the lowering can observe — the slab's width and its cells'
    bytes (the fused product itself exists only where the Pallas
    executor runs and both factors fit 128 lanes:
    executor._sampled_dispatch_plan): ``lines_by`` "kernel" with
    ``panel_rows`` = the tallest row tile that fits :data:`VMEM_LIMIT`,
    or "xla" with ``lines_why_not`` "vmem" where none does and the
    loop's panel as ``panel_rows``."""
    from matrel_tpu.parallel import strategies
    for tile in TILES:
        if vmem_bytes(tile, width, cell_bytes) <= VMEM_LIMIT:
            return {"lines_by": "kernel", "panel_rows": tile}
    return {"lines_by": "xla", "lines_why_not": "vmem",
            "panel_rows": strategies.ACC_PANEL_ROWS}


def _kernel(along: bool, op: str, tile: int, rows: int, fold: int):
    """One grid step over ``tile`` rows of the slab; ``rows`` the slab's
    own count, ``fold`` the steps a panel of the sums takes."""

    def dot(a, b, dims):
        return jax.lax.dot_general(
            a, b, dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def body(cells_ref, p_ref, rl_ref, *role_refs):
        q = sampled_values(op, cells_ref[...].astype(jnp.float32),
                           dot(p_ref[...], rl_ref[...], _OVER_LANES))
        if along:
            zl_ref, y_ref, o_ref = role_refs
            o_ref[...] = y_ref[...] + dot(q, zl_ref[...],
                                          (((1,), (0,)), ((), ())))
            return
        z_ref, o_ref, panel_ref = role_refs
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)
            panel_ref[...] = jnp.zeros_like(panel_ref)

        z = z_ref[...]
        if rows % tile:     # the last tile's rows past the slab's end
            row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            mine = row < rows - step * tile
            q, z = jnp.where(mine, q, 0.0), jnp.where(mine, z, 0.0)
        panel_ref[...] += dot(q, z, (((0,), (0,)), ((), ())))

        @pl.when((step % fold == fold - 1)
                 | (step == pl.num_programs(0) - 1))
        def _():
            o_ref[...] += panel_ref[...]
            panel_ref[...] = jnp.zeros_like(panel_ref)

    return body


@functools.lru_cache(maxsize=32)
def _runner(along: bool, op: str, rows: int, width: int, tile: int,
            interpret: bool):
    """call(cells, P, RL, ZL, Y) -> Y, or call(cells, P, RL, Z) -> sums
    (width, 128), for a slab of ``rows`` x ``width`` cells."""
    from matrel_tpu.parallel import strategies
    steps = -(-rows // tile)
    by_rows = pl.BlockSpec((tile, LANE), lambda s: (s, 0))
    whole = pl.BlockSpec((width, LANE), lambda s: (0, 0))
    in_specs = [pl.BlockSpec((tile, width), lambda s: (s, 0)), by_rows,
                whole]
    if along:
        in_specs += [whole, by_rows]
        out_spec, out_shape, scratch = by_rows, (rows, LANE), []
        aliases = {4: 0}
    else:
        in_specs += [by_rows]
        out_spec, out_shape = whole, (width, LANE)
        scratch = [pltpu.VMEM(out_shape, jnp.float32)]
        aliases = {}
    return pl.pallas_call(  # matlint: disable=ML009 a dense product's kernel: the registry is the sparse S x S family's seam
        _kernel(along, op, tile, rows,
                max(1, strategies.ACC_PANEL_ROWS // tile)),
        name="matrel_sampled_lines",
        grid=(steps,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )


def sampled_lines(Y, role: str, slab, lines, Z, op: str, P, R, *,
                  tile: int, interpret: bool = False):
    """``Y`` plus the dense lines' share of a sampled product, as
    ``pallas_spmv._sampled_dense_part`` states it, through the kernel:
    ``slab`` (n, width) with column j the line ``lines[j]`` and width a
    whole number of lane groups, ``P`` (n, 128) the factor the slab's
    rows name and ``R`` the one its lines name, ``Z`` the dense side,
    all three float32 and 128 lanes wide (zero columns past their own),
    ``Y`` (destinations, 128) float32."""
    n, width = slab.shape
    count = lines.shape[0]
    run = _runner(role == "sources", op, n, width, tile, interpret)
    RL = lines_rows(R, lines, width)
    if role == "sources":
        return run(slab, P, RL, lines_rows(Z, lines, width), Y)
    return Y.at[lines].add(run(slab, P, RL, Z)[:count],
                           indices_are_sorted=True, unique_indices=True,
                           mode="promise_in_bounds")
