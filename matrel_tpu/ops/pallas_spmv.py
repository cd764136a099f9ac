"""Pallas compact-table SpMV: the one-hot scatter without stored one-hots.

The expanded EdgeSpMVPlan tables (ops/spmv.py) cost ~224 B per padded
edge slot in HBM — sel (32 B) + oh_hi (128 B) + oh_lo (64 B) — which is
~2.4 GB for a 10M-edge graph and the reason the PageRank plan cache is
byte-capped. The one-hots only exist because XLA's ``dot_general`` needs
materialised operands; inside a Pallas kernel they can be GENERATED in
VMEM from the compact layout the plan build already produces
(src8/lane/off/val, ~13 B/slot) and never touch HBM.

Pipeline per matvec (``spmv_compact``):

  1. XLA: ``w[b,c] = x[src8[b,c]·8 + lane[b,c]] · val[b,c]`` through
     ``spmv.gather_rows``: a row gather of bytes, 128 B a slot (1.34 GB
     a matvec at 10.5M slots). Gathered as 8 float32 a row the same step
     materialised ``f32[slots, 8]`` padded to 128 lanes — 5.38 GB
     written and read back every matvec (PERF.md §6, PR 28). Where those
     temporaries (``_TEMP_BYTES_A_SLOT``) would pass a quarter of the
     device's memory the step runs in PANELS of table rows inside one
     loop, each writing its slice of ``w`` (4 B a slot): 133M slots of a
     Graph500 scale-22 graph would gather 17 GB on a 15.75 GB chip
     (PR 33). One panel where everything fits: the program as before.
  2. Pallas: generate ``oh_hi`` (C, HI') bf16 and the w-carrying rhs
     (C, LO·passes) in VMEM (w carved into bf16 residual parts by
     mantissa masking — f32-faithful at passes=3, see
     ``_bf16_split`` for why masking, not casts), one MXU contraction
     ``oh_hiᵀ @ rhs`` per grid step. Blocks layout: a step a block,
     writing its (HI', LO) output tile. Chunks layout (ops/spmv.py,
     PR 33): a step a chunk of ``spmv.CHUNK`` slots; the chunk → block
     table is scalar-prefetched into the output's index map, so the
     consecutive chunks of one block add into one resident (HI', LO)
     tile, zeroed on the block's first chunk — a hub block is many
     steps, not one tall tile, and nothing is left to an overflow COO.
  2b. Pallas, where the plan has hub chunks (``plan.hubs``, PR 36): the
     slots whose source is a hub skip step 1. ``x[hubs.ids]`` (one small
     scalar gather) lies in VMEM as a ``(M, 128)`` table, a slot names
     its hub by rank, and a second chunk-grid kernel makes ``w`` for a
     vector register of 1,024 slots by lane permutes of table rows
     (``take_along_axis``: ``vperm`` on the XLU), each kept where the
     rank's row is that one, times ``val`` — then the same
     ``_scatter_tile``, added onto step 2's block sums, which it takes
     as its accumulator (aliased). Its ``w`` never exists in HBM. The
     rows permuted are the register's own (PR 42): a block's hub slots
     lie by table row, the build records the run of rows each register
     names (``spmv.hub_walks``), and the kernel walks that run in steps
     of ``spmv.HUB_WALK`` = 64 rows from aligned (8, 128) loads, its
     bounds scalar-prefetched beside ``chunk_block``. The XLU takes a
     permute every ~2.5 cycles whatever is done about it, so the rows
     walked are the kernel's cost: all M for every register until PR 42
     (which held the table at 256 rows), about M a BLOCK since.
  2c. The (max | min, x) product (``reduce_apply``, PR 50) is steps 1
     to 2b with another body: a segmented extremum scan along the lanes
     where the sum stands (``_reduce_tile``), over the same tables —
     the hub chunks' too since PR 51 (``matrel_spmv_reduce_hubs``: the
     hub kernel's weights into the reduction's tile), which is why a
     register's slots lie by destination row (``spmv.rows_in_order``).
  3. XLA: overflow-COO accumulation (blocks layout only; unchanged
     contract).

This executor reads an EdgeSpMVPlan's compact host tables (kept on
device via a small memo). It is the DEFAULT on real TPU backends for
COOMatrix matvec/matmat, the DSL's single-device COO matmuls, and
pagerank_edges; CPU and GSPMD multi-device executor programs keep the
expanded XLA path (pallas_call has no SPMD partitioning rule — the
shard_map variants below are the multi-device form). Measured trade
(BASELINE row 5 graph): ~17× smaller device tables (13 B/slot vs ~224).
"""

from __future__ import annotations

import dataclasses
import functools

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from matrel_tpu.ops import spmv as spmv_lib

LANE = 128


def _bf16_split(v, passes: int):
    """Residual bf16 decomposition: Σ parts ≈ v with error ~2^(-8·passes).
    The one-hot factor of the scatter's matmul is exact in bf16, so the
    split of the VALUE side is the only precision knob.

    Parts are carved by MASKING the low mantissa bits (truncation toward
    zero), not by dtype casts, and returned as f32 arrays whose values
    sit exactly on the bf16 grid (a later astype(bf16) is lossless).
    Two reasons: pallas interpret mode ELIDES bf16 rounding on casts
    (measured 2026-07-30: astype(bf16).astype(f32) round-trips unrounded
    inside a kernel), which silently collapsed a cast-based split to its
    first term; and Mosaic only supports minor-dim-inserting broadcasts
    for 32-bit types, so downstream masking must happen in f32 anyway."""
    parts = []
    rem = v
    for _ in range(passes):
        bits = jax.lax.bitcast_convert_type(rem, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)
        parts.append(hi)                        # f32, on the bf16 grid
        rem = rem - hi
    return parts


def _scatter_tile(off, w, hi_n: int, lo: int, passes: int):
    """(HI', LO) partial sums of one tile of slots, in VMEM."""
    # slots ride the MINOR (128-lane) axis throughout: masks with a
    # <128 minor dim lane-pad 4-8x on the VPU and cost more than the
    # stored tables they replace (measured 45 ms vs 29 at BASELINE
    # row-5 scale before this layout)
    cr = off.shape[0]
    ids_hi = jax.lax.broadcasted_iota(
        jnp.int32, (cr, hi_n, LANE), 1)
    oh_hi = ((off // lo)[:, None, :] == ids_hi).astype(jnp.bfloat16)
    ids_lo = jax.lax.broadcasted_iota(
        jnp.int32, (cr, lo, LANE), 1)
    mask = (off % lo)[:, None, :] == ids_lo
    rhs = jnp.concatenate(
        [jnp.where(mask, wp[:, None, :], 0.0)
         for wp in _bf16_split(w, passes)],
        axis=1).astype(jnp.bfloat16)                 # (cr,lo·p,128)
    t = jax.lax.dot_general(
        oh_hi, rhs,
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (cr,hi_n,lo·p)
    ts = jnp.sum(t, axis=0)                          # (hi_n, lo·p)
    th = ts[:, :lo]
    for p in range(1, passes):
        th = th + ts[:, p * lo:(p + 1) * lo]
    return th


def _make_scatter_kernel(hi_n: int, lo: int, passes: int):
    def kernel(off_ref, w_ref, y_ref):
        off = off_ref[0]                                 # (cr, 128)
        w = w_ref[0]
        y_ref[0] = _scatter_tile(off, w, hi_n, lo, passes)

    return kernel


def _first_chunk_of_its_block(cb_ref):
    """Whether this grid step's chunk opens a block: consecutive chunks
    of one block share the output tile (the index map reads the same
    ``cb``)."""
    c = pl.program_id(0)
    return jnp.logical_or(c == 0,
                          cb_ref[c] != cb_ref[jnp.maximum(c - 1, 0)])


def _make_chunk_scatter_kernel(hi_n: int, lo: int, passes: int):
    def kernel(cb_ref, off_ref, w_ref, y_ref):
        # the block's tile starts from zero
        @pl.when(_first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        y_ref[0] += _scatter_tile(off_ref[0], w_ref[0], hi_n, lo, passes)

    return kernel


def _hub_weights(idx, table_ref, walk, step: int):
    """``table[idx // 128, idx % 128]`` for a tile of slots, a vector
    register of 8 x 128 at a time: a table row, broadcast over the
    sublanes, permuted along the lanes by ``idx % 128`` and kept where
    ``idx // 128`` names that row — for the rows the register's walk
    names and no other (``walk(r)``: the r-th register's first row, a
    multiple of 8, and its steps of ``step`` = ``spmv.HUB_WALK`` rows,
    one at the least, as ``spmv.hub_walks`` reckoned them from these
    very slots). A step is straight-line code over aligned (8, 128)
    loads, so that its permutes are in flight together: the XLU answers
    one ~90 cycles after it was asked, and a loop trip waits for its
    last (80 ns a trip and 2.1 ns a row on a v5e, PERF.md section 6,
    PR 42). The first step, which every register has, stands outside the
    loop, where the scheduler lays it under the scatter's own work. The
    permute moves 32-bit lanes and the select picks whole values, so a
    slot's weight is the table's entry bit for bit; a padded slot
    (``idx`` = 128·M) matches no row and weighs 0."""
    tile = spmv_lib.HUB_TILE
    parts = []
    for r, s in enumerate(range(0, idx.shape[0], 8)):
        lane, row = idx[s:s + 8] & (LANE - 1), idx[s:s + 8] >> 7
        first, steps = walk(r)

        def walk_step(t, w, lane=lane, row=row, first=first):
            at = pl.multiple_of(first + step * t, tile)
            here = row - at
            for g in range(0, step, tile):
                rows = table_ref[pl.ds(pl.multiple_of(at + g, tile), tile), :]
                for j in range(tile):
                    took = jnp.take_along_axis(
                        jnp.broadcast_to(rows[j:j + 1, :], lane.shape), lane,
                        axis=1, mode="promise_in_bounds")
                    w = jnp.where(here == g + j, took, w)
            return w

        parts.append(jax.lax.fori_loop(
            1, steps, walk_step,
            walk_step(0, jnp.zeros(lane.shape, jnp.float32))))
    return jnp.concatenate(parts, axis=0)


# A hub chunk's walks ride as ONE int32 a chunk (scalar prefetch lives in
# SMEM: with ``chunk_block`` 8 B a hub chunk, ``spmv._HUB_CHUNKS_MAX``): a
# register has a field of 32 / registers bits, its steps in the low
# ``_WALK_STEP_BITS`` and its first row, in tiles of ``HUB_TILE``, above
# them: room for the 4,096 rows ``spmv._HUB_ROWS_MAX`` may reach at the most.
_WALK_STEP_BITS = 7


def _pack_walks(first: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(chunks,) int32 of ``HubChunks.first`` / ``.rows``; a table too
    tall for the fields, or rows that are no whole steps of the
    ``HUB_WALK`` the kernel will be traced with, are refused."""
    regs = first.shape[1]
    bits = 32 // regs
    steps, tiles = rows // spmv_lib.HUB_WALK, first // spmv_lib.HUB_TILE
    if first.size and ((rows % spmv_lib.HUB_WALK).any()
                       or steps.max() >> _WALK_STEP_BITS
                       or tiles.max() >> (bits - _WALK_STEP_BITS)):
        raise ValueError("hub walks do not fit a packed word: "
                         f"{int(rows.max())} rows from {int(first.max())}")
    word = np.zeros(first.shape[0], np.uint32)
    for r in range(regs):
        word |= (tiles[:, r] << _WALK_STEP_BITS
                 | steps[:, r]).astype(np.uint32) << np.uint32(bits * r)
    return word.view(np.int32)


def _make_hub_kernel(regs: int, step: int, fold):
    """A hub chunk's step: ``fold(tile, off, w)`` is the block's tile
    with the chunk's slots taken in — the sum's scatter or the (max |
    min) reduction — their weights ``w`` made here from the hub table."""
    def kernel(cb_ref, walk_ref, idx_ref, off_ref, val_ref, table_ref,
               acc_ref, y_ref):
        # as the chunk kernels, but the block's tile starts from what
        # the main chunks made of it (``acc``, which the output aliases:
        # a block with no hub chunk keeps that untouched)
        @pl.when(_first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = acc_ref[...]

        def walk(r):
            bits = 32 // regs
            field = (walk_ref[pl.program_id(0)] >> (bits * r)) & (
                (1 << bits) - 1)
            return ((field >> _WALK_STEP_BITS) * spmv_lib.HUB_TILE,
                    field & ((1 << _WALK_STEP_BITS) - 1))

        w = _hub_weights(idx_ref[0], table_ref, walk, step) * val_ref[0]
        y_ref[0] = fold(y_ref[0], off_ref[0], w)

    return kernel


REDUCES = {"max": jnp.maximum, "min": jnp.minimum}


def _segment_ends(off, w, reduce: str):
    """A tile of slots whose equal ``off`` lie side by side in every row
    of 128 (a block's slots in row order, ``spmv.rows_in_order``): the
    (max | min) of each such run of ``w`` at the run's last slot, 0 at
    every other. A segmented scan along the lanes in seven doubling
    steps: a slot takes its neighbour ``d`` to the left into its
    extremum where both name one row (then so does every slot between
    them), and a run ends where the next slot names another row or the
    row of 128 does. Lane rotations and selects alone: no value is ever
    rounded."""
    op = REDUCES[reduce]
    lane = jax.lax.broadcasted_iota(jnp.int32, off.shape, 1)
    d = 1
    while d < LANE:
        same = (pltpu.roll(off, d, axis=1) == off) & (lane >= d)
        w = jnp.where(same, op(w, pltpu.roll(w, d, axis=1)), w)
        d *= 2
    ends = (pltpu.roll(off, LANE - 1, axis=1) != off) | (lane == LANE - 1)
    return jnp.where(ends, w, 0.0)


def _reduce_tile(off, w, hi_n: int, lo: int, reduce: str):
    """(HI', LO) extrema of one tile of slots, 0 in the running of every
    row: each row of 128 slots places its runs' extrema
    (:func:`_segment_ends`) by the one-hot product of
    :func:`_scatter_tile` — a row of the tile gets ONE run's value and
    zeros from a row of 128, so its three bfloat16 parts add up to the
    float32 it was, bit for bit — and the tile rows of 128 then combine
    by (max | min) where the sum stood. A tile row no slot names reads
    0, and so does a padded slot (``off`` 0, ``w`` 0): the 0 of the
    matrix's missing cells, which the caller wants in the running of
    every row but a full one (core.coo.reduce_rows)."""
    cr = off.shape[0]
    ends = _segment_ends(off, w, reduce)
    ids_hi = jax.lax.broadcasted_iota(jnp.int32, (cr, hi_n, LANE), 1)
    oh_hi = ((off // lo)[:, None, :] == ids_hi).astype(jnp.bfloat16)
    ids_lo = jax.lax.broadcasted_iota(jnp.int32, (cr, lo, LANE), 1)
    mask = (off % lo)[:, None, :] == ids_lo
    th = None
    for part in _bf16_split(ends, 3):
        rhs = jnp.where(mask, part[:, None, :], 0.0).astype(jnp.bfloat16)
        t = jax.lax.dot_general(
            oh_hi, rhs, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (cr, hi_n, lo)
        th = t if th is None else th + t
    out = th[0]
    for r in range(1, cr):
        out = REDUCES[reduce](out, th[r])
    return out


def _make_chunk_reduce_kernel(hi_n: int, lo: int, reduce: str):
    def kernel(cb_ref, off_ref, w_ref, y_ref):
        # the block's tile starts from the 0 of a missing cell
        @pl.when(_first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        y_ref[0] = REDUCES[reduce](
            y_ref[0], _reduce_tile(off_ref[0], w_ref[0], hi_n, lo, reduce))

    return kernel


@functools.lru_cache(maxsize=32)
def _compact_runner(nb: int, cap: int, block: int, lo: int, passes: int,
                    interpret: bool):
    hi_n = block // lo
    cr = cap // LANE
    scatter = pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel, unported to the registry this round (autotuned via the spmv| table rows)
        _make_scatter_kernel(hi_n, lo, passes),
        name="matrel_spmv_scatter",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, cr, LANE), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, cr, LANE), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hi_n, lo), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, hi_n, lo), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    return scatter


@functools.lru_cache(maxsize=32)
def _chunk_runner(n_chunks: int, chunk: int, nb: int, block: int, lo: int,
                  passes: int, interpret: bool):
    """scatter(chunk_block, off, w) -> (nb, HI', LO): the chunks layout's
    scatter, a grid step a chunk. ``arbitrary``: the steps of one block
    must follow one another on one core to share its output tile."""
    hi_n = block // lo
    cr = chunk // LANE
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel, unported to the registry this round (autotuned via the spmv| table rows)
        _make_chunk_scatter_kernel(hi_n, lo, passes),
        name="matrel_spmv_scatter_chunks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                       # chunk_block
            grid=(n_chunks,),
            in_specs=[
                pl.BlockSpec((1, cr, LANE), lambda c, cb: (c, 0, 0)),
                pl.BlockSpec((1, cr, LANE), lambda c, cb: (c, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hi_n, lo),
                                   lambda c, cb: (cb[c], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, hi_n, lo), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=32)
def _reduce_runner(n_chunks: int, chunk: int, nb: int, block: int, lo: int,
                   reduce: str, interpret: bool):
    """reduce(chunk_block, off, w) -> (nb, HI', LO): the chunk grid of
    :func:`_chunk_runner` with another body — a block's rows take the
    (max | min) of their slots' weights and of 0 where that one adds
    them up. float32 throughout: no ``passes``."""
    hi_n = block // lo
    cr = chunk // LANE
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel family, unported to the registry (the chunk scatter's grid)
        _make_chunk_reduce_kernel(hi_n, lo, reduce),
        name="matrel_spmv_reduce_chunks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                       # chunk_block
            grid=(n_chunks,),
            in_specs=[
                pl.BlockSpec((1, cr, LANE), lambda c, cb: (c, 0, 0)),
                pl.BlockSpec((1, cr, LANE), lambda c, cb: (c, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hi_n, lo),
                                   lambda c, cb: (cb[c], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, hi_n, lo), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _hub_grid(n_chunks: int, chunk: int, nb: int, block: int, lo: int,
              m_rows: int, interpret: bool) -> dict:
    """What the two hub kernels' ``pallas_call``s share: the chunk grid
    over a plan's hub chunks, call(chunk_block, walks, idx, off, val,
    table, acc) -> (nb, HI', LO), the ``(m_rows, 128)`` hub table whole
    in VMEM, ``acc`` aliased to the output."""
    hi_n = block // lo
    cr = chunk // LANE
    slots = pl.BlockSpec((1, cr, LANE), lambda c, cb, wk: (c, 0, 0))
    sums = pl.BlockSpec((1, hi_n, lo), lambda c, cb, wk: (cb[c], 0, 0))
    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                   # chunk_block, walks
            grid=(n_chunks,),
            in_specs=[slots, slots, slots,
                      pl.BlockSpec((m_rows, LANE), lambda c, cb, wk: (0, 0)),
                      sums],
            out_specs=sums,
        ),
        out_shape=jax.ShapeDtypeStruct((nb, hi_n, lo), jnp.float32),
        input_output_aliases={6: 0},                     # acc
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=32)
def _hub_runner(n_chunks: int, chunk: int, nb: int, block: int, lo: int,
                passes: int, m_rows: int, step: int, interpret: bool):
    """scatter(chunk_block, walks, idx, off, val, table, acc) -> acc + the
    hub chunks' block sums, (nb, HI', LO): the chunk scatter whose slot
    weights come from the ``(m_rows, 128)`` hub table in VMEM, a
    register's from the rows ``walks`` names for it (:func:`_pack_walks`),
    ``step`` rows a loop trip."""
    hi_n = block // lo
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel, unported to the registry this round (autotuned via the spmv| table rows)
        _make_hub_kernel(
            chunk // LANE // 8, step,
            lambda y, off, w: y + _scatter_tile(off, w, hi_n, lo, passes)),
        name="matrel_spmv_scatter_hubs",
        **_hub_grid(n_chunks, chunk, nb, block, lo, m_rows, interpret))


@functools.lru_cache(maxsize=32)
def _reduce_hub_runner(n_chunks: int, chunk: int, nb: int, block: int,
                       lo: int, reduce: str, m_rows: int, step: int,
                       interpret: bool):
    """reduce(chunk_block, walks, idx, off, val, table, acc) -> the (max |
    min) of ``acc`` (the main chunks' extrema, :func:`_reduce_runner`)
    and the hub chunks' slots, (nb, HI', LO): :func:`_hub_runner`'s grid
    and weights into :func:`_reduce_tile` — each register's slots lie by
    destination row (``spmv.rows_in_order``), which is all the scan asks
    and nothing the walk sees."""
    hi_n = block // lo
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel family, unported to the registry (the hub scatter's grid)
        _make_hub_kernel(
            chunk // LANE // 8, step,
            lambda y, off, w: REDUCES[reduce](
                y, _reduce_tile(off, w, hi_n, lo, reduce))),
        name="matrel_spmv_reduce_hubs",
        **_hub_grid(n_chunks, chunk, nb, block, lo, m_rows, interpret))


def compact_tables(plan: spmv_lib.EdgeSpMVPlan):
    """Device copies of the plan's compact layout, memoised on the plan
    (the plan keeps its compact host tables even after expanded-path
    use, so path order never matters): (src8, lane, off, val), and the
    chunk → block table as a fifth where the plan is laid out in
    chunks — ``compact_apply`` knows the layout by it — and after it
    the plan's hub chunks, where it has any: (ids, idx, off, val,
    chunk_block, walks), the walks packed a chunk
    (:func:`_pack_walks`)."""
    dev = getattr(plan, "_compact_dev", None)
    if dev is None:
        nb, cap = np.asarray(plan.src8).shape
        if cap % LANE:
            raise ValueError(f"capacity {cap} not a multiple of {LANE}")
        cr = cap // LANE
        shp = (nb, cr, LANE)
        # lane stays int8 on device (the kernel compares it against an
        # iota of its own dtype): 13 B/slot total, as advertised.
        # Eager even when first called from inside an executor trace —
        # the memo must hold COMMITTED arrays, not tracers (a cached
        # tracer escapes its trace and poisons every later use of this
        # plan; found by the single-device interpret CI test)
        with jax.ensure_compile_time_eval():
            dev = (jnp.asarray(np.asarray(plan.src8).reshape(shp)),
                   jnp.asarray(np.asarray(plan.lane).reshape(shp)),
                   jnp.asarray(np.asarray(plan.off).reshape(shp)),
                   jnp.asarray(np.asarray(plan.val).reshape(shp)))
            if plan.chunk_block is not None:
                dev += (jnp.asarray(plan.chunk_block, jnp.int32),)
            if plan.hubs is not None:
                hub = plan.hubs
                dev += (jnp.asarray(hub.ids),) + tuple(
                    jnp.asarray(np.asarray(a).reshape(-1, cr, LANE))
                    for a in (hub.idx, hub.off, hub.val)) + (
                    jnp.asarray(hub.chunk_block, jnp.int32),
                    jnp.asarray(_pack_walks(hub.first, hub.rows)))
        plan._compact_dev = dev
    return dev


# What a panel of the matvec keeps alive a slot, read off the
# described-v5e compile of the PageRank loop (tests/test_chip_compile.py,
# 8 values a gathered row: 4.78 GB of temporaries for a panel of 21.7M
# slots, 220 B each; 159 at 2 values a row): the gathered byte rows at
# 128 B (a uint8 row of up to 128 elements fills 128 lanes), the 16-bit
# halves the MXU product makes of them (8 B a value of the row), the
# index and the select.
_TEMP_BYTES_A_SLOT = 224
# what a plan holds a slot whatever the panels: the compact tables
# (src8 4, lane 1, off 4, val 4) and the matvec's slot weights ``w`` (4)
TABLE_BYTES_A_SLOT = 13
RESIDENT_BYTES_A_SLOT = TABLE_BYTES_A_SLOT + 4
# and a slot of its hub chunks (idx 4, off 4, val 4): their ``w`` is
# made in VMEM, and they take no part in the panels
HUB_BYTES_A_SLOT = 12
# the share of the device's memory a panel's temporaries may take: the
# tables, ``w`` and the caller's vectors have the rest
_PANEL_SHARE = 0.25
# XLA tiles a panel's fusions by the factors of its row count: 9,007
# rows, a prime, made ``w``'s update walk a row at a time (11.7 ms a
# panel by the described-v5e compile's cycle estimate; 9,024 rows 0.47)
_PANEL_ROWS_MULTIPLE = 64


def _hbm_limit() -> int:
    """What one device hands out (core.mesh.hbm_limit_bytes: the smaller
    of the config's budget and the device's ``bytes_limit``)."""
    from jax.sharding import Mesh
    from matrel_tpu.core.mesh import hbm_limit_bytes
    return hbm_limit_bytes(Mesh(np.asarray(jax.devices()[:1]), ("x",)))


def panel_rows(rows: int, cap: int,
               a_slot: int = _TEMP_BYTES_A_SLOT) -> int:
    """How many table rows (blocks or chunks, ``cap`` slots each) a
    panel of the matvec takes (``a_slot`` bytes of temporaries a slot;
    the k-wide product's are its own): all of them where they stay
    under ``_PANEL_SHARE`` of the device's memory, else the rows spread
    evenly over the fewest panels that do (the last panel is moved back
    to end with the tables, so an uneven split would gather its overlap
    twice: 11.7% of a Graph500 scale-22 round, my chip run, PR 33), up
    to a multiple of ``_PANEL_ROWS_MULTIPLE`` where that still fits."""
    room = _PANEL_SHARE * _hbm_limit()
    most = max(1, int(room // (a_slot * cap)))
    if most >= rows:
        return rows
    per = -(-rows // -(-rows // most))
    rounded = -(-per // _PANEL_ROWS_MULTIPLE) * _PANEL_ROWS_MULTIPLE
    return rounded if rounded <= most else per


def resident_bytes(slots: int, hub_slots: int = 0) -> int:
    """What a compact plan holds of one device between matvecs and
    through them: its tables and ``w``, and its hub chunks' tables."""
    return RESIDENT_BYTES_A_SLOT * slots + HUB_BYTES_A_SLOT * hub_slots


def plan_bytes(rows: int, cap: int, hub_slots: int = 0) -> int:
    """What a compact plan of ``rows`` x ``cap`` slots, and ``hub_slots``
    in hub chunks beside them, holds of one device while a matvec runs:
    :func:`resident_bytes` and one panel's temporaries."""
    panel = panel_rows(rows, cap) * cap
    return resident_bytes(rows * cap, hub_slots) + _TEMP_BYTES_A_SLOT * panel


def _slot_weights(src8, lane, val, x: jax.Array) -> jax.Array:
    """``w = x[src8·8 + lane] · val``, a slot each, in panels of table
    rows where one gather over all of them would not fit."""
    xf = x.astype(jnp.float32)
    rows, cr, _ = src8.shape
    per = panel_rows(rows, cr * LANE)
    if per >= rows:
        idx = src8 * spmv_lib.WIDTH + lane.astype(jnp.int32)
        return spmv_lib.gather_1d(xf, idx) * val
    byte_rows = spmv_lib.byte_table(xf)

    def panel(i, w):
        # the last panel is moved back to end with the tables: the rows
        # it shares with the one before are written twice, the same
        at = jnp.minimum(i * per, rows - per)
        s8, ln, v = (jax.lax.dynamic_slice_in_dim(a, at, per)
                     for a in (src8, lane, val))
        idx = s8 * spmv_lib.WIDTH + ln.astype(jnp.int32)
        return jax.lax.dynamic_update_slice_in_dim(
            w, spmv_lib.gather_rows(byte_rows, idx, jnp.float32) * v, at, 0)

    return jax.lax.fori_loop(0, -(-rows // per), panel,
                             jnp.zeros(val.shape, jnp.float32))


def _hub_table(x: jax.Array, ids: jax.Array) -> jax.Array:
    """``x[ids]`` as the hub kernels hold it: a row of 128 hubs a row."""
    table = x.astype(jnp.float32).at[ids].get(
        mode="promise_in_bounds").reshape(-1, LANE)
    # up to whole walk steps: rows of zeros, which no slot names
    return jnp.pad(table, ((0, spmv_lib.hub_table_rows(
        table.shape[0]) - table.shape[0]), (0, 0)))


def compact_apply(plan_static, tables, ov, x: jax.Array,
                  passes: int = 3, interpret: bool = False) -> jax.Array:
    """Traceable body: y = A·x from compact tables. ``plan_static`` is
    (n_rows, n_cols, block, lo); ``tables`` from compact_tables() (five
    of them: the chunks layout; eleven: with hub chunks); ``ov`` the
    overflow COO tuple (possibly empty)."""
    n_rows, n_cols, block, lo = plan_static
    src8, lane, off, val, *chunks = tables
    rows, cr, _ = src8.shape
    w = _slot_weights(src8, lane, val, x)
    if chunks:
        chunk_block, *hub = chunks
        nb = -(-n_rows // block)
        y = _chunk_runner(rows, cr * LANE, nb, block, lo, passes,
                          interpret)(chunk_block, off, w)
        if hub:
            ids, idx, hub_off, hub_val, hub_block, walks = hub
            table = _hub_table(x, ids)
            y = _hub_runner(idx.shape[0], cr * LANE, nb, block, lo, passes,
                            table.shape[0], spmv_lib.HUB_WALK, interpret)(
                hub_block, walks, idx, hub_off, hub_val, table, y)
        y = y.reshape(-1)[:n_rows]
    else:
        scatter = _compact_runner(rows, cr * LANE, block, lo, passes,
                                  interpret)
        y = scatter(off, w).reshape(-1)[:n_rows]
    if ov:
        y = spmv_lib._overflow_add(y, ov, x, n_rows)
    return y


def reduce_apply(plan_static, tables, x: jax.Array, reduce: str,
                 interpret: bool = False) -> jax.Array:
    """Traceable body: ``y[i] = (max | min)(0, A[i, j] · x[j] over row
    i's entries)`` from the compact tables of a plan in chunks whose
    rows of 128 slots hold the slots of one destination row side by side
    (``spmv.rows_in_order``; five tables, or eleven with hub chunks, as
    :func:`compact_apply` takes them): the matvec's gather
    (:func:`_slot_weights`, in its panels) and the chunk grid's
    reduction (``matrel_spmv_reduce_chunks``) where the matvec adds, and
    then the hub chunks' slots, their weights off the table ``x[ids]``
    in VMEM, into the same tiles (``matrel_spmv_reduce_hubs``). Every
    product is one float32 multiply and no sum follows it, so ``y`` is
    what the dense ``A .* x`` would give, bit for bit."""
    n_rows, n_cols, block, lo = plan_static
    src8, lane, off, val, chunk_block, *hub = tables
    rows, cr, _ = src8.shape
    nb = -(-n_rows // block)
    w = _slot_weights(src8, lane, val, x)
    y = _reduce_runner(rows, cr * LANE, nb, block, lo, reduce, interpret)(
        chunk_block, off, w)
    if hub:
        ids, idx, hub_off, hub_val, hub_block, walks = hub
        table = _hub_table(x, ids)
        y = _reduce_hub_runner(idx.shape[0], cr * LANE, nb, block, lo,
                               reduce, table.shape[0], spmv_lib.HUB_WALK,
                               interpret)(
            hub_block, walks, idx, hub_off, hub_val, table, y)
    return y.reshape(-1)[:n_rows]


_compact_jitted = jax.jit(compact_apply, static_argnums=(0, 4, 5))  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)


# -- mesh-sharded ------------------------------------------------------------
# Unlike the executor's GSPMD programs (where pallas_call has no SPMD
# partitioning rule), shard_map hands the kernel per-device shapes, so
# the compact scatter runs unchanged on each device's slice of blocks:
# ~13 B/slot / P per device, one tiled all_gather of the result.


def shard_compact_tables(plan: spmv_lib.EdgeSpMVPlan, mesh):
    """Row-decompose the compact tables over every device of ``mesh``
    (block axis padded to the device count with sentinel slots).
    Memoised per (plan, mesh) — by mesh EQUALITY, matching the runner
    cache, so rebuilding an equal Mesh per call reuses the transfer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spmv_lib._blocks_layout_only(plan, "the sharded compact tables")
    memo = getattr(plan, "_compact_sharded", None)
    if memo is None:
        memo = {}
        plan._compact_sharded = memo
    dev = memo.get(mesh)
    if dev is not None:
        return dev
    nb, cap = np.asarray(plan.src8).shape
    if cap % LANE:
        raise ValueError(f"capacity {cap} not a multiple of {LANE}")
    p = mesh.size
    nb_pad = -(-nb // p) * p
    pad = nb_pad - nb
    fills = spmv_lib.compact_pad_fills(plan.n_cols)

    def padded(a, fill, dtype):
        a = np.asarray(a)
        if pad:
            a = np.concatenate(
                [a, np.full((pad, cap), fill, a.dtype)])
        return a.reshape(nb_pad, cap // LANE, LANE).astype(dtype)

    sh = NamedSharding(mesh, P(tuple(mesh.axis_names), None, None))
    # eager even when called from inside a trace (the executor's
    # Lowerer): the memo must hold COMMITTED arrays, not tracers — a
    # cached tracer would escape its trace and poison every later
    # compile that reuses this plan on the same mesh
    with jax.ensure_compile_time_eval():
        dev = (jax.device_put(padded(plan.src8, fills["src8"], np.int32),
                              sh),
               jax.device_put(padded(plan.lane, fills["lane"], np.int8),
                              sh),
               jax.device_put(padded(plan.off, fills["off"], np.int32),
                              sh),
               jax.device_put(padded(plan.val, fills["val"], np.float32),
                              sh))
    memo[mesh] = dev
    return dev


def _compact_sharded_body(apply_fn, overflow_fn, plan_static, tables,
                          ov, x, axes, passes, interpret) -> jax.Array:
    """Shared shard-local sequence: per-device compact apply on this
    device's block-row slice → tiled all_gather → slice padding →
    replicated-overflow add."""
    n_rows, n_cols, block, lo = plan_static
    src8 = tables[0]
    y_loc = apply_fn(
        (src8.shape[0] * block, n_cols, block, lo), tables, (), x,
        passes, interpret)
    y = jax.lax.all_gather(y_loc, axes, axis=0, tiled=True)[:n_rows]
    if ov:
        y = overflow_fn(y, ov, x, n_rows)
    return y


def compact_sharded_apply(plan_static, tables, ov, x, axes,
                          passes: int = 3,
                          interpret: bool = False) -> jax.Array:
    """Per-device sharded compact matvec — call INSIDE a shard_map over
    ``axes``: ``tables`` arrive as this device's block slice, x
    replicated; one tiled all_gather assembles the result; overflow COO
    is replicated and added after the gather. Shared by the standalone
    runner here and pagerank's power-iteration loop."""
    return _compact_sharded_body(compact_apply, spmv_lib._overflow_add,
                                 plan_static, tables, ov, x, axes,
                                 passes, interpret)


def compact_sharded_matmat_apply(plan_static, tables, ov, X, axes,
                                 passes: int = 3,
                                 interpret: bool = False) -> jax.Array:
    """The k-wide sibling of compact_sharded_apply (Y = A·X inside a
    shard_map). Lets the executor keep the 13 B/slot tables on every
    mesh size instead of falling back to the expanded XLA tables."""
    return _compact_sharded_body(compact_matmat_apply,
                                 spmv_lib._overflow_add_wide,
                                 plan_static, tables, ov, X, axes,
                                 passes, interpret)


def compact_sharded_specs(axes, n_ov: int):
    """shard_map in_specs for (tables..., x, overflow...)."""
    from jax.sharding import PartitionSpec as P
    return (P(axes, None, None),) * 4 + (P(),) + (P(),) * n_ov


@functools.lru_cache(maxsize=32)
def _compact_sharded_runner(plan_static, mesh, passes: int, n_ov: int,
                            interpret: bool):
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def kernel(src8, lane, off, val, x, *ov):
        return compact_sharded_apply(plan_static,
                                     (src8, lane, off, val), ov, x,
                                     axes, passes, interpret)

    return jax.jit(shard_map(kernel, mesh=mesh,  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
                             in_specs=compact_sharded_specs(axes, n_ov),
                             out_specs=P(), check_vma=False))


def _resolve_interpret(interpret) -> bool:
    """None → config (the shared resolver in config.py)."""
    from matrel_tpu.config import resolve_interpret
    return resolve_interpret(interpret)


def spmv_compact_sharded(plan: spmv_lib.EdgeSpMVPlan, x: jax.Array,
                         mesh, passes: int = 3,
                         interpret=None) -> jax.Array:
    """y = A·x with compact tables sharded over ``mesh``."""
    interpret = _resolve_interpret(interpret)
    tables = shard_compact_tables(plan, mesh)
    ov = plan.overflow
    run = _compact_sharded_runner(
        (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO), mesh,
        passes, len(ov), interpret)
    return run(*tables, jnp.asarray(x, jnp.float32), *ov)


# -- k-wide (SpMM) -----------------------------------------------------------
# Y = A·X for a dense X of k columns. A slot's whole row of X is fetched
# ONCE (XLA's row gather: a row of up to 128 float32 fills 128 lanes
# whatever it holds, so 128 columns cost what 8 do), in panels of table
# rows as the matvec's ``w``, and one chunk-grid kernel scatters a panel:
# per 128 slots a one-hot of their destination rows, exact in bfloat16,
# times the slots' rows carved into ``passes`` bfloat16 parts — plain MXU
# products, the block's (block, 128) tile resident over its consecutive
# chunks. ``val`` multiplies inside the kernel: XLA does not fuse a
# multiply into its gather, and a pass of its own over the gathered rows
# is 1 KB a slot of HBM traffic (PERF.md §6, PR 37). Both layouts take
# this one kernel: a blocks-layout row is walked as ``capacity / tile``
# chunks of its block.
#
# The one-hot's height follows the rows a chunk really holds (PR 38; PR
# 49: from a ladder of heights). ``wide_windows`` reads every chunk table
# once on the host (``spmv.chunk_windows``): ``win[c]`` names the
# shortest rung of ``spmv.WINDOWS`` (128, 256 rows; those below the
# block) that holds the chunk's real slots and the first of its rows (a
# multiple of 8, the rung's index in the low bits), the one-hot is
# (rung, 128) and the sums add into rows ``start : start + rung`` of the
# tile: at 128 rows a quarter, at 256 a half of the MXU pushes, result
# pops and adds of the (block, 128) one-hot at block 512, the same three
# parts, the same float32 accumulation. Where no rung holds them
# ``win[c]`` is −1 and the chunk takes the whole block's one-hot. ``win``
# rides as a scalar-prefetch operand beside ``chunk_block`` and the
# kernel holds a body a height under ``pl.when`` (``_by_window``);
# nothing chooses but the tables. The chunks fill lays a block's slots
# in row order (native/spmv_plan.cc), so 2,048 slots name a few rows (a
# Netflix user holds 209 ratings, a movie 5,654; the residual beside a
# slab 16, so a chunk spans ~125 rows and 45% of them need the second
# rung); a plan in input order reads −1 throughout and runs the body it
# always ran.
#
# A plan may bring a DENSE PART beside its compact parts (PR 43,
# core.coo.DenseLines): the lines of one axis that hold more entries than
# a dense line costs lie in one slab on the device and not in the tables.
# ``_dense_part`` adds their product on the MXU; gather and scatter, which
# cost by the entry, see what is left.

#
# A SAMPLED product (ir/expr.py ``sampled``, PR 46) is the same product
# over values that are made on the way: an entry's value is the matrix's
# own ``op`` (divided by, or times) the dot of the two 128-wide rows its
# coordinates name in the factors of a dense product, so ``(S ./ (A·B))``
# is never stored. Its compact parts run through a kernel of their own
# (PR 47, ``matrel_sampled_scatter_chunks``): the scatter above plus, a
# sub-row of 128 slots, the destination factor's rows of the slots off
# the BLOCK TILE of that factor, which rides beside the block sums by
# the same index map (the one-hot, transposed, times the tile: a chunk's
# slots all land in one block, most within a window of it), the entry's
# dot with the source's row (the gathered rows the scatter multiplies
# anyway where the product's dense side IS that factor, else a second
# rows operand) and ``val op dot``. XLA gathers a slot's source row and
# nothing else. The dense part makes a panel of the slab's quotient on
# the MXU and multiplies it at once (``_sampled_dense_part``).

WIDE_COLS = LANE        # columns a pass of the k-wide product takes
# What a panel of the k-wide product keeps alive a slot, read off the
# described-v5e compile at the Netflix shape (tests/test_chip_compile.py:
# 2.66 GB of temporaries for a panel of 4.19M slots, of which 0.49 GB are
# two copies of the 246 MB output): the gathered row (512 B), the index
# and the panel's slices of the tables.
_TEMP_BYTES_A_SLOT_WIDE = 4 * WIDE_COLS + 24


# and of a sampled product whose scatter's own rows are not the source
# factor's: that factor's row beside them (the destination's rows, the
# dot and the quotient live in the kernel)
_TEMP_BYTES_A_SLOT_SAMPLED = 4 * WIDE_COLS


def _wide_sums(off, val, g_ref, height: int, passes: int):
    """(height, 128) float32: the chunk's slots' rows of X times ``val``,
    summed by destination row ``off`` (cr, 128) over rows 0..height; a
    slot whose ``off`` lies outside them adds nothing."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (height, LANE), 0)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1))
    acc = jnp.zeros((height, WIDE_COLS), jnp.float32)
    for s in range(off.shape[0]):
        oh = (off[s:s + 1, :] == rows).astype(jnp.bfloat16)
        # the slots' values from the lanes onto the sublanes:
        # a diagonal select and a lane sum of one term, exact
        col = jnp.sum(jnp.where(eye, val[s:s + 1, :], 0.0),
                      axis=1, keepdims=True)                 # (128, 1)
        w = g_ref[0, s * LANE:(s + 1) * LANE, :] * col
        for part in _bf16_split(w, passes):
            # one MXU pass a part, whatever matmul precision the
            # caller's context asks of its own float32 dots
            acc = acc + jnp.dot(oh, part.astype(jnp.bfloat16),
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
    return acc


def _by_window(block: int, live, win, add) -> None:
    """``add(at, height)`` under the ``pl.when`` that ``win``
    (``spmv.chunk_windows``: start + rung, or −1) chooses for a live
    chunk: one body a rung of the ladder below ``block`` — the chunk's
    rows lie in that window of the tile from row ``at``, its one-hot is
    that tall — and the whole block's (``at`` None) where no rung holds
    them."""
    for rung, height in enumerate(spmv_lib.WINDOWS):
        if height < block:
            @pl.when(live & (win >= 0) & (win & 7 == rung))
            def _(rung=rung, height=height):
                add(pl.multiple_of(win - rung, 8), height)

    @pl.when(live & (win < 0))
    def _():
        add(None, block)


def _make_wide_scatter_kernel(block: int, passes: int):
    def kernel(cb_ref, skip_ref, win_ref, off_ref, val_ref, g_ref, acc_ref,
               y_ref):
        # the block's tile starts from what the panels and parts before
        # this call summed (``acc``, which the output aliases)
        @pl.when(_first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = acc_ref[...]

        def add(at, height):
            if at is None:
                y_ref[0] += _wide_sums(off_ref[0], val_ref[0], g_ref,
                                       height, passes)
            else:
                y_ref[0, pl.ds(at, height), :] += _wide_sums(
                    off_ref[0] - at, val_ref[0], g_ref, height, passes)

        # the last panel is moved back to end with the tables: the
        # chunks it shares with the one before are not added twice
        c = pl.program_id(0)
        _by_window(block, c >= skip_ref[0], win_ref[c], add)

    return kernel


@functools.lru_cache(maxsize=32)
def _wide_runner(n_chunks: int, chunk: int, nb: int, block: int,
                 passes: int, interpret: bool):
    """scatter(chunk_block, skip, win, off, val, rows, acc) -> acc + the
    chunks' block sums, (nb, block, 128): ``rows`` are the slots'
    gathered rows of X, (n_chunks, chunk, 128); the first ``skip[0]``
    chunks add nothing; ``win`` as :func:`wide_windows` gives it."""
    cr = chunk // LANE
    slots = pl.BlockSpec((1, cr, LANE), lambda c, cb, skip, win: (c, 0, 0))
    sums = pl.BlockSpec((1, block, WIDE_COLS),
                        lambda c, cb, skip, win: (cb[c], 0, 0))
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel, unported to the registry this round (autotuned via the spmv| table rows)
        _make_wide_scatter_kernel(block, passes),
        name="matrel_spmm_scatter_chunks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                  # chunk_block, skip, win
            grid=(n_chunks,),
            in_specs=[slots, slots,
                      pl.BlockSpec((1, chunk, WIDE_COLS),
                                   lambda c, cb, skip, win: (c, 0, 0)),
                      sums],
            out_specs=sums,
        ),
        out_shape=jax.ShapeDtypeStruct((nb, block, WIDE_COLS), jnp.float32),
        input_output_aliases={6: 0},                     # acc
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _sampled_sums(off, val, g_ref, m_ref, tile, op: str, passes: int):
    """:func:`_wide_sums` of a sampled product, (height, 128) float32
    over the rows of ``tile``, the chunk's block (or window) of the
    destination factor: a slot's value is ``val op`` the dot of its
    source's row (``m_ref``'s; ``g_ref``'s own where None) and its
    destination's. In two rounds over the sub-rows of 128 slots, so that
    each keeps the MXU fed (one round a sub-row read 6,805 bundles a
    windowed step on the described v5e, two read 3,415): first every
    sub-row's destination rows — its one-hot, TRANSPOSED, times the
    tile's three bfloat16 parts, which is the float32 row again
    whatever ``passes`` says — and their dots, a lane sum, moved from
    the sublanes onto the lanes where ``val`` lies; then ONE ``val op
    dot`` for the chunk; then the scatter that :func:`_wide_sums`
    makes, a sub-row's quotient moved back onto the sublanes as it
    moves ``val``."""
    height = tile.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (height, LANE), 0)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1))

    def dot(a, b, ca):
        # one MXU pass a part, ``a`` contracted over its dimension ``ca``
        return jax.lax.dot_general(
            a, b.astype(jnp.bfloat16), (((ca,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

    def rows_of(ref, s):
        return ref[0, s * LANE:(s + 1) * LANE, :]

    # split once a step, not once a sub-row
    parts = _bf16_split(tile, 3)
    ohs, dots = [], []
    for s in range(off.shape[0]):
        oh = (off[s:s + 1, :] == rows).astype(jnp.bfloat16)
        theirs = sum(dot(oh, part, 0) for part in parts)     # (128, 128)
        d = jnp.sum(rows_of(g_ref if m_ref is None else m_ref, s) * theirs,
                    axis=1, keepdims=True)                    # (128, 1)
        # a diagonal select and a sum of one term, exact
        dots.append(jnp.sum(jnp.where(eye, d, 0.0), axis=0, keepdims=True))
        ohs.append(oh)
    q = sampled_values(op, val, jnp.concatenate(dots, axis=0))
    acc = jnp.zeros((height, WIDE_COLS), jnp.float32)
    for s, oh in enumerate(ohs):
        col = jnp.sum(jnp.where(eye, q[s:s + 1, :], 0.0),
                      axis=1, keepdims=True)                  # (128, 1)
        for part in _bf16_split(rows_of(g_ref, s) * col, passes):
            acc = acc + dot(oh, part, 1)
    return acc


def _make_sampled_scatter_kernel(block: int, passes: int, op: str,
                                 shared: bool):
    """:func:`_make_wide_scatter_kernel` for a sampled product: a slot's
    value is made here. The chunk's block of the destination factor
    rides beside the block sums (``dst_ref``, the same index map)."""
    def kernel(cb_ref, skip_ref, win_ref, off_ref, val_ref, g_ref, *refs):
        m_ref = None if shared else refs[0]
        dst_ref, acc_ref, y_ref = refs[-3:]

        @pl.when(_first_chunk_of_its_block(cb_ref))
        def _():
            y_ref[...] = acc_ref[...]

        def add(at, height):
            if at is None:
                y_ref[0] += _sampled_sums(off_ref[0], val_ref[0], g_ref,
                                          m_ref, dst_ref[0], op, passes)
            else:
                y_ref[0, pl.ds(at, height), :] += _sampled_sums(
                    off_ref[0] - at, val_ref[0], g_ref, m_ref,
                    dst_ref[0, pl.ds(at, height), :], op, passes)

        c = pl.program_id(0)
        _by_window(block, c >= skip_ref[0], win_ref[c], add)

    return kernel


@functools.lru_cache(maxsize=32)
def _sampled_runner(n_chunks: int, chunk: int, nb: int, block: int,
                    passes: int, op: str, shared: bool, interpret: bool):
    """scatter(chunk_block, skip, win, off, val, rows[, source rows],
    dst, acc) -> acc + the chunks' block sums of a sampled product:
    :func:`_wide_runner`'s operands, the slots' rows of the source
    factor where they are not ``rows`` themselves, and ``dst`` (nb,
    block, 128), the destination factor laid out as the sums are."""
    cr = chunk // LANE
    slots = pl.BlockSpec((1, cr, LANE), lambda c, cb, skip, win: (c, 0, 0))
    rows = pl.BlockSpec((1, chunk, WIDE_COLS),
                        lambda c, cb, skip, win: (c, 0, 0))
    sums = pl.BlockSpec((1, block, WIDE_COLS),
                        lambda c, cb, skip, win: (cb[c], 0, 0))
    n_rows = 1 if shared else 2
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMV scatter kernel, unported to the registry this round (autotuned via the spmv| table rows)
        _make_sampled_scatter_kernel(block, passes, op, shared),
        name="matrel_sampled_scatter_chunks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                  # chunk_block, skip, win
            grid=(n_chunks,),
            in_specs=[slots, slots] + [rows] * n_rows + [sums, sums],
            out_specs=sums,
        ),
        out_shape=jax.ShapeDtypeStruct((nb, block, WIDE_COLS), jnp.float32),
        input_output_aliases={6 + n_rows: 0},            # acc
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _wide_slot_bytes(sampled_rows: int) -> int:
    """A panel's temporaries a slot: the k-wide product's own, and a
    sampled product's ``sampled_rows`` (0 or 1) further gathered rows."""
    return (_TEMP_BYTES_A_SLOT_WIDE
            + sampled_rows * _TEMP_BYTES_A_SLOT_SAMPLED)


def wide_panel_rows(rows: int, cap: int, sampled_rows: int = 0) -> int:
    """:func:`panel_rows` for the k-wide product, whose panel holds a
    slot's whole gathered row (a sampled product's: ``sampled_rows``
    more of them)."""
    return panel_rows(rows, cap, _wide_slot_bytes(sampled_rows))


def wide_panel_bytes(rows: int, cap: int, sampled_rows: int = 0) -> int:
    """One panel's temporaries of the k-wide product over a plan of
    ``rows`` x ``cap`` slots."""
    return (_wide_slot_bytes(sampled_rows)
            * wide_panel_rows(rows, cap, sampled_rows) * cap)


def wide_plan_bytes(rows: int, cap: int) -> int:
    """What a compact plan of ``rows`` x ``cap`` slots holds of one
    device while a k-wide product runs: its tables and one panel's
    temporaries (the output and the dense side are the caller's)."""
    return TABLE_BYTES_A_SLOT * rows * cap + wide_panel_bytes(rows, cap)


def _walk(cr: int) -> int:
    """Rows of 128 slots a chunk takes of a blocks-layout row of ``cr``:
    the largest divisor of ``cr`` that keeps a chunk within
    ``spmv.CHUNK`` slots."""
    most = spmv_lib.CHUNK // LANE
    return next(d for d in range(min(cr, most), 0, -1) if cr % d == 0)


def _as_chunks(src, off, val, chunk_block):
    """The tables as the chunk kernel walks them. A blocks-layout row of
    ``cr`` x 128 slots becomes ``cr / d`` chunks of its block (``d``:
    :func:`_walk`): the same memory, read as more rows."""
    if chunk_block is not None:
        return src, off, val, chunk_block
    rows, cr, _ = off.shape
    d = _walk(cr)
    shp = (rows * (cr // d), d, LANE)
    return (src.reshape(shp), off.reshape(shp), val.reshape(shp),
            jnp.asarray(np.repeat(np.arange(rows, dtype=np.int32), cr // d)))


def wide_windows(plan: spmv_lib.EdgeSpMVPlan):
    """(``win`` of every chunk table the k-wide kernel walks of this
    plan — its own as :func:`_as_chunks` reads them, then its hub
    chunks' — as device arrays; how many chunks have a window of each
    height of ``spmv.WINDOWS``, {height: chunks}), reckoned once from
    the host tables (``spmv.chunk_windows``) and memoised on the plan. A
    padded slot is known by its sentinel source, which reads the zero
    row."""
    memo = getattr(plan, "_wide_win", None)
    if memo is None:
        off = np.asarray(plan.off)
        real = ~((np.asarray(plan.src8) == plan.n_cols // spmv_lib.WIDTH)
                 & (np.asarray(plan.lane) == plan.n_cols % spmv_lib.WIDTH))
        if plan.chunk_block is None:
            shp = (-1, _walk(off.shape[1] // LANE) * LANE)
            off, real = off.reshape(shp), real.reshape(shp)
        wins = [spmv_lib.chunk_windows(off, real, plan.block)]
        if plan.hubs is not None:
            hub = plan.hubs
            wins.append(spmv_lib.chunk_windows(
                hub.off, hub.idx < hub.ids.size, plan.block))
        # committed arrays, not tracers (see compact_tables)
        with jax.ensure_compile_time_eval():
            dev = tuple(jnp.asarray(w) for w in wins)
        tall = np.concatenate([spmv_lib.window_of(w)[1] for w in wins])
        memo = plan._wide_win = (dev, {
            h: int(np.count_nonzero(tall == h)) for h in spmv_lib.WINDOWS})
    return memo


def _chunk_sets(tables, n_cols: int, wins=None):
    """compact_tables() of either layout as the sets of chunk tables
    ``(src, off, val, chunk_block, win)`` the k-wide kernel walks: the
    plan's own, and its hub chunks' where it has any. ``wins`` from
    :func:`wide_windows`; without them (tables that are a device's slice
    inside a shard_map) no chunk has a window."""
    src8, lane, off, val, *chunks = tables
    chunk_block, *hub = chunks if chunks else (None,)
    src = src8 * spmv_lib.WIDTH + lane.astype(jnp.int32)
    sets = [_as_chunks(src, off, val, chunk_block)]
    if hub:
        # a hub slot names its source by rank; the padded slots' rank,
        # one past the last hub, reads the zero row
        ids, idx, hub_off, hub_val, hub_block, _ = hub
        ids = jnp.concatenate([ids, jnp.full((1,), n_cols, ids.dtype)])
        sets.append((ids[idx], hub_off, hub_val, hub_block))
    if wins is None:
        wins = [jnp.full(cb.shape, -1, jnp.int32) for *_, cb in sets]
    return [st + (win,) for st, win in zip(sets, wins)]


def _in_panels(rows: int, per: int, panel, y):
    """``panel(i, y)`` over ``rows`` table rows, ``per`` a panel."""
    if per >= rows:
        return panel(jnp.int32(0), y)
    return jax.lax.fori_loop(0, -(-rows // per), panel, y)


def _wide_accumulate(y, sets, X, block: int, passes: int, interpret: bool):
    """``y`` (nb, block, 128) plus the block sums of every set of chunk
    tables against the rows of ``X`` (the set's columns and a zero row
    for the padded slots, 128 wide), a panel of chunks at a time."""
    nb = y.shape[0]
    for src, off, val, cb, win in sets:
        rows, cr, _ = off.shape
        chunk = cr * LANE
        per = wide_panel_rows(rows, chunk)
        run = _wide_runner(per, chunk, nb, block, passes, interpret)

        def panel(i, y):
            at = jnp.minimum(i * per, rows - per)
            s, o, v, c, w = (jax.lax.dynamic_slice_in_dim(a, at, per)
                             for a in (src, off, val, cb, win))
            g = X.at[s.reshape(-1)].get(mode="promise_in_bounds")
            skip = jnp.reshape(i * per - at, (1,)).astype(jnp.int32)
            return run(c, skip, w, o, v, g.reshape(per, chunk, WIDE_COLS),
                       y)

        y = _in_panels(rows, per, panel, y)
    return y


def _sampled_accumulate(y, sets, X, block: int, passes: int,
                        interpret: bool, op: str, of_src, of_dst):
    """:func:`_wide_accumulate` of a sampled product: a slot's value is
    the table's ``op`` the dot of its source's row of ``of_src`` (laid
    out as ``X`` is; None: ``X`` itself, one gather serves the dot and
    the scatter) and its destination's row of ``of_dst`` (nb, block,
    128), which the kernel takes off the block tile the chunk adds into:
    no gather reads the destination's factor."""
    nb = y.shape[0]
    for src, off, val, cb, win in sets:
        rows, cr, _ = off.shape
        chunk = cr * LANE
        per = wide_panel_rows(rows, chunk, 0 if of_src is None else 1)
        run = _sampled_runner(per, chunk, nb, block, passes, op,
                              of_src is None, interpret)

        def panel(i, y):
            at = jnp.minimum(i * per, rows - per)
            s, o, v, c, w = (jax.lax.dynamic_slice_in_dim(a, at, per)
                             for a in (src, off, val, cb, win))
            gathered = [t.at[s.reshape(-1)].get(mode="promise_in_bounds")
                        .reshape(per, chunk, WIDE_COLS)
                        for t in (X, of_src) if t is not None]
            skip = jnp.reshape(i * per - at, (1,)).astype(jnp.int32)
            return run(c, skip, w, o, v, *gathered, of_dst, y)

        y = _in_panels(rows, per, panel, y)
    return y


def _dense_part(Y, role: str, slab, lines, X):
    """``Y`` plus a plan's dense part (core.coo.DenseLines: ``slab``
    (n, lines up to whole groups of 128), column j the line
    ``lines[j]``) times ``X``: where the lines are the product's
    ``"sources"``, ``slab @ X[lines]``; where its ``"destinations"``,
    ``slabᵀ · X`` added into the rows ``lines``, the slab read as it
    lies. On the MXU with float32 sums, a long contraction in panels
    (``strategies.dot_in_panels``: one dot over 480k rows drifts): a
    float32 slab at ``highest`` whatever the caller's config asks of its
    own dots (the compact part is float32-faithful whatever it asks); a
    bfloat16 slab, which holds its values exactly, against the dense
    side's three bfloat16 parts side by side — the passes of ``highest``
    that are not zero, in one reading of the slab."""
    from matrel_tpu.config import default_config
    from matrel_tpu.parallel import strategies

    def dot(a, ca, b):
        # contracted over ``a``'s dimension ``ca`` and ``b``'s rows
        if a.shape[ca] >= strategies.LONG_CONTRACTION:
            return strategies.dot_in_panels(a, ca, b, 0, dataclasses.replace(
                default_config(), matmul_precision="highest"))
        return jax.lax.dot_general(
            a, b, (((ca,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def times(ca, b):
        if slab.dtype == jnp.float32:
            return dot(slab, ca, b)
        k = b.shape[1]
        parts = dot(slab, ca, jnp.concatenate(
            [p.astype(jnp.bfloat16) for p in _bf16_split(b, 3)], axis=1))
        return parts[:, :k] + parts[:, k:2 * k] + parts[:, 2 * k:]

    Xf = X.astype(jnp.float32)
    n = lines.shape[0]
    if role == "sources":
        mine = Xf.at[lines].get(mode="promise_in_bounds")
        return Y + times(1, jnp.pad(mine, ((0, slab.shape[1] - n), (0, 0))))
    return Y.at[lines].add(times(0, Xf)[:n], indices_are_sorted=True,
                           unique_indices=True, mode="promise_in_bounds")


def compact_matmat_parts(plan_static, part_statics, part_arrays,
                         X: jax.Array, passes: int = 3,
                         interpret: bool = False) -> jax.Array:
    """Traceable body: Y = A·X for dense X (n_cols, k), A given as one
    compact plan a range of its columns (COOMatrix's source panels; a
    plan whole is one part at 0), every one adding onto the same block
    sums: ``part_statics`` = ((col0, its plan_static), ...) and
    ``part_arrays`` = ((its compact_tables(), its overflow, its
    wide_windows()[0] or None), ...). More than 128 columns run 128 at a
    time. A plan's dense part rides as a last part, (role, None) and
    (slab, lines), and adds :func:`_dense_part`; ``passes`` governs the
    compact parts."""
    dense = None
    if part_statics and isinstance(part_statics[-1][0], str):
        dense = (part_statics[-1][0],) + tuple(part_arrays[-1])
        part_statics, part_arrays = part_statics[:-1], part_arrays[:-1]
    n_rows, _, block, _ = plan_static
    nb = -(-n_rows // block)
    k = X.shape[1]
    Xf = X.astype(jnp.float32)
    outs = []
    for j0 in range(0, k, WIDE_COLS):
        kc = min(WIDE_COLS, k - j0)
        y = jnp.zeros((nb, block, WIDE_COLS), jnp.float32)
        for (col0, (_, n_cols, _, _)), (tables, _, wins) in zip(
                part_statics, part_arrays):
            # padded slots (src == n_cols) read a zero row; the columns
            # are padded to the lanes a gathered row fills anyway
            Xp = jnp.pad(Xf[col0:col0 + n_cols, j0:j0 + kc],
                         ((0, spmv_lib.WIDTH), (0, WIDE_COLS - kc)))
            y = _wide_accumulate(y, _chunk_sets(tables, n_cols, wins), Xp,
                                 block, passes, interpret)
        outs.append(y.reshape(-1, WIDE_COLS)[:n_rows, :kc])
    Y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    for (col0, (_, n_cols, _, _)), (_, ov, _) in zip(part_statics,
                                                     part_arrays):
        if ov:
            Y = spmv_lib._overflow_add_wide(Y, ov, X[col0:col0 + n_cols],
                                            n_rows)
    return Y if dense is None else _dense_part(Y, *dense, X)


def sampled_values(op: str, s, d):
    """``s op d`` as the executor's element-wise node gives it where
    ``s`` is a sparse matrix's values: ``s * d``, or ``s / d`` with
    ``x / 0 = 0`` (and so ``0 / 0 = 0``: a padded slot, a cell the slab
    does not hold)."""
    if op == "mul":
        return s * d
    return jnp.where(d == 0, jnp.zeros((), s.dtype),
                     s / jnp.where(d == 0, jnp.ones((), d.dtype), d))


def lines_rows(t, lines, width: int):
    """The rows of ``t`` that a slab's ``lines`` name, in the slab's
    column order, zero rows up to its ``width``."""
    return jnp.pad(t.at[lines].get(mode="promise_in_bounds"),
                   ((0, width - lines.shape[0]), (0, 0)))


def _sampled_dense_part(Y, role: str, slab, lines, Z, op: str, of_src,
                        of_dst, inner: int, interpret: bool):
    """``Y`` (destinations, k) plus the dense lines' share of a sampled
    product (:func:`sampled_matmat_parts`): the dense product at the
    slab's cells on the MXU, ``D = P · R[lines]ᵀ`` (``P`` the factor
    whose rows the slab's rows name, ``R`` the one its lines name), the
    sampled values ``Q = slab op D`` where the slab holds an entry (the
    slab is the STRUCTURE here: its zeros are the cells without one), and
    ``Q``'s product at once — where the lines are the product's
    ``"sources"``, ``Y += Q · Z[lines]``; where its ``"destinations"``,
    ``Y[lines] += Qᵀ · Z``. Every dot is float32 at ``highest`` whatever
    the slab's dtype: the quotient is no bfloat16's, so a product costs
    six MXU passes where the slab's own values cost three. ``Z``,
    ``of_src`` and ``of_dst`` come 128 lanes wide, zero past ``Z``'s k
    and the factors' ``inner`` columns.

    Who multiplies is ``sampled_lines.plan``'s to say from the shapes
    (core.coo.sampled_facts says the same of the plan: ``lines_by``).
    The kernel ``matrel_sampled_lines`` (ops/sampled_lines.py), a row
    tile of the slab a grid step: ``D`` and ``Q`` exist only in VMEM;
    what leaves it is ``Y``'s row tile, written where ``Y`` lies
    (``"sources"``), or the ``(width, 128)`` sums once at the grid's end
    (``"destinations"``); the ragged last tile is the kernel's own, its
    rows past the slab's end masked. Else :func:`_sampled_lines_xla`, the
    loop of panels it replaces."""
    from matrel_tpu.ops import sampled_lines
    k = Y.shape[1]
    how = sampled_lines.plan(slab.shape[1], slab.dtype.itemsize)
    if how["lines_by"] != "kernel":
        return _sampled_lines_xla(Y, role, slab, lines, Z[:, :k], op,
                                  of_src[:, :inner], of_dst[:, :inner])
    P, R = (of_dst, of_src) if role == "sources" else (of_src, of_dst)
    out = sampled_lines.sampled_lines(
        jnp.pad(Y, ((0, 0), (0, WIDE_COLS - k))), role, slab, lines, Z, op,
        P, R, tile=how["panel_rows"], interpret=interpret)
    return out[:, :k]


def _sampled_lines_xla(Y, role: str, slab, lines, Z, op: str, of_src,
                       of_dst):
    """:func:`_sampled_dense_part` as an XLA loop, a panel of
    ``strategies.ACC_PANEL_ROWS`` rows of the slab at a time: ``D = P_p ·
    R[lines]ᵀ`` and ``Q`` are one fusion, ``Q``'s product the next, so
    the panel's ``(rows, width)`` float32 quotient is written to HBM by
    the first and read by the second; ``"sources"`` adds into ``Y``'s
    panel of rows, ``"destinations"`` sums ``Qᵀ · Z_p`` over the panels
    on the vector unit; the ragged tail is a last, shorter panel. Where
    the kernel's tiles do not fit, and the tests' second opinion."""
    from matrel_tpu.parallel import strategies
    n, width = lines.shape[0], slab.shape[1]
    along = role == "sources"
    P, R = (of_dst, of_src) if along else (of_src, of_dst)

    def dot(a, ca, b, cb):
        return jax.lax.dot_general(
            a, b, (((ca,), (cb,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    RL = lines_rows(R, lines, width)

    def quotient(start, rows):
        cells = jax.lax.dynamic_slice_in_dim(slab, start, rows) \
            .astype(jnp.float32)
        return sampled_values(op, cells, dot(
            jax.lax.dynamic_slice_in_dim(P, start, rows), 1, RL, 1))

    def over_panels(step, carry):
        """``step(start, rows, carry)`` over the slab's rows, a panel a
        round of a ``fori_loop`` and the ragged tail after it."""
        per = strategies.ACC_PANEL_ROWS
        whole, tail = divmod(slab.shape[0], per)
        if whole:
            carry = jax.lax.fori_loop(
                0, whole, lambda i, c: step(i * per, per, c), carry)
        return step(whole * per, tail, carry) if tail else carry

    if along:
        ZL = lines_rows(Z, lines, width)

        def step(start, rows, Y):
            mine = jax.lax.dynamic_slice_in_dim(Y, start, rows)
            return jax.lax.dynamic_update_slice_in_dim(
                Y, mine + dot(quotient(start, rows), 1, ZL, 0), start, 0)

        return over_panels(step, Y)
    sums = over_panels(
        lambda start, rows, acc: acc + dot(
            quotient(start, rows), 0,
            jax.lax.dynamic_slice_in_dim(Z, start, rows), 0),
        jnp.zeros((width, Z.shape[1]), jnp.float32))
    return Y.at[lines].add(sums[:n], indices_are_sorted=True,
                           unique_indices=True, mode="promise_in_bounds")


def sampled_matmat_parts(plan_static, part_statics, part_arrays,
                         Z: jax.Array, op: str, of_src, of_dst,
                         passes: int = 3,
                         interpret: bool = False) -> jax.Array:
    """Traceable body: ``(A op (P·R)) · Z`` for the matrix ``A`` of a
    plan (its operands as :func:`compact_matmat_parts` takes them,
    :func:`plan_operands`), a dense ``Z`` (n_cols, k <= 128) and a
    dense product of which only A's entries are wanted: entry (i, j) is
    A's value ``op`` ("div" / "mul") the dot of ``of_dst[i]`` and
    ``of_src[j]``, the rows of the product's two factors (n_rows x k'
    and n_cols x k', k' <= 128) that the entry's coordinates name.
    ``of_src`` None says that it is ``Z`` itself. The compact parts
    gather a slot's source rows, panel by panel, and scatter through
    the sampled kernel (``matrel_sampled_scatter_chunks``), which takes
    the destination's rows off the block tile it adds into, makes the
    entry's dot and its value, and adds (``passes`` 3: float32-faithful,
    and what the executor runs; they are the parts of the scatter's
    contributions, the dot is float32 whatever they say); the dense
    part is :func:`_sampled_dense_part`, float32 at ``highest``
    whatever ``passes`` says: ONE kernel over the slab where its tiles
    fit VMEM (``matrel_sampled_lines``: the lines' sampled values never
    leave VMEM), else XLA's loop of panels, which writes a panel of them
    to HBM between its two dots; overflow entries go by the scalar path
    with the same values. The sampled values are never stored whole."""
    dense = None
    if part_statics and isinstance(part_statics[-1][0], str):
        dense = (part_statics[-1][0],) + tuple(part_arrays[-1])
        part_statics, part_arrays = part_statics[:-1], part_arrays[:-1]
    n_rows, _, block, _ = plan_static
    nb = -(-n_rows // block)
    k = Z.shape[1]

    def lanes(t):       # a gathered row fills 128 lanes whatever it holds
        t = t.astype(jnp.float32)
        return jnp.pad(t, ((0, 0), (0, WIDE_COLS - t.shape[1])))

    Zf = lanes(Z)
    src = None if of_src is None else lanes(of_src)
    dst = jnp.pad(lanes(of_dst), ((0, nb * block - n_rows), (0, 0)))
    y = jnp.zeros((nb, block, WIDE_COLS), jnp.float32)
    zero_row = ((0, spmv_lib.WIDTH), (0, 0))
    tiles = dst.reshape(nb, block, WIDE_COLS)
    for (col0, (_, n_cols, _, _)), (tables, _, wins) in zip(
            part_statics, part_arrays):
        y = _sampled_accumulate(
            y, _chunk_sets(tables, n_cols, wins),
            jnp.pad(Zf[col0:col0 + n_cols], zero_row), block, passes,
            interpret, op, None if src is None else jnp.pad(
                src[col0:col0 + n_cols], zero_row), tiles)
    Y = y.reshape(-1, WIDE_COLS)[:n_rows, :k]
    mine = Zf if src is None else src
    for (col0, (_, n_cols, _, _)), (_, ov, _) in zip(part_statics,
                                                     part_arrays):
        if ov:
            ov_c, ov_r, ov_v = ov
            d = jnp.sum(mine[col0:col0 + n_cols][ov_c] * dst[ov_r], axis=1)
            Y = spmv_lib._overflow_add_wide(
                Y, (ov_c, ov_r, sampled_values(op, ov_v, d)),
                Z[col0:col0 + n_cols], n_rows)
    if dense is None:
        return Y
    role, slab, lines = dense
    return _sampled_dense_part(Y, role, slab, lines, Zf, op, mine,
                               lanes(of_dst), of_dst.shape[1], interpret)


def compact_matmat_apply(plan_static, tables, ov, X: jax.Array,
                         passes: int = 3,
                         interpret: bool = False) -> jax.Array:
    """Traceable body: Y = A·X for dense X (n_cols, k). ``tables`` from
    compact_tables(), either layout, with hub chunks or none — or a
    device's slice of them inside a shard_map, which is why no chunk
    has a window here (:func:`spmm_compact` hands the plan's own)."""
    return compact_matmat_parts(plan_static, ((0, plan_static),),
                                ((tables, ov, None),), X, passes, interpret)


_compact_matmat_jitted = jax.jit(compact_matmat_parts,  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
                                 static_argnums=(0, 1, 4, 5))


def plan_operands(plan):
    """(plan_static, part_statics, part_arrays) of an EdgeSpMVPlan or of
    a plan in source panels (anything with ``parts`` = ((col0, plan),
    ...), ``n_rows``, ``n_cols``, ``block``: core.coo.PanelledPlan), as
    :func:`compact_matmat_parts` takes them, the plan's ``dense`` part,
    where it has one, last; the parts' tables and windows move to the
    device on first use."""
    parts = getattr(plan, "parts", None) or ((0, plan),)
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    statics = tuple((col0, (p.n_rows, p.n_cols, p.block, spmv_lib.LO))
                    for col0, p in parts)
    arrays = tuple((compact_tables(p), p.overflow, wide_windows(p)[0])
                   for _, p in parts)
    dense = getattr(plan, "dense", None)
    if dense is not None:
        statics += ((plan.dense_role, None),)
        arrays += ((dense.slab, dense.lines_dev),)
    return static, statics, arrays


def spmm_compact(plan, X: jax.Array, passes: int = 3,
                 interpret=None) -> jax.Array:
    """Y = A·X via compact tables (see spmv_compact), either layout, or
    a plan in source panels. k == 1 takes the matvec kernel (its
    byte-row gather moves a quarter of what a float32 row does).
    passes=3 is f32-faithful — the same fidelity as the expanded path it
    replaces; pass 2 only where ranking-grade error is acceptable."""
    interpret = _resolve_interpret(interpret)
    X = jnp.asarray(X, jnp.float32)
    if X.shape[1] == 0:
        return jnp.zeros((plan.n_rows, 0), jnp.float32)
    if X.shape[1] == 1 and not hasattr(plan, "parts"):
        return spmv_compact(plan, X[:, 0], passes=passes,
                            interpret=interpret)[:, None]
    static, part_statics, part_arrays = plan_operands(plan)
    return _compact_matmat_jitted(static, part_statics, part_arrays, X,
                                  passes, interpret)


def spmv_compact(plan: spmv_lib.EdgeSpMVPlan, x: jax.Array,
                 passes: int = 3, interpret=None) -> jax.Array:
    """y = A·x via the compact-table Pallas scatter (opt-in; see module
    docstring). Numerically ~f32 at passes=3."""
    interpret = _resolve_interpret(interpret)
    tables = compact_tables(plan)
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    return _compact_jitted(static, tables, plan.overflow, x, passes,
                           interpret)
