"""Pallas TPU kernel for block-sparse × dense MatMul.

The hot op of BASELINE row 4, hand-scheduled: the sparse tile list drives a
scalar-prefetched grid, so the kernel DMAs exactly the dense row-blocks the
nonzero tiles touch — no gather materialisation, no segment-sum pass, and
revisit-accumulation directly in the output VMEM block.

Grid: (m_tiles, nnzb) — tile index varies fastest, so all sparse tiles are
processed consecutively for a fixed output column tile, and output blocks
are revisited consecutively for runs of equal block_rows (the tile list is
row-major sorted; TPU grids execute sequentially, which makes the
accumulate-in-place safe).

Tile payloads stay in the input dtype (bf16 friendly); accumulation is f32
in the MXU via preferred_element_type.
"""

from __future__ import annotations

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding

from matrel_tpu.config import MatrelConfig


def _make_kernel(precision, nnzb):
    def _kernel(brows, bcols, blocks_ref, d_ref, out_ref, acc_ref):
        i = pl.program_id(1)  # sparse-tile index (fastest)
        row = brows[i]
        first_visit = jnp.logical_or(i == 0,
                                     brows[jnp.maximum(i - 1, 0)] != row)
        last_visit = jnp.logical_or(
            i == nnzb - 1, brows[jnp.minimum(i + 1, nnzb - 1)] != row)

        # Accumulate row-runs in an f32 VMEM scratch; the HBM-backed out
        # block is written ONCE per run (bf16 revisit-rounding avoided
        # without paying f32 write-back traffic per visit).
        @pl.when(first_visit)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        tile = blocks_ref[0]          # [bs, bs]
        dtile = d_ref[0]              # [bs, tm]
        acc_ref[:] += jax.lax.dot(
            tile, dtile,
            precision=precision,
            preferred_element_type=jnp.float32,
        )

        @pl.when(last_visit)
        def _flush():
            out_ref[:] = acc_ref[:].astype(out_ref.dtype)

    return _kernel


def _pick_tm(pm: int) -> int:
    """Output column-tile width: whole padded m if small, else 512-wide
    strips (same policy as make_spmm's grid construction)."""
    tm = pm if pm <= 512 else 512
    while pm % tm != 0:
        tm //= 2
        if tm < 128:
            return pm
    return tm


def pallas_eligible(S, pm: int) -> bool:
    """Mosaic requires each block's last two dims to be MULTIPLES of
    (8, 128) respectively, or equal the array's dims. The out block is
    (bs, tm) on (gr·bs, pm); tiny or odd block sizes (the fuzzer's bs=4
    caught this on real TPU) must fall back to the XLA path. bf16
    payloads at bs=8/16/24 were probed on-chip (2026-07-30) and compile
    fine, so the 8-sublane rule is not
    dtype-widened here. The tm conjunct is currently always true by
    _pick_tm's contract (pm itself or a multiple of 128) — kept as a
    guard should that policy change."""
    bs = S.block_size
    gr = S.grid[0]
    tm = _pick_tm(pm)
    return ((bs % 8 == 0 or gr == 1)
            and (tm % 128 == 0 or tm == pm))


def spmm_call(bs: int, tm: int, m_tiles: int, nnzb: int, gr: int,
              pm: int, out_dtype, interpret: bool = False):
    """The pallas_call of one SpMM, from shapes alone (make_spmm binds
    it to a matrix; tests/test_chip_compile.py compiles it for a
    described chip): ``kernel(rows, cols, payload[nnzb,bs,bs],
    dblocks[gc,bs,pm]) -> [gr*bs, pm]``."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_rows, block_cols
        grid=(m_tiles, nnzb),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda j, i, brows, bcols: (i, 0, 0)),
            pl.BlockSpec((1, bs, tm), lambda j, i, brows, bcols: (bcols[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bs, tm), lambda j, i, brows, bcols: (brows[i], j)),
        scratch_shapes=[pltpu.VMEM((bs, tm), jnp.float32)],
    )
    # bf16 payloads run the MXU's native single pass; asking Mosaic for
    # fp32 contract precision on bf16 operands is both pointless (inputs
    # carry bf16 information) and rejected ("Bad lhs type"). f32 payloads
    # keep full-f32 MXU passes.
    precision = (jax.lax.Precision.DEFAULT if out_dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    return pl.pallas_call(  # matlint: disable=ML009 legacy SpMM kernel, unported to the registry this round (block-sparse x DENSE path; registry covers S x S)
        _make_kernel(precision, nnzb),
        name="matrel_spmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((gr * bs, pm), out_dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


def make_spmm(S, pm, out_pshape, d_spec, out_sharding, cfg: MatrelConfig,
              interpret: bool = False):
    """Build a jitted SpMM runner bound to S's static tile metadata."""
    import numpy as np

    bs = S.block_size
    gr, gc = S.grid

    # Every output row-block must be visited at least once or its VMEM block
    # is never initialised: statically append one zero tile per empty row
    # and re-sort row-major so revisit-accumulation stays consecutive.
    host_rows = np.asarray(S.block_rows)
    host_cols = np.asarray(S.block_cols)
    empty_rows = np.setdiff1d(np.arange(gr, dtype=np.int32), host_rows)
    all_rows = np.concatenate([host_rows, empty_rows]).astype(np.int32)
    all_cols = np.concatenate(
        [host_cols, np.zeros_like(empty_rows)]).astype(np.int32)
    perm = np.lexsort((all_cols, all_rows))
    all_rows, all_cols = all_rows[perm], all_cols[perm]
    n_pad_tiles = len(empty_rows)
    # position of each combined tile in the original payload stack; padded
    # tiles point at index nnzb (the appended zero tile)
    src = np.concatenate([np.arange(S.nnzb), np.full(n_pad_tiles, S.nnzb)])
    src = src[perm].astype(np.int32)
    nnzb = S.nnzb + n_pad_tiles
    # output column tile: whole padded m if small, else 512-wide strips
    tm = _pick_tm(pm)
    m_tiles = pm // tm

    out_dtype = S.blocks.dtype
    kernel = spmm_call(bs, tm, m_tiles, nnzb, gr, pm, out_dtype, interpret)

    # The tile stack is static per matrix: permute it into kernel order
    # ONCE at build time. Doing this inside `run` cost ~2 ms/call at
    # BASELINE row-4 scale — as much as the kernel itself (measured
    # 2026-07-30: full SpMM 2.19 ms, in-jit permutation alone 1.9 ms).
    # The permuted payload depends only on the matrix, not on pm/d_spec,
    # so it is memoised ON S and shared by every runner for that matrix
    # (one ~tile-stack-sized copy per matrix, not per cache key); it
    # dies with S. ensure_compile_time_eval keeps the build eager even
    # when the cache miss happens inside an outer jit trace — otherwise
    # tracers leak into the cached closure and every later independent
    # trace over the same matrix crashes. The closures below capture
    # values, never S itself, so the runner cache's weakref eviction
    # (ops/spmm.py) can free everything when the matrix dies.
    baked_blocks = S.blocks
    memo = getattr(S, "_pallas_payload_memo", None)
    if memo is None or memo[0] is not baked_blocks:
        # memo[0] identity check: a runner built AFTER an S.blocks
        # reassignment must not reuse a payload permuted from the old
        # stack (the per-runner guard below only protects runners built
        # BEFORE the reassignment)
        with jax.ensure_compile_time_eval():
            payload_prepared = jnp.concatenate(
                [baked_blocks,
                 jnp.zeros((1, bs, bs), baked_blocks.dtype)])[
                     jnp.asarray(src)]
            rows_d, cols_d = jnp.asarray(all_rows), jnp.asarray(all_cols)
        S._pallas_payload_memo = (baked_blocks, payload_prepared,
                                  rows_d, cols_d)
    else:
        _, payload_prepared, rows_d, cols_d = memo
    mesh = S.mesh

    @jax.jit  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
    def _run(payload, rows, cols, dd):
        dd = jax.lax.with_sharding_constraint(dd, NamedSharding(mesh, d_spec))
        want_rows = gc * bs
        if dd.shape[0] < want_rows:
            dd = jnp.pad(dd, ((0, want_rows - dd.shape[0]), (0, 0)))
        # mesh padding can exceed the tile grid's extent (small k on a
        # big mesh — soak seed 50114); the excess rows are exact zeros
        # by the padding invariant — same unconditional slice as
        # _xla_spmm and the sharded runner
        dblocks = dd[:want_rows].reshape(gc, bs, pm)
        out = kernel(rows, cols, payload, dblocks)
        out = out[: out_pshape[0], : out_pshape[1]]
        if out.shape != out_pshape:
            out = jnp.pad(out, ((0, out_pshape[0] - out.shape[0]),
                                (0, out_pshape[1] - out.shape[1])))
        return jax.lax.with_sharding_constraint(out, out_sharding)

    def run(blocks, brows, bcols, dd):
        if blocks is not baked_blocks:
            # the XLA fallback honors a reassigned S.blocks; this path
            # bakes it, so diverge loudly instead of silently
            raise ValueError(
                "S.blocks was reassigned after the SpMM runner was built; "
                "construct a new BlockSparseMatrix instead of mutating")
        del brows, bcols  # baked into the prepared payload at build
        return _run(payload_prepared, rows_d, cols_d, dd)

    # the jitted program and its baked arguments, so a caller can look
    # at what was compiled (chip_smoke.py reads the HLO) without
    # closing over the payload as a constant
    run.jitted, run.baked_args = _run, (payload_prepared, rows_d, cols_d)
    return run
