"""Block-sparse × block-sparse MatMul (SpGEMM) — tile-intersection.

The densify fallback for S×S multiplies (executor.py's fallthrough)
materialises one operand fully and pays SpMM FLOPs over the WHOLE dense
width — at the flagship 1%-block-density scale that is ~100× the memory
and FLOPs the sparse structure requires. This module multiplies the two
TILE MAPS instead:

* Structure (host, numpy, once per operand pair): intersect the tile
  lists on the contraction block index — pair (ia, ib) exists iff
  A.block_cols[ia] == B.block_rows[ib]. Output tiles are the distinct
  (A.block_rows[ia], B.block_cols[ib]) keys; pairs are sorted by output
  tile so accumulation is a segment-sum (XLA) or a consecutive-run VMEM
  accumulate (Pallas). The expected output tile count is exactly what
  ``ir/stats.matmul_density`` estimates at block granularity — the same
  estimator the executor's dispatch threshold reads.

* Compute (device): gather both payload stacks by the pair lists, ONE
  batched MXU matmul over [npairs, bs, bs] tiles, segment-sum into the
  output tile stack. Dense bs×bs tiles keep the MXU at full speed — the
  sparsity is exploited BETWEEN tiles, never inside one.

* Kernels (device): dispatched through the REGISTRY
  (ops/kernel_registry.py, round 11 — docs/SPARSE_KERNELS.md): the XLA
  gather path and the original scalar-prefetch Pallas kernel are the
  universal entries, joined by per-structure Pallas variants (band
  diagonal-walk, grouped cluster accumulate, powerlaw run-length
  bucketing) selected by the operand pair's classified structure, a
  measured autotune winner, or config.spgemm_kernel_override. On
  GENERIC-classified pairs (and wherever Pallas is unavailable) the
  unforced/unmeasured selection is bit-identical to the historical
  two-way choice; home-structure pairs get their specialized schedule
  (numerically equivalent — different accumulation order).

* Sharded wrapper (style of ops/spmm_sharded.py): output tiles cut into
  ``mesh.size`` equal contiguous slot ranges; each device owns the
  pairs landing in its range (zero-padded to the per-device cap, with
  sentinel pairs pointing at an appended zero tile), computes its local
  output sub-stack with zero collectives, then ONE tiled all_gather
  assembles the output tile stack.

Both operand tile stacks stay replicated (the single-chip SpMM plan's
broadcast side); nothing here ever materialises a dense operand.
"""

from __future__ import annotations

import math
import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from matrel_tpu.config import (MatrelConfig, default_config,
                               resolve_interpret)
from matrel_tpu.core import padding
from matrel_tpu.core.sparse import BlockSparseMatrix


# -- host structure ---------------------------------------------------------


def pair_structure(a_rows: np.ndarray, a_cols: np.ndarray,
                   b_rows: np.ndarray, b_cols: np.ndarray,
                   gc_out: int) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, np.ndarray,
                                         np.ndarray]:
    """Tile-intersection pair lists for C = A·B.

    Returns ``(pa, pb, slot, out_rows, out_cols)``: pair ``t``
    multiplies A tile ``pa[t]`` by B tile ``pb[t]`` into output tile
    ``slot[t]`` of the (out_rows, out_cols) tile set; pairs are sorted
    by slot (row-major output order). All int32, possibly empty.
    """
    a_rows = np.asarray(a_rows, np.int64)
    a_cols = np.asarray(a_cols, np.int64)
    b_rows = np.asarray(b_rows, np.int64)
    b_cols = np.asarray(b_cols, np.int64)
    # constructors keep stacks row-major sorted, but a hand-built B may
    # not be — sort defensively (searchsorted needs sorted keys)
    if b_rows.size and np.any(np.diff(b_rows) < 0):
        border = np.argsort(b_rows, kind="stable")
    else:
        border = None
    brs = b_rows if border is None else b_rows[border]
    starts = np.searchsorted(brs, a_cols, side="left")
    ends = np.searchsorted(brs, a_cols, side="right")
    counts = ends - starts
    total = int(counts.sum())
    empty = (np.zeros(0, np.int32),) * 3 + (np.zeros(0, np.int32),) * 2
    if total == 0:
        return empty
    pa = np.repeat(np.arange(a_rows.size, dtype=np.int64), counts)
    cum = np.zeros(a_rows.size + 1, np.int64)
    np.cumsum(counts, out=cum[1:])
    pb = (np.arange(total, dtype=np.int64) - np.repeat(cum[:-1], counts)
          + np.repeat(starts, counts))
    if border is not None:
        pb = border[pb]
    key = a_rows[pa] * gc_out + b_cols[pb]
    uniq, slot = np.unique(key, return_inverse=True)
    order = np.argsort(slot, kind="stable")
    return (pa[order].astype(np.int32), pb[order].astype(np.int32),
            slot.ravel()[order].astype(np.int32),
            (uniq // gc_out).astype(np.int32),
            (uniq % gc_out).astype(np.int32))


def _out_dtype(A: BlockSparseMatrix, B: BlockSparseMatrix,
               cfg: MatrelConfig):
    """Match the executor's dense-matmul dtype policy: f32 accumulate,
    cast back to the common input dtype under keep_input_dtype."""
    if cfg.keep_input_dtype and A.dtype == B.dtype:
        return A.dtype
    return jnp.float32


def _check_shapes(A: BlockSparseMatrix, B: BlockSparseMatrix) -> None:
    if A.shape[1] != B.shape[0]:
        raise ValueError(f"spgemm shape mismatch: {A.shape} x {B.shape}")
    if A.block_size != B.block_size:
        raise ValueError(
            f"spgemm needs matching block sizes, got {A.block_size} "
            f"vs {B.block_size} — rebuild one operand on the other's "
            f"grid (BlockSparseMatrix.from_numpy/from_coo_arrays)")


# -- runner cache (ops/spmm.py idiom: keyed on both operand ids, purged
# when EITHER matrix is collected so baked pair tables don't pin HBM) ------

_RUNNER_CACHE: dict = {}
_STRUCT_CACHE: dict = {}
_FINALIZER_IDS: set = set()


def _purge_runners(sid: int) -> None:
    _FINALIZER_IDS.discard(sid)
    for cache in (_RUNNER_CACHE, _STRUCT_CACHE):
        for k in [k for k in cache if sid in k[:2]]:
            del cache[k]


def _register_purge(S) -> None:
    if id(S) not in _FINALIZER_IDS:
        _FINALIZER_IDS.add(id(S))
        weakref.finalize(S, _purge_runners, id(S))


def _pair_structure_cached(A: BlockSparseMatrix, B: BlockSparseMatrix):
    """The 'once per operand pair' half of the module contract: the
    host intersection (pair_structure) for an (A, B) pair is cached
    keyed on both operand identities — an iterative workload re-runs
    only the device compute, not the O(pairs·log pairs) numpy
    structure work. Purged with the runners when either matrix is
    collected (review r6: only the runner was cached before)."""
    key = (id(A), id(B))
    hit = _STRUCT_CACHE.get(key)
    if hit is not None:
        return hit
    out = pair_structure(
        np.asarray(A.block_rows), np.asarray(A.block_cols),
        np.asarray(B.block_rows), np.asarray(B.block_cols), B.grid[1])
    _STRUCT_CACHE[key] = out
    _register_purge(A)
    _register_purge(B)
    return out


def pallas_eligible(bs: int, npairs: int) -> bool:
    """Every Pallas block here spans the full trailing (bs, bs) dims of
    its array, which Mosaic always accepts, but sub-8-sublane tiles
    still break the kernel's layout assumptions (the pallas_spmm
    lesson, soak seed 50114) — gate on the sublane multiple."""
    return bs % 8 == 0 and npairs > 0


def _tiles_runner(A, B, cfg, interpret, pairs, n_out, out_dtype,
                  kernel=None):
    """Cached device runner producing the output TILE STACK from the
    two payload stacks + pair tables — now a REGISTRY dispatch
    (ops/kernel_registry.py): the chosen kernel id comes from the
    caller (the executor passes the planner's ``spgemm_kernel`` stamp)
    or from the registry's own selection over the operand pair's
    structure class. With nothing stamped, measured or overridden, a
    GENERIC-classified pair selects bit-identically to the historical
    two-way choice (Pallas on real TPU / forced interpret when
    eligible, XLA gather/segment-sum otherwise); home-structure pairs
    get their specialized schedule — same product, different
    accumulation order."""
    from matrel_tpu.ops import kernel_registry as kr
    pa = pairs[1]
    npairs = int(np.asarray(pa).size)
    kid = kernel
    if kid is None:
        structure = kr.pair_class_of(A, B)
        kid, _ = kr.select_kernel(structure, A.block_size, npairs, cfg,
                                  side=max(A.shape[0], A.shape[1],
                                           B.shape[1]),
                                  mesh=A.mesh)
    elif not kr.admissible(kid, A.block_size, npairs, cfg,
                           dtype=out_dtype):
        kid = kr.legacy_default(A.block_size, npairs, cfg)
    key = (id(A), id(B), npairs, n_out, str(out_dtype), kid,
           interpret, cfg.matmul_precision)
    run = _RUNNER_CACHE.get(key)
    if run is not None:
        return run
    run = kr.build_runner(kid, A, B, cfg, interpret, pairs, n_out,
                          out_dtype)
    _RUNNER_CACHE[key] = run
    _register_purge(A)
    _register_purge(B)
    return run


def _edge_masked(S: BlockSparseMatrix):
    """Payload stack with the logical-edge overhang zeroed.

    On ragged shapes the last block row/column overhangs the logical
    region, and tiles there may carry nonzeros beyond the edge —
    ``BlockSparseMatrix.random`` fills whole tiles
    (``from_numpy``/``from_coo_arrays`` zero-pad, so they are already
    clean). A dense SpMM partner is zero-padded there, so overhang
    always multiplied zeros; in S×S BOTH operands carry it:
    contraction-edge garbage × garbage lands in KEPT output entries
    (caught by the ragged verify probe), and output-edge garbage ×
    valid values would leak into the padded region the executor's
    zero-padding invariant promises is exact zeros. Masking both edges
    makes every product tile exactly the logical values. Eager
    (ensure_compile_time_eval — a traced mask would poison the memo
    with tracers, the spmm transpose-memo lesson) and memoised on the
    matrix."""
    bs = S.block_size
    rmod = S.shape[0] % bs
    cmod = S.shape[1] % bs
    if rmod == 0 and cmod == 0:
        return S.blocks
    memo = getattr(S, "_spgemm_edge_memo", None)
    if memo is not None:
        return memo
    blocks = S.blocks
    with jax.ensure_compile_time_eval():
        if rmod:
            idx = np.nonzero(np.asarray(S.block_rows)
                             == S.shape[0] // bs)[0]
            if idx.size:
                blocks = blocks.at[jnp.asarray(idx), rmod:, :].set(0)
        if cmod:
            idx = np.nonzero(np.asarray(S.block_cols)
                             == S.shape[1] // bs)[0]
            if idx.size:
                blocks = blocks.at[jnp.asarray(idx), :, cmod:].set(0)
    S._spgemm_edge_memo = blocks
    return blocks


# -- public API -------------------------------------------------------------


def spgemm_tiles(A: BlockSparseMatrix, B: BlockSparseMatrix,
                 config: Optional[MatrelConfig] = None,
                 interpret=None, kernel: Optional[str] = None):
    """C = A·B as (tiles, out_rows, out_cols): the output tile stack
    [n_out, bs, bs] plus its coordinates on the (gr_A, gc_B) grid.
    Neither operand is densified; empty intersection yields one zero
    tile at (0, 0) (the BlockSparseMatrix empty convention).
    ``kernel`` forces one registered kernel id (the executor passes
    the planner's stamp; None lets the registry select)."""
    cfg = config or default_config()
    _check_shapes(A, B)
    interp = resolve_interpret(interpret, cfg)
    pa, pb, slot, out_rows, out_cols = _pair_structure_cached(A, B)
    out_dtype = _out_dtype(A, B, cfg)
    if pa.size == 0:
        tiles = jnp.zeros((1, A.block_size, A.block_size), out_dtype)
        return tiles, np.zeros(1, np.int32), np.zeros(1, np.int32)
    n_out = int(out_rows.size)
    run = _tiles_runner(A, B, cfg, interp,
                        (slot, pa, pb, out_rows, out_cols), n_out,
                        out_dtype, kernel=kernel)
    if getattr(run, "consumes_args", True):
        tiles = run(_edge_masked(A), _edge_masked(B),
                    jnp.asarray(slot), jnp.asarray(pa),
                    jnp.asarray(pb))
    else:
        # baked specialized runners replay their pre-gathered payload;
        # uploading npairs-sized tables per call would be pure dead
        # work on the repeated-query hot path
        tiles = run(None, None, None, None, None)
    return tiles, out_rows, out_cols


def spgemm(A: BlockSparseMatrix, B: BlockSparseMatrix,
           config: Optional[MatrelConfig] = None,
           interpret=None, kernel: Optional[str] = None
           ) -> BlockSparseMatrix:
    """C = A·B with a SPARSE result: only the tile intersections are
    computed and only the nonzero output tiles are stored."""
    cfg = config or default_config()
    tiles, out_rows, out_cols = spgemm_tiles(A, B, cfg,
                                             interpret=interpret,
                                             kernel=kernel)
    rep = NamedSharding(A.mesh, P())
    return BlockSparseMatrix(
        blocks=jax.lax.with_sharding_constraint(tiles, rep)
        if A.mesh.size > 1 else tiles,
        block_rows=jax.device_put(out_rows, rep),
        block_cols=jax.device_put(out_cols, rep),
        shape=(A.shape[0], B.shape[1]),
        block_size=A.block_size, mesh=A.mesh)


def apply_dense(A: BlockSparseMatrix, B: BlockSparseMatrix,
                config: Optional[MatrelConfig] = None,
                interpret=None, kernel: Optional[str] = None,
                epilogue=None, epilogue_elementwise: bool = False
                ) -> jax.Array:
    """Trace-compatible SpGEMM for the executor: the product scattered
    into a PADDED dense array with canonical sharding (what every other
    lowering hands its consumer). The scatter is the only dense
    materialisation — it is the op's OUTPUT, not an operand.

    ``epilogue`` is the fused-region slot (ir/fusion.py /
    docs/FUSION.md): the absorbed consumer chain reaches the kernel
    seam through the registry's per-structure epilogue hook
    (``kernel_registry.epilogue_mode``) — zero-preserving pointwise
    chains (``epilogue_elementwise`` True, the executor's proof) may
    run TILE-WISE over the output stack before the scatter on
    structure classes registered "tilewise"; everything else applies
    to the scattered dense output. No kernel body is forked either
    way."""
    from matrel_tpu.ops import kernel_registry as kr
    cfg = config or default_config()
    tiles, out_rows, out_cols = spgemm_tiles(A, B, cfg,
                                             interpret=interpret,
                                             kernel=kernel)
    if epilogue is not None:
        mode = kr.epilogue_mode(kr.pair_class_of(A, B),
                                epilogue_elementwise)
        if mode == "tilewise":
            tiles = kr.apply_tile_epilogue(tiles, epilogue)
            epilogue = None          # consumed before the scatter
    n, m = A.shape[0], B.shape[1]
    bs = A.block_size
    gr = math.ceil(n / bs)
    gc = math.ceil(m / bs)
    mesh = A.mesh
    pshape = padding.padded_shape((n, m), mesh)
    sharding = padding.canonical_sharding(pshape, mesh)

    full = jnp.zeros((gr, gc, bs, bs), dtype=tiles.dtype)
    full = full.at[jnp.asarray(out_rows), jnp.asarray(out_cols)].set(tiles)
    dense = full.transpose(0, 2, 1, 3).reshape(gr * bs, gc * bs)
    dense = dense[: pshape[0], : pshape[1]]
    if dense.shape != pshape:
        dense = jnp.pad(dense, ((0, pshape[0] - dense.shape[0]),
                                (0, pshape[1] - dense.shape[1])))
    # tiles can overhang the logical edge on ragged shapes; their
    # overhang region is exact zeros because _edge_masked scrubs both
    # operands' edge tiles (products of clean operands are clean), so
    # no re-mask is needed — and the padded region BEYOND the tile
    # grid is zeros from jnp.pad already.
    if epilogue is not None:         # the conservative "dense" hook
        dense = epilogue(dense)
    return jax.lax.with_sharding_constraint(dense, sharding)


# -- sharded wrapper (ops/spmm_sharded.py style) ----------------------------


def spgemm_sharded(A: BlockSparseMatrix, B: BlockSparseMatrix,
                   config: Optional[MatrelConfig] = None
                   ) -> BlockSparseMatrix:
    """Scale-out SpGEMM: the PAIR list distributed over A.mesh.

    Output tile slots are cut into ``mesh.size`` equal contiguous
    ranges; each device owns exactly the pairs landing in its range
    (zero-padded to the per-device cap with sentinel pairs that hit an
    appended zero tile), computes its local output sub-stack with ZERO
    collectives, then one tiled all_gather assembles the stack — the
    same balance/padding contract as shard_block_sparse."""
    from matrel_tpu.utils.compat import shard_map
    cfg = config or default_config()
    _check_shapes(A, B)
    mesh = A.mesh
    p = mesh.size
    bs = A.block_size
    pa, pb, slot, out_rows, out_cols = _pair_structure_cached(A, B)
    out_dtype = _out_dtype(A, B, cfg)
    if pa.size == 0:
        rep = NamedSharding(mesh, P())
        return BlockSparseMatrix(
            blocks=jax.device_put(np.zeros((1, bs, bs),
                                           np.dtype(out_dtype)), rep),
            block_rows=jax.device_put(np.zeros(1, np.int32), rep),
            block_cols=jax.device_put(np.zeros(1, np.int32), rep),
            shape=(A.shape[0], B.shape[1]), block_size=bs, mesh=mesh)

    n_out = int(out_rows.size)
    spd = -(-n_out // p)                 # output slots per device
    dev_of = slot // spd
    counts = np.bincount(dev_of, minlength=p)
    cap = max(1, int(counts.max()))
    starts = np.zeros(p + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    offs = np.arange(pa.size, dtype=np.int64) - starts[dev_of]
    # sentinel pairs multiply appended zero tiles → contribute nothing
    pa_d = np.full((p, cap), A.nnzb, np.int32)
    pb_d = np.full((p, cap), B.nnzb, np.int32)
    slot_d = np.zeros((p, cap), np.int32)
    pa_d[dev_of, offs] = pa
    pb_d[dev_of, offs] = pb
    slot_d[dev_of, offs] = (slot % spd).astype(np.int32)

    axes = tuple(mesh.axis_names)
    sh1 = NamedSharding(mesh, P(axes))
    prec = getattr(jax.lax.Precision, cfg.matmul_precision.upper(),
                   jax.lax.Precision.HIGHEST)
    common = jnp.promote_types(A.dtype, B.dtype)

    def kernel(ab, bb, pa_l, pb_l, slot_l):
        ga = jnp.take(ab, pa_l, axis=0)              # (cap, bs, bs)
        gb = jnp.take(bb, pb_l, axis=0)
        part = jax.lax.dot_general(
            ga, gb, (((2,), (1,)), ((0,), (0,))),
            precision=prec, preferred_element_type=jnp.float32)
        local = jax.ops.segment_sum(part, slot_l, num_segments=spd)
        return jax.lax.all_gather(local, axes, axis=0, tiled=True)

    fn = shard_map(kernel, mesh=mesh,
                   in_specs=(P(), P(), P(axes), P(axes), P(axes)),
                   out_specs=P(), check_vma=False)

    @jax.jit  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
    def run(ab, bb, pa_l, pb_l, slot_l):
        ab = jnp.concatenate(
            [ab.astype(common), jnp.zeros((1, bs, bs), common)])
        bb = jnp.concatenate(
            [bb.astype(common), jnp.zeros((1, bs, bs), common)])
        tiles = fn(ab, bb, pa_l, pb_l, slot_l)[:n_out]
        return tiles.astype(out_dtype)

    tiles = run(_edge_masked(A), _edge_masked(B),
                jax.device_put(pa_d.reshape(-1), sh1),  # matlint: disable=ML008 host-built pair-table placed on its sharded layout at plan build
                jax.device_put(pb_d.reshape(-1), sh1),  # matlint: disable=ML008 host-built pair-table placed on its sharded layout at plan build
                jax.device_put(slot_d.reshape(-1), sh1))  # matlint: disable=ML008 host-built pair-table placed on its sharded layout at plan build
    rep = NamedSharding(mesh, P())
    return BlockSparseMatrix(
        blocks=jax.lax.with_sharding_constraint(tiles, rep),
        block_rows=jax.device_put(out_rows, rep),
        block_cols=jax.device_put(out_cols, rep),
        shape=(A.shape[0], B.shape[1]), block_size=bs, mesh=mesh)
