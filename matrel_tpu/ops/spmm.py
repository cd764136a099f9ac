"""Block-sparse × dense MatMul (SpMM) — the BASELINE row-4 op.

Portable XLA path: gather the dense operand's row-blocks for each sparse
tile, one batched MXU matmul over the tile stack, segment-sum partial
products into output row-blocks. Everything is static-shaped; the MXU sees
one big [nnzb, bs, bs] × [nnzb, bs, m] batch — exactly the shape it likes.

Distribution: the sparse operand (tile stack) is replicated — the broadcast
side of a BMM-style plan (SURVEY.md §2 BMM) — and the dense operand is
column-sharded, so each device computes full rows × its column slice with
ZERO execution-time collectives.

The Pallas fast path (ops/pallas_spmm.py) replaces the gather+segment-sum
with scalar-prefetched DMA when running on real TPU.
"""

from __future__ import annotations

import logging
import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from matrel_tpu.config import MatrelConfig, default_config, on_tpu
from matrel_tpu.core import mesh as mesh_lib, padding
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.core.sparse import BlockSparseMatrix


log = logging.getLogger("matrel_tpu.spmm")


def _resolve_interpret(interpret, cfg) -> bool:
    """None → config (the shared resolver in config.py)."""
    from matrel_tpu.config import resolve_interpret
    return resolve_interpret(interpret, cfg)


# Runner cache: make_spmm/_xla_spmm build a fresh jitted closure per call,
# which would recompile on every spmm() of the same matrix (jit caches by
# function identity). Key on the static pieces of the plan. Runner
# closures capture values from S but never S itself, and a weakref
# finalizer purges a matrix's entries when it is collected — the Pallas
# runner bakes a permuted copy of the whole tile stack, so entries
# outliving their matrix would pin ~2× the stack in HBM per matrix.
_RUNNER_CACHE: dict = {}
_FINALIZER_IDS: set = set()


def _purge_runners(sid: int) -> None:
    _FINALIZER_IDS.discard(sid)
    for k in [k for k in _RUNNER_CACHE if k[0] == sid]:
        del _RUNNER_CACHE[k]


def _cached_runner(S, pm, out_pshape, d_spec, out_sharding, cfg, interpret,
                   explicit_interpret):
    key = (id(S), pm, out_pshape, str(d_spec), cfg.use_pallas,
           cfg.matmul_precision, interpret, explicit_interpret)
    run = _RUNNER_CACHE.get(key)
    if run is None:
        # compiled (non-interpret) Pallas only on a real TPU backend:
        # the resolved ``interpret`` flag already carries the
        # pallas_interpret forcing, and an explicit interpret=False on
        # CPU must fall through to XLA, never lower Mosaic on CPU
        use_pallas = interpret or (cfg.use_pallas and on_tpu())
        if use_pallas:
            from matrel_tpu.ops import pallas_spmm
            # ONLY an EXPLICIT interpret=True skips the eligibility
            # gate (tests drive deliberately tiny blocks); config-driven
            # interpret (pallas_interpret) must still respect it —
            # ineligible stacks (e.g. bs=4) break the kernel's layout
            # assumptions in ANY mode (found by soak seed 50114)
            use_pallas = ((interpret and explicit_interpret)
                          or pallas_spmm.pallas_eligible(S, pm))
            if not use_pallas:
                log.warning(
                    "spmm: tile stack not Pallas-eligible (block_size=%d, "
                    "dense width %d); running the XLA gather path",
                    S.block_size, pm)
        if use_pallas:
            run = pallas_spmm.make_spmm(S, pm, out_pshape, d_spec,
                                        out_sharding, cfg, interpret=interpret)
        else:
            run = _xla_spmm(S, pm, out_pshape, d_spec, out_sharding, cfg)
        run.executor = "pallas_spmm" if use_pallas else "xla"
        _RUNNER_CACHE[key] = run
        if id(S) not in _FINALIZER_IDS:
            _FINALIZER_IDS.add(id(S))
            weakref.finalize(S, _purge_runners, id(S))
    return run


def _dense_spec(pm: int, mesh) -> P:
    x, y = mesh.axis_names
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    if pm % (gx * gy) == 0 and pm >= gx * gy:
        return P(None, (x, y))
    return P()


def apply(S: BlockSparseMatrix, dd: jax.Array,
          d_shape: Tuple[int, int],
          config: Optional[MatrelConfig] = None,
          interpret=None, epilogue=None, ran=None) -> jax.Array:
    """Trace-compatible SpMM: S (static metadata) × dense padded array
    ``dd`` of logical shape ``d_shape``. Returns the padded product with
    canonical output sharding.

    ``epilogue`` is the fused-region slot (ir/fusion.py /
    docs/FUSION.md): a traceable callable applied to the padded product
    inside the SAME traced computation, so an absorbed consumer chain
    compiles as the SpMM's epilogue instead of its own dispatch. The
    runner itself is epilogue-agnostic (one cached kernel per matrix,
    never forked per epilogue); None keeps the historical path
    bit-identically. ``ran`` is told which runner the product goes to
    (``"pallas_spmm"`` or ``"xla"`` — the executor's
    ``plan.meta["executors"]``)."""
    cfg = config or default_config()
    n, k = S.shape
    k2, m = d_shape
    if k != k2:
        raise ValueError(f"spmm shape mismatch: {S.shape} x {d_shape}")
    explicit_interpret = interpret is not None
    interpret = _resolve_interpret(interpret, cfg)
    mesh = S.mesh
    out_pshape = padding.padded_shape((n, m), mesh)
    out_sharding = padding.canonical_sharding(out_pshape, mesh)
    pm = dd.shape[1]
    d_spec = _dense_spec(pm, mesh)
    run = _cached_runner(S, pm, out_pshape, d_spec, out_sharding, cfg,
                         interpret, explicit_interpret)
    if ran is not None:
        ran(run.executor)
    out = run(S.blocks, S.block_rows, S.block_cols, dd)
    return out if epilogue is None else epilogue(out)


def spmm(S: BlockSparseMatrix, D: BlockMatrix,
         config: Optional[MatrelConfig] = None,
         interpret=None) -> BlockMatrix:
    """C = S @ D with S block-sparse (n×k), D dense (k×m)."""
    cfg = config or default_config()
    n, _ = S.shape
    _, m = D.shape
    data = apply(S, D.data, D.shape, cfg, interpret=interpret)
    return BlockMatrix.from_array(
        data, (n, m), S.mesh,
        padding.canonical_spec(tuple(data.shape), S.mesh),
        nnz=None, block_size=S.block_size)


def _xla_spmm(S, pm, out_pshape, d_spec, out_sharding, cfg):
    bs = S.block_size
    gr, gc = S.grid
    mesh = S.mesh
    prec = getattr(jax.lax.Precision, cfg.matmul_precision.upper(),
                   jax.lax.Precision.HIGHEST)

    @jax.jit  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
    def run(blocks, brows, bcols, dd):
        dd = jax.lax.with_sharding_constraint(dd, NamedSharding(mesh, d_spec))
        want_rows = gc * bs
        if dd.shape[0] < want_rows:
            dd = jnp.pad(dd, ((0, want_rows - dd.shape[0]), (0, 0)))
        dblocks = dd[: want_rows].reshape(gc, bs, pm)
        gathered = jnp.take(dblocks, bcols, axis=0)        # [nnzb, bs, pm]
        partial = jax.lax.dot_general(
            blocks, gathered,
            (((2,), (1,)), ((0,), (0,))),                   # batched tile GEMM
            precision=prec,
            preferred_element_type=jnp.float32)             # [nnzb, bs, pm]
        summed = jax.ops.segment_sum(partial, brows, num_segments=gr)
        out = summed.reshape(gr * bs, pm).astype(blocks.dtype)
        out = out[: out_pshape[0], : out_pshape[1]]
        if out.shape != out_pshape:
            out = jnp.pad(out, ((0, out_pshape[0] - out.shape[0]),
                                (0, out_pshape[1] - out.shape[1])))
        return jax.lax.with_sharding_constraint(out, out_sharding)

    return run


def spmv(S: BlockSparseMatrix, v: BlockMatrix,
         config: Optional[MatrelConfig] = None) -> BlockMatrix:
    """Sparse matrix × vector — the PageRank building block."""
    return spmm(S, v, config)
