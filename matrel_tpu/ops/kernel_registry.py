"""Dispatchable sparse-kernel registry — the ONE seam for SpGEMM
kernels (ROADMAP item 5; JITSPMM, arXiv:2312.05639).

The engine's S×S multiply used to make exactly one hardcoded choice:
XLA gather/segment-sum vs the single scalar-prefetch Pallas kernel,
gated by ``config.spgemm_density_threshold``. This module replaces that
two-way branch with a REGISTRY of kernels, each declaring the sparsity
STRUCTURE classes it is specialized for (ir/stats classifiers over the
block edge lists), so that

* the planner can stamp a ``spgemm_kernel`` choice from cost estimates,
* the round-4 autotuner can MEASURE registered variants per
  (shape class, structure class, backend) and persist winners exactly
  like matmul strategies (``spgemm|<class>|<structure>|...`` keys),
* MV110 can statically verify every stamped kernel id is in-registry
  and admissible for the stamped structure class, and
* future GPU/multi-backend kernels land HERE, not in a new branch
  (the matlint ML009 "one seam" rule keeps it that way).

Registered vocabulary (every kernel computes the exact same tile-stack
product; variants differ only in schedule, so any of them is
correctness-preserving on any structure):

  xla_gather       gather + batched tile GEMM + segment_sum (XLA; the
                   legacy fallback, admissible everywhere)
  pallas_generic   the original scalar-prefetch kernel, one pair per
                   grid step (the behavior-preserving Pallas default)
  pallas_band      row_band home: pair runs are short and uniform, so
                   pairs are pre-gathered at BUILD time into a
                   CONTIGUOUS grouped table (sequential DMA, no
                   per-pair prefetch indirection) and each grid step
                   retires G pairs as ONE (bs, G·bs)x(G·bs, bs) MXU
                   contraction — G× fewer grid steps
  pallas_cluster   clustered_tile home: same grouped schedule with a
                   LARGER accumulate group over the cluster's long
                   slot runs (bigger VMEM working set, fewer flushes)
  pallas_powerlaw  powerlaw_coo home: output rows BUCKETED by pair
                   count — light rows run a small group, hub rows a
                   large one — so the MXU is never padded to the
                   heaviest row's run length

Selection order (``select_kernel``): config override (the soak/degrade
forcing knob) > measured autotune winner (``config.autotune``) >
registry cost model (a specialized kernel is nominated ONLY on its
home structure class; on "generic" the legacy choice stands
bit-identically) > legacy default.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrel_tpu.config import MatrelConfig, default_config, pallas_enabled

# -- registry ---------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered SpGEMM kernel.

    ``structures`` are the HOME classes the registry's cost model
    nominates it for; ``universal`` marks the legacy entries admissible
    on every class. ``group`` is the pair-group factor G of the grouped
    schedule (0 = XLA path, 1 = one pair per step); ``bucket_split``
    (powerlaw only) is the run length at which an output row moves
    from the light bucket to the heavy one."""

    kernel_id: str
    structures: Tuple[str, ...]
    needs_pallas: bool
    group: int
    description: str
    universal: bool = False
    bucket_split: int = 0


REGISTRY: Dict[str, KernelSpec] = {}

#: Test/obs hook: how many kernel selections ran. The bit-identity
#: contract says ZERO when ``spgemm_density_threshold = 0`` (nothing
#: dispatches, so nothing may consult the registry).
_LOOKUPS = {"count": 0}

#: Mosaic's default scoped-VMEM limit for one kernel on a v5e core —
#: the "limit 16.00M" of its RESOURCE_EXHAUSTED refusal. Every grouped
#: schedule's per-step footprint (``dot_step_vmem_bytes``) is held to it.
VMEM_SCOPED_LIMIT_BYTES = 16 * 1024 * 1024


def register_kernel(spec: KernelSpec) -> None:
    REGISTRY[spec.kernel_id] = spec


def kernel_ids() -> Tuple[str, ...]:
    return tuple(REGISTRY)


def get_kernel(kernel_id: str) -> KernelSpec:
    return REGISTRY[kernel_id]


def _itemsize(dtype) -> int:
    return 4 if dtype is None else jnp.dtype(dtype).itemsize


def dot_step_vmem_bytes(lhs: int, rhs: int, out: int, itemsize: int,
                        acc: bool) -> int:
    """Scoped VMEM Mosaic allocates for ONE grid step of an
    ``out (+)= lhs @ rhs`` kernel (operand sizes in ELEMENTS), counted
    the way the chip's compiler counts it (checked against v5e compiles
    in tests/test_chip_compile.py): both inputs and the out block are
    double-buffered, the dot result is an f32 temporary, ``acc`` adds
    the f32 accumulator scratch. f32 payloads contract at HIGHEST
    precision, whose bf16 splits of both operands take temporaries of
    2x the operand bytes; bf16 operands of a width that is not a power
    of two are re-laid out through temporaries measured at up to 0.37x
    the operand bytes — budgeted as half. Linear in every size."""
    io = 2 * (lhs + rhs + out) * itemsize
    tmp = out * 4 * (2 if acc else 1)
    if itemsize >= 4:
        tmp += 2 * (lhs + rhs) * 4
    else:
        tmp += (lhs + rhs) * itemsize // 2
    return io + tmp


def grouped_factor(bs: int, requested: int, itemsize: int = 4) -> int:
    """Effective pair-group G for a grouped variant at this block size
    and payload width: the requested factor clamped so one
    (bs, G·bs)x(G·bs, bs) accumulate step fits the scoped-VMEM limit.
    ``itemsize`` defaults to f32, the widest payload — a G feasible
    there is feasible for every narrower dtype."""
    tile = bs * bs
    fixed = dot_step_vmem_bytes(0, 0, tile, itemsize, acc=True)
    per_g = dot_step_vmem_bytes(tile, tile, 0, itemsize, acc=True)
    cap = (VMEM_SCOPED_LIMIT_BYTES - fixed) // max(per_g, 1)
    return int(max(1, min(requested, cap)))


def _pallas_eligible(bs: int, npairs: int) -> bool:
    """ops/spgemm.py's 8-sublane eligibility rule — lazily imported so
    there is exactly ONE copy (the soak-seed-50114 class of fix must
    never have to land in two places)."""
    from matrel_tpu.ops import spgemm as spgemm_lib
    return spgemm_lib.pallas_eligible(bs, npairs)


def admissible(kernel_id: str, bs: int, npairs: int,
               config: Optional[MatrelConfig] = None,
               dtype=None) -> bool:
    """Can this kernel RUN for a (bs, npairs) SpGEMM under this config?
    Pallas entries need the pallas gate (real TPU or interpret mode)
    and the 8-sublane block rule (the pallas_spmm lesson, soak seed
    50114); grouped entries additionally need a VMEM-feasible G >= 2
    (G == 1 would be the generic schedule with extra padding).
    ``dtype`` is the payload dtype the kernel will run at; None (the
    planner, which stamps before operands are materialised) counts
    f32, the widest."""
    spec = REGISTRY.get(kernel_id)
    if spec is None:
        return False
    cfg = config or default_config()
    if spec.needs_pallas:
        if not pallas_enabled(cfg):
            return False
        if not _pallas_eligible(bs, npairs):
            return False
        if spec.group > 1 and grouped_factor(
                bs, spec.group, _itemsize(dtype)) < 2:
            return False
    return True


def legacy_default(bs: int, npairs: int,
                   config: Optional[MatrelConfig] = None) -> str:
    """EXACTLY the pre-registry two-way choice: the scalar-prefetch
    Pallas kernel where eligible, the XLA gather path otherwise — the
    bit-identity anchor for the default config."""
    cfg = config or default_config()
    if pallas_enabled(cfg) and _pallas_eligible(bs, npairs):
        return "pallas_generic"
    return "xla_gather"


def select_kernel(structure: str, bs: int, npairs: int,
                  config: Optional[MatrelConfig] = None,
                  side: Optional[int] = None,
                  mesh=None) -> Tuple[str, str]:
    """(kernel_id, source) for one SpGEMM. ``source`` records WHY (the
    choose_strategy_ex contract): "override" (config forcing knob —
    soak batteries and the degradation ladder), "measured" (autotune
    table winner for this (shape, structure, backend) class — the
    MV106 measured-stamp precedent), "model" (a specialized kernel on
    its home structure class), "default" (the legacy two-way choice,
    bit-identical to the pre-registry engine)."""
    cfg = config or default_config()
    _LOOKUPS["count"] += 1
    ov = cfg.spgemm_kernel_override
    if ov:
        if ov not in REGISTRY:
            raise ValueError(
                f"spgemm_kernel_override {ov!r} is not a registered "
                f"kernel (have {kernel_ids()})")
        if admissible(ov, bs, npairs, cfg):
            return ov, "override"
        return legacy_default(bs, npairs, cfg), "default"
    if cfg.autotune and mesh is not None and side:
        from matrel_tpu.parallel import autotune
        best = autotune.lookup_or_measure_spgemm(side, structure, bs,
                                                 mesh, cfg)
        if best is not None and admissible(best, bs, npairs, cfg):
            return best, "measured"
    for kid, spec in REGISTRY.items():
        if (not spec.universal and structure in spec.structures
                and admissible(kid, bs, npairs, cfg)):
            return kid, "model"
    return legacy_default(bs, npairs, cfg), "default"


# -- fused epilogue hooks (whole-plan fusion, docs/FUSION.md) ---------------
# The ``apply_dense``-style epilogue seam: when a fused region absorbs a
# consumer chain into its producer SpGEMM (ir/fusion.py), the chain
# reaches the kernel HERE — per structure class, WITHOUT forking kernel
# bodies. Each hook names how the epilogue is applied to the kernel's
# output:
#
#   "tilewise"  the epilogue runs over the [n_out, bs, bs] OUTPUT TILE
#               STACK before the dense scatter — nnzb·bs² elements
#               instead of n·m. Only legal for zero-preserving,
#               shape-polymorphic chains (scalar mul / pow>0 — the
#               executor's epilogue_elementwise flag proves it); the
#               untouched tiles stay exact zeros so the scatter's
#               padded region is still exact.
#   "dense"     the epilogue runs over the scattered padded dense
#               output (always legal; the conservative default).
#
# Registering a specialized mode for a new structure class is one
# ``register_epilogue_hook`` call — the ML009 "one seam" discipline
# extended to epilogues (MV111 verifies the stamps that route here).

EPILOGUE_MODES = ("tilewise", "dense")

_EPILOGUE_HOOKS: Dict[str, str] = {}


def register_epilogue_hook(structure: str, mode: str) -> None:
    if mode not in EPILOGUE_MODES:
        raise ValueError(
            f"epilogue mode must be one of {EPILOGUE_MODES}, "
            f"got {mode!r}")
    _EPILOGUE_HOOKS[structure] = mode


def epilogue_mode(structure: str, elementwise_ok: bool) -> str:
    """The application mode for one fused SpGEMM epilogue: the
    structure class's registered hook, demoted to "dense" whenever the
    chain is not provably zero-preserving shape-polymorphic
    (``elementwise_ok`` False) — correctness never rides the
    registration."""
    if not elementwise_ok:
        return "dense"
    return _EPILOGUE_HOOKS.get(structure, "dense")


def apply_tile_epilogue(tiles, epilogue):
    """Run a zero-preserving pointwise epilogue over the output tile
    stack (the "tilewise" hook body — one place, every kernel)."""
    return epilogue(tiles)


# -- structure classification (memoised per operand) ------------------------


def structure_of_matrix(S) -> str:
    """Structure class of one BlockSparseMatrix, memoised on the matrix
    (its tile lists are immutable — the pair_structure cache idiom)."""
    memo = getattr(S, "_structure_memo", None)
    if memo is not None:
        return memo
    from matrel_tpu.ir import stats
    gr, gc = S.grid
    cls = stats.classify_block_structure(np.asarray(S.block_rows),
                                         np.asarray(S.block_cols),
                                         gr, gc)
    S._structure_memo = cls
    return cls


def structure_of_child(child, bs: int) -> str:
    """Structure class of an S×S matmul OPERAND node (sparse_leaf or
    coo_leaf). COO leaves are classified at the dispatch block size
    from their bucketed tile keys — one O(nnz) numpy pass, memoised
    per block size (the _block_density_memo idiom)."""
    m = child.attrs["matrix"]
    if child.kind == "sparse_leaf":
        return structure_of_matrix(m)
    memo = getattr(m, "_structure_memo", None)
    if memo is not None and memo[0] == bs:
        return memo[1]
    from matrel_tpu.ir import stats
    gr = math.ceil(m.shape[0] / bs)
    gc = math.ceil(m.shape[1] / bs)
    keys = np.unique((np.asarray(m.rows, np.int64) // bs) * gc
                     + np.asarray(m.cols, np.int64) // bs)
    cls = stats.classify_block_structure(keys // gc, keys % gc, gr, gc)
    m._structure_memo = (bs, cls)
    return cls


def pair_class_of(A, B) -> str:
    """Structure class of a BlockSparseMatrix operand pair (the
    ops-level entry; the expr-level one is
    executor.spgemm_kernel_choice)."""
    from matrel_tpu.ir import stats
    return stats.pair_structure_class(structure_of_matrix(A),
                                      structure_of_matrix(B))


# -- kernel implementations -------------------------------------------------
# Every builder returns ``run(a_blocks, b_blocks, slots, pa, pb) ->
# [n_out, bs, bs] tile stack`` — the uniform contract ops/spgemm.py's
# runner cache dispatches through.


def _make_pair_kernel(precision, npairs):
    """The original scalar-prefetch kernel: one (A tile, B tile) pair
    per grid step, f32 VMEM accumulate, one flush per slot run."""
    from jax.experimental import pallas as pl

    def kern(slots, pa, pb, a_ref, b_ref, out_ref, acc_ref):
        i = pl.program_id(0)
        s = slots[i]
        first = jnp.logical_or(i == 0,
                               slots[jnp.maximum(i - 1, 0)] != s)
        last = jnp.logical_or(
            i == npairs - 1, slots[jnp.minimum(i + 1, npairs - 1)] != s)

        @pl.when(first)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot(
            a_ref[0], b_ref[0], precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _flush():
            out_ref[0] = acc_ref[:].astype(out_ref.dtype)

    return kern


def _pallas_precision(out_dtype):
    # bf16 payloads run the MXU's native pass; see pallas_spmm
    return (jax.lax.Precision.DEFAULT if out_dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)


def _build_pallas_generic(bs, npairs, n_out, out_dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from matrel_tpu.utils import compat

    prec = _pallas_precision(out_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # slots, pa, pb
        grid=(npairs,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda i, slots, pa, pb: (pa[i], 0, 0)),
            pl.BlockSpec((1, bs, bs), lambda i, slots, pa, pb: (pb[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bs, bs), lambda i, slots, pa, pb: (slots[i], 0, 0)),
        scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
    )
    kernel = pl.pallas_call(
        _make_pair_kernel(prec, npairs),
        name="matrel_pallas_generic",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, bs, bs), out_dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )

    @jax.jit  # matlint: disable=ML010 registry runner — the sanctioned kernel seam's own dispatch program
    def run(a_blocks, b_blocks, slots, pa, pb):
        return kernel(slots, pa, pb, a_blocks.astype(out_dtype),
                      b_blocks.astype(out_dtype))

    return run


def _build_xla_gather(n_out, out_dtype, cfg):
    prec = getattr(jax.lax.Precision, cfg.matmul_precision.upper(),
                   jax.lax.Precision.HIGHEST)

    @jax.jit  # matlint: disable=ML010 registry runner — the sanctioned kernel seam's own dispatch program
    def run(a_blocks, b_blocks, slots, pa, pb):
        common = jnp.promote_types(a_blocks.dtype, b_blocks.dtype)
        ga = jnp.take(a_blocks.astype(common), pa, axis=0)
        gb = jnp.take(b_blocks.astype(common), pb, axis=0)
        part = jax.lax.dot_general(
            ga, gb, (((2,), (1,)), ((0,), (0,))),       # batched tile GEMM
            precision=prec, preferred_element_type=jnp.float32)
        tiles = jax.ops.segment_sum(part, slots, num_segments=n_out)
        return tiles.astype(out_dtype)

    return run


def _grouped_tables(slot: np.ndarray, n_out: int, G: int,
                    npairs: int) -> Tuple[np.ndarray, np.ndarray]:
    """(src, group_slot) for the grouped schedule: each output slot's
    pair run padded to a multiple of G with SENTINEL pairs (index
    ``npairs`` — the appended zero tile), so every grid step retires
    exactly G pairs of its one slot. ``src[j]`` is the pair feeding
    position j of the padded layout; ``group_slot[g]`` the output slot
    of group g. Pairs arrive slot-sorted (pair_structure's contract)."""
    counts = np.bincount(slot, minlength=n_out).astype(np.int64)
    gcounts = np.maximum(-(-counts // G), 1)
    offsets = np.zeros(n_out + 1, np.int64)
    np.cumsum(gcounts * G, out=offsets[1:])
    src = np.full(int(offsets[-1]), npairs, np.int64)
    starts = np.zeros(n_out + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = offsets[slot] + (np.arange(slot.size, dtype=np.int64)
                           - starts[slot])
    src[pos] = np.arange(slot.size, dtype=np.int64)
    group_slot = np.repeat(np.arange(n_out, dtype=np.int32),
                           gcounts.astype(np.int64))
    return src, group_slot


def _make_grouped_kernel(precision, n_groups):
    """Grouped schedule: one grid step retires G pairs of one output
    slot as a single (bs, G·bs)x(G·bs, bs) MXU contraction over the
    PRE-GATHERED contiguous payload (built eagerly once per operand
    pair — the pallas_spmm payload-memo idiom). G× fewer grid steps
    and no per-pair prefetch indirection; sentinel pairs multiply zero
    tiles and contribute nothing."""
    from jax.experimental import pallas as pl

    def kern(gslots, a_ref, b_ref, out_ref, acc_ref):
        i = pl.program_id(0)
        s = gslots[i]
        first = jnp.logical_or(i == 0,
                               gslots[jnp.maximum(i - 1, 0)] != s)
        last = jnp.logical_or(
            i == n_groups - 1,
            gslots[jnp.minimum(i + 1, n_groups - 1)] != s)

        @pl.when(first)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot(
            a_ref[0], b_ref[0], precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _flush():
            out_ref[0] = acc_ref[:].astype(out_ref.dtype)

    return kern


def _bake_grouped(a_masked, b_masked, pa, pb, src, bs, G, out_dtype):
    """Pre-gather the pair payloads into grouped kernel order, EAGERLY
    (ensure_compile_time_eval — traced baking would poison the runner
    cache with tracers, the spmm transpose-memo lesson): A groups land
    as (n_groups, bs, G·bs) row-concatenated tiles, B groups as
    (n_groups, G·bs, bs) stacks, so one jax.lax.dot per step contracts
    the whole group."""
    n_groups = src.size // G
    with jax.ensure_compile_time_eval():
        az = jnp.concatenate(
            [a_masked.astype(out_dtype),
             jnp.zeros((1, bs, bs), out_dtype)])
        bz = jnp.concatenate(
            [b_masked.astype(out_dtype),
             jnp.zeros((1, bs, bs), out_dtype)])
        pa_ext = np.concatenate(
            [np.asarray(pa, np.int64), [a_masked.shape[0]]])
        pb_ext = np.concatenate(
            [np.asarray(pb, np.int64), [b_masked.shape[0]]])
        ga = jnp.take(az, jnp.asarray(pa_ext[src]), axis=0)
        ga = ga.reshape(n_groups, G, bs, bs).transpose(0, 2, 1, 3) \
            .reshape(n_groups, bs, G * bs)
        gb = jnp.take(bz, jnp.asarray(pb_ext[src]), axis=0) \
            .reshape(n_groups, G * bs, bs)
        # DEFAULT placement, not the payload stacks' committed
        # replicated sharding: replicated-committed inputs make the
        # (non-partitionable) pallas_call execute once PER REPLICA —
        # measured 9× on the 8-device CPU mesh. The consumer
        # (spgemm/apply_dense) re-applies its sharding constraint to
        # the output as it always did.
        ga = jnp.asarray(np.asarray(ga))
        gb = jnp.asarray(np.asarray(gb))
    return ga, gb


def _grouped_call(bs, G, n_groups, n_out, out_dtype, interpret,
                  local_out=None):
    """The pallas_call of one grouped bucket. ``local_out`` (powerlaw
    buckets) compacts the output stack to the bucket's own slots."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from matrel_tpu.utils import compat

    prec = _pallas_precision(out_dtype)
    out_n = local_out if local_out is not None else n_out
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # group_slot
        grid=(n_groups,),
        in_specs=[
            pl.BlockSpec((1, bs, G * bs), lambda i, gs: (i, 0, 0)),
            pl.BlockSpec((1, G * bs, bs), lambda i, gs: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, bs), lambda i, gs: (gs[i], 0, 0)),
        scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
    )
    return pl.pallas_call(
        _make_grouped_kernel(prec, n_groups),
        name="matrel_pallas_grouped",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_n, bs, bs), out_dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _adaptive_group(counts: np.ndarray, requested: int, bs: int,
                    itemsize: int) -> int:
    """Effective G for one grouped schedule: the MEDIAN slot-run
    length, clamped by the spec's request and the VMEM budget. A fixed
    G pads every short run to the group width (measured 17× SLOWER
    than the generic kernel on a band whose runs are 2–3 pairs — the
    very padding pathology the powerlaw bucketing exists to avoid), so
    the group tracks what the structure actually offers; floor 2
    (G == 1 is the generic schedule with extra copies)."""
    if counts.size == 0:
        return 2
    med = int(np.median(counts[counts > 0])) if np.any(counts > 0) else 1
    return max(2, min(grouped_factor(bs, requested, itemsize),
                      max(med, 2)))


def _build_grouped(A, B, bs, pairs, n_out, out_dtype, interpret, G):
    """Band/cluster builder: ONE grouped schedule over all slots."""
    from matrel_tpu.ops import spgemm as spgemm_lib
    slot, pa, pb = pairs
    counts = np.bincount(np.asarray(slot, np.int64), minlength=n_out)
    G = _adaptive_group(counts, G, bs, _itemsize(out_dtype))
    src, group_slot = _grouped_tables(np.asarray(slot, np.int64), n_out,
                                      G, int(np.asarray(pa).size))
    ga, gb = _bake_grouped(spgemm_lib._edge_masked(A),
                           spgemm_lib._edge_masked(B),
                           pa, pb, src, bs, G, out_dtype)
    kernel = _grouped_call(bs, G, group_slot.size, n_out, out_dtype,
                           interpret)

    @jax.jit  # matlint: disable=ML010 registry runner — the sanctioned kernel seam's own dispatch program
    def _run(gs, a, b):
        return kernel(gs, a, b)

    gs_dev = jnp.asarray(group_slot)

    def run(a_blocks, b_blocks, slots, pa_, pb_):
        # per-call args are identical by construction (the runner cache
        # keys on both operand ids); the grouped payload was baked from
        # the same masked stacks at build time
        del a_blocks, b_blocks, slots, pa_, pb_
        return _run(gs_dev, ga, gb)

    run.consumes_args = False    # baked: callers may skip transfers
    return run


def _band_call(bs, wa, rc, gr, nchunks, out_dtype, interpret):
    """The pallas_call of the band schedule: one step contracts a
    (bs, wa·bs) A strip with a (wa·bs, rc·bs) B chunk."""
    from jax.experimental import pallas as pl
    from matrel_tpu.utils import compat

    prec = _pallas_precision(out_dtype)

    def kern(a_ref, b_ref, out_ref):
        out_ref[0] = jax.lax.dot(
            a_ref[0], b_ref[0], precision=prec,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    return pl.pallas_call(
        kern,
        name="matrel_pallas_band",
        grid=(gr, nchunks),
        in_specs=[
            pl.BlockSpec((1, bs, wa * bs), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, wa * bs, rc * bs),
                         lambda i, j: (i * nchunks + j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, rc * bs),
                               lambda i, j: (i * nchunks + j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gr * nchunks, bs, rc * bs),
                                       out_dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )


def _build_band(A, B, bs, pairs, n_out, out_dtype, interpret, wmax,
                out_rows, out_cols):
    """Band builder — WALK THE DIAGONAL: per A block-row the k-band
    (the row's contiguous contraction tiles) and the output col-band
    are both narrow, so ONE grid step computes the row's ENTIRE output
    band as a single (bs, Wa·bs)x(Wa·bs, Rc·bs) MXU contraction over
    CONTIGUOUSLY BAKED row strips (sequential DMA down the diagonal —
    no scalar-prefetch indirection, no revisit accumulation, no
    predicates). Grid = block-rows × column chunks: orders of
    magnitude fewer steps than one-pair-per-step, which is where both
    the Mosaic grid overhead and the interpret-mode cost live. Exactly
    the schedule that would drown a power-law shape (every row padded
    to the hub width) — which is why it is the row_band
    specialization, not the default. Rows whose bands exceed the
    VMEM-feasible width fall back to the grouped schedule."""
    from matrel_tpu.ops import spgemm as spgemm_lib

    out_rows = np.asarray(out_rows, np.int64)
    out_cols = np.asarray(out_cols, np.int64)
    a_rows = np.asarray(A.block_rows, np.int64)
    a_cols = np.asarray(A.block_cols, np.int64)
    b_rows = np.asarray(B.block_rows, np.int64)
    b_cols = np.asarray(B.block_cols, np.int64)
    gr = A.grid[0]
    gcb = B.grid[1]

    def _span(idx, vals, size):
        lo = np.full(size, np.iinfo(np.int64).max)
        hi = np.full(size, -1)
        np.minimum.at(lo, idx, vals)
        np.maximum.at(hi, idx, vals)
        return lo, hi

    kmin, kmax = _span(a_rows, a_cols, gr)
    cmin, cmax = _span(out_rows, out_cols, gr)
    live = kmax >= 0
    wa = int(max((kmax - kmin + 1)[live].max(initial=1), 1))
    rr = int(max((cmax - cmin + 1)[live &
                                   (cmax >= 0)].max(initial=1), 1))
    # VMEM feasibility: the A strip + one B chunk + the out chunk as
    # Mosaic counts one step — chunk the output band when it does not
    # fit, fall back entirely when even Rc = 1 does not
    isz = _itemsize(out_dtype)
    tile = bs * bs
    fixed = dot_step_vmem_bytes(wa * tile, 0, 0, isz, acc=False)
    per_rc = dot_step_vmem_bytes(0, wa * tile, tile, isz, acc=False)
    rc = int(min(rr, max(VMEM_SCOPED_LIMIT_BYTES - fixed, 0) // per_rc))
    if rc < 1 or wa > grouped_factor(bs, max(wmax, 2), isz) * 2:
        return _build_grouped(A, B, bs, pairs, n_out, out_dtype,
                              interpret, wmax)
    nchunks = -(-rr // rc)

    def _lookup(rows, cols, gc_):
        keys = rows * gc_ + cols
        order = np.argsort(keys)
        return keys[order], order

    akeys, aorder = _lookup(a_rows, a_cols, A.grid[1])
    bkeys, border = _lookup(b_rows, b_cols, gcb)

    def _find(keys_sorted, order, want, nnzb):
        """payload index per wanted key, nnzb (the appended zero tile)
        where absent."""
        pos = np.searchsorted(keys_sorted, want)
        pos = np.clip(pos, 0, keys_sorted.size - 1)
        hit = keys_sorted[pos] == want
        return np.where(hit, order[pos], nnzb).astype(np.int64)

    rows_i = np.arange(gr)
    k_of = np.clip(kmin, 0, None)[:, None] + np.arange(wa)[None, :]
    k_valid = k_of <= np.where(live, kmax, -1)[:, None]
    a_want = rows_i[:, None] * A.grid[1] + np.clip(k_of, 0,
                                                   A.grid[1] - 1)
    a_idx = _find(akeys, aorder, a_want.ravel(), A.nnzb)
    a_idx = np.where(k_valid.ravel(), a_idx, A.nnzb)

    c_of = np.clip(cmin, 0, None)[:, None] \
        + np.arange(nchunks * rc)[None, :]
    c_valid = c_of <= np.where(cmax >= 0, cmax, -1)[:, None]
    b_want = (np.repeat(k_of[:, :, None], nchunks * rc, axis=2) * gcb
              + np.clip(c_of, 0, gcb - 1)[:, None, :])
    b_ok = k_valid[:, :, None] & c_valid[:, None, :]
    b_idx = _find(bkeys, border, b_want.ravel(), B.nnzb)
    b_idx = np.where(b_ok.ravel(), b_idx, B.nnzb)

    with jax.ensure_compile_time_eval():
        az = jnp.concatenate(
            [spgemm_lib._edge_masked(A).astype(out_dtype),
             jnp.zeros((1, bs, bs), out_dtype)])
        bz = jnp.concatenate(
            [spgemm_lib._edge_masked(B).astype(out_dtype),
             jnp.zeros((1, bs, bs), out_dtype)])
        # A strips (gr, bs, wa·bs); B strips (gr·nchunks, wa·bs, rc·bs)
        ga = jnp.take(az, jnp.asarray(a_idx), axis=0) \
            .reshape(gr, wa, bs, bs).transpose(0, 2, 1, 3) \
            .reshape(gr, bs, wa * bs)
        gb = jnp.take(bz, jnp.asarray(b_idx), axis=0) \
            .reshape(gr, wa, nchunks, rc, bs, bs) \
            .transpose(0, 2, 1, 4, 3, 5) \
            .reshape(gr * nchunks, wa * bs, rc * bs)
        # default placement (see _bake_grouped)
        ga = jnp.asarray(np.asarray(ga))
        gb = jnp.asarray(np.asarray(gb))
        # out slot -> flat (row, chunk, col-in-chunk) tile position
        sel = (out_rows * nchunks * rc
               + (out_cols - np.clip(cmin, 0, None)[out_rows]))
        sel_dev = jnp.asarray(sel)

    kernel = _band_call(bs, wa, rc, gr, nchunks, out_dtype, interpret)

    @jax.jit  # matlint: disable=ML010 registry runner — the sanctioned kernel seam's own dispatch program
    def _run(a, b, sel_):
        rowout = kernel(a, b)
        flat = rowout.reshape(gr * nchunks, bs, rc, bs) \
            .transpose(0, 2, 1, 3).reshape(gr * nchunks * rc, bs, bs)
        return jnp.take(flat, sel_, axis=0)

    def run(a_blocks, b_blocks, slots, pa_, pb_):
        del a_blocks, b_blocks, slots, pa_, pb_
        return _run(ga, gb, sel_dev)

    run.consumes_args = False    # baked: callers may skip transfers
    return run


def _build_bucketed(A, B, bs, pairs, n_out, out_dtype, interpret,
                    g_light, g_heavy, split):
    """Powerlaw builder: output slots bucketed by pair-run length —
    light rows (run <= split) pad only to g_light, hub rows run the
    wide g_heavy group — then one tile-level scatter recombines. The
    "never pad the MXU to the max row" schedule."""
    from matrel_tpu.ops import spgemm as spgemm_lib
    slot, pa, pb = pairs
    slot = np.asarray(slot, np.int64)
    pa = np.asarray(pa)
    pb = np.asarray(pb)
    counts = np.bincount(slot, minlength=n_out)
    heavy_slots = np.nonzero(counts > split)[0]
    light_slots = np.nonzero(counts <= split)[0]
    a_m = spgemm_lib._edge_masked(A)
    b_m = spgemm_lib._edge_masked(B)

    buckets = []
    for slots_sel, G in ((light_slots, g_light), (heavy_slots, g_heavy)):
        if slots_sel.size == 0:
            continue
        G = _adaptive_group(counts[slots_sel], G, bs,
                            _itemsize(out_dtype))
        # compact this bucket's pairs onto local slot ids (slot-sorted
        # order is preserved, so the grouped tables stay run-coherent)
        local_of = np.full(n_out, -1, np.int64)
        local_of[slots_sel] = np.arange(slots_sel.size)
        mask = local_of[slot] >= 0
        bslot = local_of[slot[mask]]
        bpa, bpb = pa[mask], pb[mask]
        src, group_slot = _grouped_tables(bslot, int(slots_sel.size), G,
                                          int(bpa.size))
        ga, gb = _bake_grouped(a_m, b_m, bpa, bpb, src, bs, G,
                               out_dtype)
        kernel = _grouped_call(bs, G, group_slot.size, n_out, out_dtype,
                               interpret, local_out=int(slots_sel.size))
        buckets.append((kernel, jnp.asarray(group_slot), ga, gb,
                        jnp.asarray(slots_sel.astype(np.int32))))

    kernels = [b[0] for b in buckets]

    @jax.jit  # matlint: disable=ML010 registry runner — the sanctioned kernel seam's own dispatch program
    def _run(*flat):
        # baked arrays arrive as ARGUMENTS, never closed-over: a
        # zero-arg jit would trace the multi-GB payload stacks as
        # embedded constants (compile-memory + HBM duplication — the
        # _build_grouped/_build_band calling convention)
        out = jnp.zeros((n_out, bs, bs), out_dtype)
        for i, kernel in enumerate(kernels):
            gs, ga, gb, ids = flat[4 * i:4 * i + 4]
            out = out.at[ids].set(kernel(gs, ga, gb))
        return out

    flat_args = tuple(x for b in buckets for x in b[1:])

    def run(a_blocks, b_blocks, slots, pa_, pb_):
        del a_blocks, b_blocks, slots, pa_, pb_
        return _run(*flat_args)

    run.consumes_args = False    # baked: callers may skip transfers
    return run


def build_runner(kernel_id: str, A, B, cfg: MatrelConfig,
                 interpret: bool, pairs, n_out: int, out_dtype):
    """Build the device runner for one registered kernel over one
    operand pair — the single constructor ops/spgemm.py's runner cache
    calls. ``pairs`` is the host (slot, pa, pb, out_rows, out_cols)
    structure from pair_structure (slot-sorted; the band schedule also
    reads the output tile coordinates)."""
    spec = REGISTRY[kernel_id]
    bs = A.block_size
    slot, pa, pb, out_rows, out_cols = pairs
    npairs = int(np.asarray(pa).size)
    pairs3 = (slot, pa, pb)
    if kernel_id == "xla_gather":
        return _build_xla_gather(n_out, out_dtype, cfg)
    if kernel_id == "pallas_generic":
        return _build_pallas_generic(bs, npairs, n_out, out_dtype,
                                     interpret)
    isz = _itemsize(out_dtype)
    G = grouped_factor(bs, spec.group, isz)
    if spec.bucket_split > 0:
        return _build_bucketed(A, B, bs, pairs3, n_out, out_dtype,
                               interpret,
                               g_light=max(2, grouped_factor(bs, 2, isz)),
                               g_heavy=G,
                               split=spec.bucket_split)
    if kernel_id == "pallas_band":
        return _build_band(A, B, bs, pairs3, n_out, out_dtype,
                           interpret, spec.group, out_rows, out_cols)
    return _build_grouped(A, B, bs, pairs3, n_out, out_dtype,
                          interpret, G)


# -- structure-shaped operand synthesis (autotune probes, bench, soak) ------

#: Minimum tiles a synthetic hub row carries (keeps the powerlaw probe
#: skewed even on tiny dry grids).
POWERLAW_PROBE_HUB_MIN = 12


def synthesize_structure(structure: str, n: int, bs: int, mesh,
                         seed: int = 0, dtype="float32"):
    """A BlockSparseMatrix whose tile layout EXHIBITS one structure
    class — the shared generator behind the autotune measurement
    probes, tests/test_kernel_registry.py and the soak battery, so all
    three measure the population the classifier actually bins."""
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from jax.sharding import NamedSharding, PartitionSpec as P

    gr = gc = max(2, math.ceil(n / bs))
    rng = np.random.default_rng(seed)
    if structure == "row_band":
        bw = 5                     # tile offsets -2..2 (stencil-ish)
        r = np.repeat(np.arange(gr), bw)
        c = r + np.tile(np.arange(bw) - bw // 2, gr)
        keep = (c >= 0) & (c < gc)
        rows, cols = r[keep], c[keep]
    elif structure == "clustered_tile":
        ncl = max(2, gr // 8)
        cb = 4
        rows_l, cols_l = [], []
        for _ in range(ncl):
            cr = int(rng.integers(0, max(gr - cb, 1)))
            cc = int(rng.integers(0, max(gc - cb, 1)))
            ii, jj = np.meshgrid(np.arange(cb), np.arange(cb),
                                 indexing="ij")
            rows_l.append(cr + ii.ravel())
            cols_l.append(cc + jj.ravel())
        rows = np.concatenate(rows_l)
        cols = np.concatenate(cols_l)
    elif structure == "powerlaw_coo":
        hubs = max(2, gr // 16)
        hub_rows = rng.choice(gr, size=hubs, replace=False)
        rows_l = [np.repeat(hub_rows,
                            max(gc // 2, POWERLAW_PROBE_HUB_MIN))]
        cols_l = [rng.integers(0, gc, rows_l[0].size)]
        rows_l.append(np.arange(gr))
        cols_l.append(rng.integers(0, gc, gr))
        rows = np.concatenate(rows_l)
        cols = np.concatenate(cols_l)
    else:
        nnzb = max(4, 2 * gr)
        flat = rng.choice(gr * gc, size=min(nnzb, gr * gc),
                          replace=False)
        rows, cols = flat // gc, flat % gc
    keys = np.unique(rows.astype(np.int64) * gc
                     + cols.astype(np.int64))
    trows = (keys // gc).astype(np.int32)
    tcols = (keys % gc).astype(np.int32)
    payload = jnp.asarray(
        rng.standard_normal((keys.size, bs, bs)).astype(np.float32),
        dtype=dtype)
    rep = NamedSharding(mesh, P())
    return BlockSparseMatrix(
        blocks=jax.device_put(payload, rep),
        block_rows=jax.device_put(trows, rep),
        block_cols=jax.device_put(tcols, rep),
        shape=(gr * bs, gc * bs), block_size=bs, mesh=mesh)


# -- vocabulary -------------------------------------------------------------

register_kernel(KernelSpec(
    kernel_id="xla_gather", structures=(), needs_pallas=False, group=0,
    universal=True,
    description="gather + batched tile GEMM + segment_sum (XLA; "
                "legacy fallback, admissible everywhere)"))
register_kernel(KernelSpec(
    kernel_id="pallas_generic", structures=(), needs_pallas=True,
    group=1, universal=True,
    description="scalar-prefetch pair kernel, one pair per grid step "
                "(the pre-registry Pallas default)"))
register_kernel(KernelSpec(
    kernel_id="pallas_band", structures=("row_band",),
    needs_pallas=True, group=8,
    description="contiguous pre-gathered pair groups along the "
                "diagonal; G pairs per step as one widened MXU "
                "contraction"))
register_kernel(KernelSpec(
    kernel_id="pallas_cluster", structures=("clustered_tile",),
    needs_pallas=True, group=16,
    description="wide accumulate groups over the cluster's long slot "
                "runs (larger VMEM working set, fewer flushes)"))
register_kernel(KernelSpec(
    kernel_id="pallas_powerlaw", structures=("powerlaw_coo",),
    needs_pallas=True, group=8, bucket_split=4,
    description="output rows bucketed by pair count: light rows pad "
                "to a small group, hub rows run the wide one"))

# fused-epilogue hooks per structure class: the home classes of the
# specialized kernels apply zero-preserving epilogues TILE-WISE (their
# output stacks are far smaller than the dense grid — band: O(gr·bw)
# tiles, powerlaw: hub-dominated); "generic" keeps the conservative
# dense application, bit-matching the legacy post-scatter order.
register_epilogue_hook("row_band", "tilewise")
register_epilogue_hook("clustered_tile", "tilewise")
register_epilogue_hook("powerlaw_coo", "tilewise")
register_epilogue_hook("generic", "dense")
