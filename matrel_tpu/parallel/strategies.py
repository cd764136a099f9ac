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
                         →  a row panel of A collected along y (the
                            other devices' slices beside its own), a
                            column panel of B all-gathered along x;
                            local dots over the whole contraction
                            produce that panel of C, sharded P(x, y),
                            with no further comm. One panel each where
                            the chip's memory takes it; more, derived
                            from the budget, where not.
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
from typing import Optional, Tuple

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from matrel_tpu.utils.compat import shard_map

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib

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


#: Rows of a long float32 contraction that ONE dot accumulates (see
#: :func:`dot_in_panels`), and from what length on a contraction counts
#: as long. A float32 sum that one dot accumulates on the MXU drifts
#: with its length. Read on a v5e against float64 (PR 31; the diagonal
#: of the Gram of 2,555,904 rows of uniform [-1, 1), mean over its 1000
#: entries): +1.83e-4 as one dot at ``highest``, worse than one bfloat16
#: pass (+4.4e-6 at ``default``); -1.19e-5 in panels of 65,536 rows,
#: -4.3e-7 in panels of 8,192, -3.2e-8 in panels of 2,048, for 163.9,
#: 184.2, 168.1 and 174.6 ms. A batched dot over reshaped panels is no
#: cure: the compiler makes one dot of it again (+1.83e-4). Every
#: contraction of the benchmark's other cells is shorter than the
#: threshold.
ACC_PANEL_ROWS = 8192
LONG_CONTRACTION = 1 << 17


def _sum_of_panels(length: int, panel, zero):
    """The sum over a contraction of ``length`` of ``panel(start,
    rows)`` (an array, or a tuple of them, shaped like ``zero``), one
    panel of :data:`ACC_PANEL_ROWS` a round of a ``fori_loop`` and the
    ragged tail after it, added on the vector unit in that order."""
    add = functools.partial(jax.tree_util.tree_map, jnp.add)
    whole, tail = divmod(length, ACC_PANEL_ROWS)
    # a loop's body is traced even for no round: a contraction shorter
    # than one panel (the rows a kernel's tiles leave over) has none
    out = zero if not whole else jax.lax.fori_loop(
        0, whole,
        lambda i, acc: add(acc, panel(i * ACC_PANEL_ROWS, ACC_PANEL_ROWS)),
        zero)
    if tail:
        out = add(out, panel(whole * ACC_PANEL_ROWS, tail))
    return out


def dot_in_panels(a, ca: int, b, cb: int,
                  config: Optional[MatrelConfig] = None,
                  reduce=None) -> jax.Array:
    """The float32 product of ``a`` and ``b`` contracted over ``a``'s
    dimension ``ca`` and ``b``'s ``cb``, the contraction cut into panels
    of :data:`ACC_PANEL_ROWS`: each panel is one dot with an accumulator
    of its own, and the panels' products are added on the vector unit
    (312 additions for 2.5M rows). The operands come as they lie — a
    transposed one by its dimension, not as ``x.T`` — because a loop
    takes its operands whole: a transposed 10 GB table in front of the
    loop is a second table (compiled for a v5e: refused at 20.5 of 15.75
    GB), while a panel sliced from the table inside the loop is read in
    place (temporaries: none). ``reduce`` is :func:`over_own_rows`':
    applied to the sum of the panels where ``a`` and ``b`` are one
    device's share of the contraction."""
    dims = (((ca,), (cb,)), ((), ()))
    prec = _precision(config)

    def panel(start, rows):
        return jax.lax.dot_general(
            jax.lax.dynamic_slice_in_dim(a, start, rows, axis=ca),
            jax.lax.dynamic_slice_in_dim(b, start, rows, axis=cb),
            dims, precision=prec, preferred_element_type=jnp.float32)

    out = _sum_of_panels(
        a.shape[ca], panel,
        jnp.zeros((a.shape[1 - ca], b.shape[1 - cb]), jnp.float32))
    return out if reduce is None else reduce(out)


#: Width of a block column of a long Gram's upper block triangle
#: (:func:`gram_in_panels`): a multiple of the 128 lanes, so a block is
#: cut at a tile's edge however the table lies. Read on a v5e (PR 32;
#: the Gram of 2,555,904 x 1000 float32 at ``highest``, host clock
#: around six calls each, spread under 0.2 ms): the full square 168.1
#: ms; width 256 (4 dots a panel, 62.5% of the square's operations)
#: 118.5; width 128 (8 dots, 56.3%) 120.7; 256 then 128s 119.7; 384
#: then 128s 122.2; 512 then 128s 126.9; block ROWS instead of columns
#: 119.3 (256) and 119.1 (128). Every dot of a panel costs about 10
#: us beside its operations at the full square's rate (traced: the four
#: block columns run at 73, 80, 83 and 87% of the six-pass peak, the
#: square at 93%; no gap between them), so the fewer dots win what the
#: finer triangle saves. The finer triangle is ONE kernel's
#: (:func:`gram_in_tiles`, PR 55): the plans that still take this loop
#: are those planner.gram_kernel_plan declines by name — a mesh (a
#: device at a time, cell ``linreg_10m_2x2``), a table that lies by
#: rows, ``a * t(a)``, k under two blocks of 128 (the NMF cells' ``t(W)
#: * W``) or in ragged sublanes, no Pallas executor (the CPU).
GRAM_BLOCK = 256


def gram_blocks(k: int) -> Tuple[Tuple[int, int], ...]:
    """(start, end) of the block columns a Gram of ``k`` columns is cut
    into: :data:`GRAM_BLOCK` wide, the last one ragged."""
    return tuple((s, min(s + GRAM_BLOCK, k))
                 for s in range(0, k, GRAM_BLOCK))


def gram_tiles(k: int) -> Tuple[int, int]:
    """(computed, of): the block products a panel of
    :func:`gram_in_panels` multiplies, of those the square holds."""
    nb = len(gram_blocks(k))
    return nb * (nb + 1) // 2, nb * nb


def gram_rider_room(k: int) -> int:
    """How many right-hand-side columns ride a Gram of ``k`` columns
    (:func:`gram_in_panels` ``rhs``): the lanes the last block column
    leaves spare in its MXU tiles of 128, as far as there are columns
    below the block to widen its slice into. 24 at k = 1000 (232 of
    256 lanes), 0 where k is a multiple of 128 or one block."""
    s, e = gram_blocks(k)[-1]
    return min(-(e - s) % 128, s)


def gram_in_panels(a, ca: int, config: Optional[MatrelConfig] = None,
                   rhs=None, reduce=None):
    """The float32 Gram of ``a`` contracted with itself over its
    dimension ``ca`` (``t(a) * a`` for 0, ``a * t(a)`` for 1), in the
    panels of :func:`dot_in_panels` — the lowering of every long Gram
    that planner.gram_kernel_plan does not hand to ONE kernel
    (:func:`gram_in_tiles`: one device, ``t(a) * a``, the table's rows
    on the lanes), in either layout of the table and on a mesh — each
    panel multiplying the upper
    block triangle alone: block column j is one dot of the panel's
    first ``end_j`` columns with its block j, with an accumulator of
    its own, and the lower triangle is one mirror of the k x k result
    after the loop. An entry above the diagonal is a sum of the same
    panels' 8,192-row dots as in the square (a narrower dot may add a
    panel's products in another order: 2.9e-7 of the largest entry
    apart at most on a v5e); an entry below it is a copy, so the result
    is symmetric bit for bit (the square's is not). ``a``
    comes as it lies and is sliced inside the loop: compiled for a v5e
    each block column is one convolution fusion that reads its two
    slices of the table in place, in either layout of the table.

    ``rhs`` (``(rows, m)`` float32, ``m`` at most
    :func:`gram_rider_room`) are right-hand sides contracted over the
    same rows. They ride the last block column: its right operand is
    the table's slice widened by ``m`` columns on its low side with the
    riders selected over those columns, its accumulator ``(k, w + m)``,
    and the result is the pair (Gram, ``t(a) * rhs``) for ONE pass over
    ``a``; every Gram entry is the sum of the same panels' dots as
    without riders. Three ways to hand the riders to that dot, compiled
    for a v5e at 2,555,904 x 1000 and m = 1 (PR 34; estimated cycles of
    a panel's operations, 530,494 without riders): the select, fused
    into the convolution with the slice read in place, 540,992 (m = 8:
    540,892; m = 24: 549,788); ``concatenate([slice, y])``, fused as
    well but with the 232 columns staged into fast memory first (a
    ``dynamic-slice`` of 43,294 cycles), 579,630; and
    :func:`dot_in_panels`' multiply-reduce as a loop-mate of the dots,
    683,651: what it costs in a loop of its own, since one fusion runs
    at a time.

    ``reduce`` is :func:`over_own_rows`': where ``a`` (and ``rhs``) are
    one device's rows of a table that lies by rows on a mesh, it sums
    the block columns' accumulators over the devices, riders and all
    (:func:`gram_reduce_bytes`: 2.5 MB at k = 1000, where the mirrored
    Gram would be 4), BEFORE the mirror: an entry below the diagonal
    stays a copy, so the mesh's Gram is symmetric bit for bit too,
    whatever order the all-reduce adds its four terms in."""
    free = 1 - ca
    k = a.shape[free]
    blocks = gram_blocks(k)
    dims = (((ca,), (ca,)), ((), ()))
    prec = _precision(config)
    m = 0 if rhs is None else rhs.shape[1]

    def panel(start, rows):
        p = jax.lax.dynamic_slice_in_dim(a, start, rows, axis=ca)
        rights = [jax.lax.slice_in_dim(p, s, e, axis=free)
                  for s, e in blocks]
        if m:
            s, e = blocks[-1]
            wide = jax.lax.slice_in_dim(p, s - m, e, axis=free)
            riders = jax.lax.dynamic_slice_in_dim(rhs, start, rows, axis=0)
            pad = [(0, 0), (0, 0)]
            pad[free] = (0, e - s)
            rights[-1] = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, wide.shape, free) < m,
                jnp.pad(riders.T if ca else riders, pad), wide)
        return tuple(
            jax.lax.dot_general(
                jax.lax.slice_in_dim(p, 0, e, axis=free), right,
                dims, precision=prec, preferred_element_type=jnp.float32)
            for (_, e), right in zip(blocks, rights))

    cols = list(_sum_of_panels(
        a.shape[ca], panel,
        tuple(jnp.zeros((e, e - s + (m if e == k else 0)), jnp.float32)
              for s, e in blocks)))
    if reduce is not None:
        cols = list(reduce(tuple(cols)))
    rode, cols[-1] = cols[-1][:, :m], cols[-1][:, m:]
    upper = jnp.concatenate(
        [jnp.pad(c, ((0, k - c.shape[0]), (0, 0))) for c in cols], axis=1)
    gram = jnp.where(jnp.tri(k, dtype=bool), upper.T, upper)
    return gram if rhs is None else (gram, rode)


def gram_in_tiles(a, config: Optional[MatrelConfig] = None, rhs=None, *,
                  tile: int, interpret: bool = False):
    """:func:`gram_in_panels`' answer for ``t(a) * a`` (and ``t(a) *
    rhs``) on ONE device over a table whose rows lie on the lanes, as
    ONE kernel (ops/gram_kernel.py: the upper triangle in blocks of 128
    a row tile of ``tile``, the table read once, the riders inside it)
    where planner.gram_kernel_plan says ``one_read``. The rows beyond
    the last whole tile (fewer than ``tile``) go through
    :func:`gram_in_panels` and :func:`dot_in_panels` on the tail slice,
    and the lower triangle is the same ONE mirror: symmetric bit for
    bit."""
    from matrel_tpu.ops import gram_kernel
    n, k = a.shape
    head = n // tile * tile
    upper, rode = gram_kernel.gram_upper(
        a, rhs, tile=tile, precision=_precision(config),
        interpret=interpret)
    gram = jnp.where(jnp.tri(k, dtype=bool), upper.T, upper)
    if head < n:
        rest = jax.lax.slice_in_dim(a, head, n, axis=0)
        gram = gram + gram_in_panels(rest, 0, config)
        if rhs is not None:
            rode = rode + dot_in_panels(
                rest, 0, jax.lax.slice_in_dim(rhs, head, n, axis=0), 0,
                config)
    return gram if rhs is None else (gram, rode)


def gram_reduce_bytes(k: int, m: int = 0) -> int:
    """Bytes of the block-column accumulators of :func:`gram_in_panels`
    over ``k`` columns with ``m`` riders: what its ``reduce`` moves a
    device (2,504,864 at k = 1000 with one rider)."""
    return 4 * sum(e * (e - s + (m if e == k else 0))
                   for s, e in gram_blocks(k))


def over_own_rows(mesh: Mesh, body, operands, contracted):
    """``body(reduce, *operands)`` where every operand lies cut over
    ALL the mesh's devices along the dimension it is contracted over
    (``contracted``: 0 for a table that lies by rows, 1 for one that
    lies by columns): inside ONE ``shard_map`` each device runs
    ``body`` on the share it holds, read in place (the in_specs are the
    operands' own layout: nothing is gathered, nothing transposed), and
    ``reduce`` is one ``psum`` over both mesh axes of whatever ``body``
    hands it; what ``body`` returns comes out replicated. Upstream's
    cross-product multiply over a ``RowPartitioner``'s partitions
    (SURVEY.md section 2), with an all-reduce where Spark reduces by
    key. On one device there is nothing to reduce and no ``shard_map``:
    ``body(None, *operands)`` is the whole program, the same code."""
    if mesh.size == 1:
        return body(None, *operands)
    axes = tuple(mesh.axis_names)

    def reduce(partial):
        return jax.lax.psum(partial, axes)

    # check_vma=False: the loop's accumulators start as zeros that vary
    # over no axis and are added to products that vary over both
    return shard_map(
        functools.partial(body, reduce), mesh=mesh,
        in_specs=tuple(P(axes, None) if c == 0 else P(None, axes)
                       for c in contracted),
        out_specs=P(), check_vma=False)(*operands)


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
    (SURVEY.md §2 CPMM). Both operands are re-laid to these specs as
    arrays, a transposed one first transposed: a tall table whose Gram is
    wanted cannot take this path at the size of a chip's memory (its
    `t(X)` is a second table, X over `y` alone twice a device's share),
    and one that lies by rows over all devices does not: it is multiplied
    where it lies (:func:`over_own_rows`, the stamp ``cpmm_rows``)."""
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


def acc_itemsize(itemsize: int) -> int:
    """Bytes of the accumulator a product of ``itemsize``-byte operands
    carries (_acc_dtype: bf16 and int8 accumulate in four bytes)."""
    return max(int(itemsize), 4)


def rmm_moves_under_dot(pk: int, gy: int, panels: Tuple[int, int]) -> int:
    """How many moves of A's slices a row panel of the panelled rmm
    issues ahead of a dot that hides them: the other gy − 1 devices'
    slices where the contraction is cut along the mesh row and the
    product runs in column panels (panel 0 is then multiplied before
    the loop over the others, its own chunk first, while the slices
    are on their way); 0 where there is no move or no loop."""
    return gy - 1 if gy > 1 and pk % gy == 0 and panels[1] > 1 else 0


def rmm_transient_bytes(pn: int, pk: int, pm: int, gx: int, gy: int,
                        itemsize: int, panels: Tuple[int, int]) -> float:
    """What the panelled rmm allocates on one device beside its operand
    shards as they lie and its stored output: the other devices' slices
    of one row panel of A (gy − 1 of them; its own it reads in place),
    one gathered column panel of B (none on a one-row grid) — three
    where there are several: the one multiplied, the next in flight,
    and the one more that the compiler's loop pipelining was seen to
    keep — with the slice of it that a chunk of the contraction reads,
    and the product's panel in the accumulator's width (two on a grid
    with columns, where the chunks' partial products are summed; none
    where one dot stores what it accumulates). Held beside the chip's
    compiler at 65536² bf16 on a 2×2 mesh it reads 4.75 / 3.4 GiB at
    8 / 16 column panels where the compiler's own temporaries were
    5.0 / 3.5 (PR 27). Where column panel 0 is multiplied ahead of the
    loop (:func:`rmm_moves_under_dot`) it leaves its dots rounded, as a
    panel of its own, before the stored output is made around it: one
    panel in the storage width beside everything the loop holds (0.25
    GiB at 8 panels there: 5.0 GiB, the described chip's compile of
    both products 7.25 beside 7.0; PR 30)."""
    r, c = panels
    rows, cols = pn / gx / r, pm / gy / c
    acc = acc_itemsize(itemsize)
    out = rows * (pk / gy) * (gy - 1) * itemsize
    if gx > 1:
        out += (3 if c > 1 else 1) * pk * cols * itemsize
    if gy > 1:
        out += (pk / gy) * cols * itemsize + 2 * rows * cols * acc
    elif acc > itemsize:
        out += rows * cols * acc
    if rmm_moves_under_dot(pk, gy, panels):
        out += rows * cols * itemsize
    return out


def rmm_panels(pn: int, pk: int, pm: int, gx: int, gy: int,
               itemsize: int, room: Optional[float]) -> Tuple[int, int]:
    """(row panels, column panels) of the panelled rmm whose transient
    fits ``room`` bytes a device: the fewest row panels (every further
    one gathers B once more), then the fewest column panels. Panels
    halve while they stay whole and at least 128 wide. Where not even
    the narrowest fit, the narrowest (the caller's gate refuses them);
    ``room`` None (the gate is off) is one panel."""
    if room is None:
        return (1, 1)

    def halvings(extent):
        out = [1]
        while extent % (2 * out[-1]) == 0 and extent // (2 * out[-1]) >= 128:
            out.append(2 * out[-1])
        return out

    rows = halvings(pn // gx) if gy > 1 else [1]
    cols = halvings(pm // gy) if gx > 1 else [1]
    for r in rows:
        for c in cols:
            if rmm_transient_bytes(pn, pk, pm, gx, gy, itemsize,
                                   (r, c)) <= room:
                return (r, c)
    return (rows[-1], cols[-1])


def matmul_rmm(a: jax.Array, b: jax.Array, mesh: Mesh,
               config: Optional[MatrelConfig] = None,
               panels: Optional[Tuple[int, int]] = None,
               out_dtype=None) -> jax.Array:
    """Replication-MM, panelled: each device owns every input of a panel
    of its C tile — a row panel of A, as its own slice and the slices of
    the other devices of its mesh row, and a column panel of B gathered
    along x — and computes it over the WHOLE contraction: one local dot
    a chunk of the contraction (one chunk a mesh column), the chunks'
    products summed in the accumulator's dtype, and one rounding, to
    ``out_dtype`` (the storage dtype; None: the accumulator's), as the
    panel leaves. Rounded panels are never summed. The moves are the
    all-gather-shaped cogroup of the reference (SURVEY.md §2 RMM); a
    device's own slice of A is read where it lies, so what is held
    beside the operands is (gy − 1)/gy of A's row panel, not a second
    copy of all of it.

    ``panels`` = (row panels, column panels). One each is the classic
    RMM: every input of the tile at once. More bound the transient to
    one row panel of A, one column panel of B with the next one in
    flight, and the product's panel in the accumulator's width: at
    65536² bf16 on a 2×2 v5e mesh the whole gathers are 8 GiB beside
    8 GiB of tables and intermediate, and cannot be allocated (PERF.md
    §6, PR 27). Every further row panel gathers B once more.

    A loop takes its operands whole, so a move asked for in front of it
    is waited for in front of it, with nothing to run beside it. Where
    there are both (:func:`rmm_moves_under_dot`: column panels, and the
    contraction cut along the mesh row), column panel 0 of every row
    panel is therefore multiplied ahead of the loop, straight-line: its
    own chunk's dot reads no moved slice and runs while they arrive (at
    65536² on the 2×2 mesh a dot of 47 ms over a move of 39 that stood
    exposed, PERF.md §6, PR 30); the loop runs panels 1 … c − 1. Same
    dots, same order of the sums, same one rounding: the answer is the
    loop's bit for bit.

    The planner derives the counts from what its plan leaves of the chip's
    memory (planner.choose_strategy_ex); None derives them here for the
    product taken alone (:func:`rmm_panels` on ``hbm_limit_bytes`` less
    the operands' shards and the output). There is no width knob."""
    x, y = mesh.axis_names
    gx, gy = mesh.shape[x], mesh.shape[y]
    prec = _precision(config)
    acc = _acc_dtype(a, b)
    store = acc if out_dtype is None else out_dtype
    n, k = a.shape
    m = b.shape[1]
    if panels is None:
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        isz = max(a.dtype.itemsize, b.dtype.itemsize)
        p = gx * gy
        panels = rmm_panels(
            n, k, m, gx, gy, isz,
            limit - (n * k + k * m) * isz / p
            - n * m * jnp.dtype(store).itemsize / p if limit > 0 else None)
    r, c = panels

    # the contraction is cut where it divides (a vector's k = 1 does
    # not): an operand whose k stays whole arrives with every slice,
    # and its side runs no collective
    a_cut = gy > 1 and k % gy == 0
    b_cut = gx > 1 and k % gx == 0

    def kernel(ab, bb):
        rows, cols = ab.shape[0] // r, bb.shape[1] // c
        chunk = ab.shape[1]             # of the contraction: k / gy
        j = jax.lax.axis_index(y) if a_cut else 0

        def row_panel(i):
            """A's row panel, a slice a chunk of the contraction: this
            device's own first, then, s steps along the mesh row, the
            slice of the device that holds chunk (j + s) % gy."""
            own = (ab if r == 1 else
                   jax.lax.dynamic_slice_in_dim(ab, i * rows, rows, 0))
            if not a_cut:
                return [own]
            return [own] + [
                jax.lax.ppermute(own, y, [(src, (src - s) % gy)
                                          for src in range(gy)])
                for s in range(1, gy)]

        def col_panel(jc):      # (k, cols): every x-slice of B's panel
            pb = (bb if c == 1 else
                  jax.lax.dynamic_slice_in_dim(bb, jc * cols, cols, 1))
            return (jax.lax.all_gather(pb, x, axis=0, tiled=True)
                    if b_cut else pb)

        def dot(slices, pb, moving=False):
            """The panel of C over the whole contraction, summed in the
            accumulator's dtype, this device's own chunk first; one
            rounding, as the panel leaves. ``moving``: the other
            devices' slices are still on their way."""
            def rows_b(s):
                return (jax.lax.dynamic_slice_in_dim(
                    pb, ((j + s) % gy) * chunk, chunk, 0) if a_cut else pb)

            total = _local_dot(slices[0], rows_b(0), prec, acc)
            moved = slices[1:]
            if moving:
                # what the other chunks' dots read exists only once the
                # own chunk's dot is done, so that dot may not sink
                # below the slices' arrival and the panel is not
                # gathered a second time behind it (under memory
                # pressure the chip's compiler was seen to do both).
                # Orders, computes nothing; the sum below reads the dot
                # itself, so it still rides the last dot's output
                _, moved, pb = jax.lax.optimization_barrier(
                    (total, moved, pb))
            for s, pa in enumerate(moved, 1):
                total = total + _local_dot(pa, rows_b(s), prec, acc)
            return total.astype(store)

        if r == 1 and c == 1:
            return dot(row_panel(0), col_panel(0))

        ahead = rmm_moves_under_dot(k, gy, panels) > 0

        def rows_of(i, out):
            slices = row_panel(i)

            def cols_of(jc, carry, moving=False):
                out, pb = carry
                # the next column panel is asked for before this one is
                # multiplied, so its gather runs under the dots (the
                # last step asks for panel 0 again: every device runs
                # the same collectives)
                nxt = col_panel((jc + 1) % c) if c > 1 else pb
                return jax.lax.dynamic_update_slice(
                    out, dot(slices, pb, moving), (i * rows, jc * cols)), nxt

            carry = out, col_panel(0)
            if ahead:
                # panel 0 ahead of the loop: a loop waits for the moves
                # of ``slices`` in front of it, with no dot beside them
                carry = cols_of(0, carry, moving=True)
            return jax.lax.fori_loop(int(ahead), c, cols_of, carry)[0]

        out0 = compat.pvary(jnp.zeros((ab.shape[0], bb.shape[1]), store),
                            (x, y))
        return jax.lax.fori_loop(0, r, rows_of, out0)

    # neither operand's collectives may start before both operands
    # exist: the compiler otherwise hoists the moves of a catalog table
    # over the product that makes the other operand, and what the plan
    # reckoned for one product is alive during two (seen at 65536²:
    # 2 GiB of the second product's slices through all of the first)
    a, b = jax.lax.optimization_barrier((a, b))
    f = shard_map(kernel, mesh=mesh,
                  in_specs=(P(x, y if a_cut else None),
                            P(x if b_cut else None, y)),
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
               epilogue=None, panels: Optional[Tuple[int, int]] = None,
               out_dtype=None) -> jax.Array:
    """``epilogue`` is the fused-region slot (ir/fusion.py /
    docs/FUSION.md): a traceable callable applied to the strategy's
    output INSIDE the same traced computation, so an absorbed
    elementwise/scalar/reduction chain compiles as the contraction's
    epilogue instead of its own dispatch. None (the default) is the
    historical path, bit-identically. ``panels`` and ``out_dtype`` are
    the panelled rmm's (the planner's panel counts and the storage
    dtype its panels leave the dot in); no other strategy reads
    them."""
    # fault site "strategy": the resilience harness's hook at strategy
    # execution (trace time). One truthiness test when injection is off.
    from matrel_tpu.resilience import faults as faults_lib
    faults_lib.check("strategy", config)
    if strategy == "rmm":
        out = matmul_rmm(a, b, mesh, config, panels=panels,
                         out_dtype=out_dtype)
    else:
        out = MATMUL_IMPLS[strategy](a, b, mesh, config)
    return out if epilogue is None else epilogue(out)
