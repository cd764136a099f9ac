"""Element-sparse COO matrix — the TPU answer to the reference's CSC
local payloads (SURVEY.md §2 "Local matrix kernels": MLlib `SparseMatrix`
is element-granular CSC).

Block-granular sparsity (`core/sparse.py`) is the MXU-idiomatic layout for
matrices whose nonzeros cluster into dense tiles; uniform/graph-shaped
sparsity (1e-5-class densities) would touch every tile. `COOMatrix` covers
that regime: a fixed edge list compiled once into a blocked one-hot SpMV
plan (`ops/spmv.py` — width-row gather + hi/lo one-hot MXU scatter, no
XLA scatter anywhere; on real TPU the compact-table Pallas executor of
`ops/pallas_spmv.py` runs it at 13 B/slot), with transpose plans built
lazily and a plain segment-sum fallback for degree distributions the
planner refuses.

Matvec is the hot op (PageRank-class workloads). `matmat` and the DSL's
`coo_leaf × dense` product take dense sides of up to 128 columns at the
cost of 8: a slot's whole row of the dense side is gathered once (a row
of up to 128 float32 fills 128 lanes whatever it holds) and a chunk-grid
Pallas kernel scatters the rows through plain one-hot MXU products
(`ops/pallas_spmv.py`, "k-wide") — factor matrices (GNMF, rank 128) as
well as the tall-skinny multivector shapes (personalization vectors,
feature panels) this type began with.

Plans are built once a matrix and an orientation and kept with it. Where
the compact executor of one device will run them (`_plan_layout`), the
layout is `"auto"`: skewed matrices lie in chunks (1.01 slots an entry on
a Netflix-shaped ratings matrix where one capacity a block takes 1.25
and leaves 12,873 entries to the scalar tail). Where the dense side of a
k-wide product has more rows than a gather table keeps its row rate for
(`spmv.source_panels`: 64 MB, 131,064 rows of 512 B), the orientation's
plan is a `PanelledPlan`: one compact plan a range of sources, every one
adding into the same output.

A matrix's k-wide plans also have a DENSE PART where its own degrees
ask for one (`DenseLines`, PR 43). Gather and scatter cost by the entry
(2.6 ns an entry a product on a v5e), a dense line by its length; a
ratings matrix's hottest movie columns are 5-48 % dense and its tail
0.01 %. So the lines of one axis that hold more entries than a dense
line costs (`_dense_groups`: in whole groups of 128, in order of degree,
inside a byte budget) leave the coordinate list's compact layout for ONE
slab `(length of the other axis, lines)` on the device, duplicates
summed, kept on the matrix and shared by both orientations and by `.T`:
where the lines are a product's sources it adds `slab @ X[lines]`, where
they are its destinations `Y[lines] += slab^T X`, on the MXU with
float32 sums, a long contraction in panels (`strategies.dot_in_panels`).
The slab is float32 and multiplied at `highest`; it is bfloat16, twice
the lines in the bytes, only where the build has checked that it holds
every value exactly (ratings 1 to 5 in distinct cells: `_bfloat16_holds`
and `DenseLines.fill`), and then multiplies the dense side's three
bfloat16 parts: the same sums. The compact plans hold what is left, laid
out exactly as they would be alone, and `plan_facts`' `entries` counts
those; a matrix none of whose lines pays has no slab and the plans it
always had. It is upstream's own design (a block is a dense or a CSC
payload by its density), by the line instead of by the block.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.ops import spmv as spmv_lib

# plans built in this process, an orientation of a matrix each (a
# PanelledPlan's parts are one build)
_PLAN_BUILDS = 0


def plan_builds() -> int:
    """How many COO plans this process has built (not answered from the
    matrix's memo): the ``hit`` false ``matrel.spmm.plan.build`` spans,
    for a caller outside a profiler session."""
    return _PLAN_BUILDS


def _counted_build(build, **said):
    """``build()`` under its ``matrel.spmm.plan.build`` span (cold: it
    is in ``cold_spans()`` whether or not a session runs, with what
    ``build_spmv_plan`` tallies of its parts), counted."""
    global _PLAN_BUILDS
    with trace_lib.phase("spmm.plan.build", **said):
        plan = build()
    _PLAN_BUILDS += 1
    return plan


def _plan_layout() -> str:
    """The layout a COOMatrix asks ``build_spmv_plan`` for: ``"auto"``
    where the executor that will run the plan takes both layouts (the
    compact-table Pallas executors of ONE device, ops/pallas_spmv.py),
    ``"blocks"`` where the expanded XLA tables or a mesh's sharded
    tables will read it."""
    from matrel_tpu.config import pallas_enabled
    return ("auto" if pallas_enabled() and len(jax.devices()) == 1
            else "blocks")


# The dense part's rule. A line pays where the entries it holds cost
# more on the compact path than its whole length costs on the MXU.
# ``_SPARSE_NS_AN_ENTRY``: a product's gather and scatter, by the entry;
# ``_DENSE_NS_A_CELL``: a slab's cell (128 columns, float32 at six
# bfloat16 passes or an exact bfloat16 slab at three, and its bytes of
# HBM), by the slab's dtype. Read off one sweep of forced widths on the
# Netflix-shaped matrix on a v5e (PERF.md section 6, PR 43: a fit of six
# products a width, 0 to 4,352 lines, least squares to 3 ms of every
# reading): a float32 line of 480,189 cells breaks even at 1,851
# entries, a bfloat16 one at 1,343. They stand on that one matrix.
# Lines come in groups of ``_DENSE_GROUP``, the MXU's contraction width:
# a 129th line costs what a 256th does.
_SPARSE_NS_AN_ENTRY = 2.67
_DENSE_NS_A_CELL = {"float32": 0.0103, "bfloat16": 0.0075}
_DENSE_GROUP = 128
# the share of the device's memory the slab may take (less where the
# plans' other bytes leave less: _dense_room)
_DENSE_SHARE = 0.25
# entries a call of the slab's scatter adds
_SLAB_PIECE = 1 << 22


@dataclasses.dataclass
class DenseLines:
    """The dense part of a COOMatrix's k-wide plans: the ``lines``
    (ascending ids) of ``axis`` (0: rows, 1: columns, of the matrix that
    chose them — its transpose view reads the axis flipped) whose
    ``entries`` of the coordinate list lie in ``slab``, ``(length of the
    other axis, lines up to whole groups of 128)`` on the device, column
    j the line ``lines[j]``, the spare columns zero, a repeated cell's
    values summed. float32 — or bfloat16 (``dtype``) where the choice
    found every value of the matrix exactly a bfloat16's and no cell
    turns out repeated (:meth:`fill` checks; the ratings 1 to 5 are):
    such a slab times the dense side's three bfloat16 parts is the same
    sums as the float32 one at ``highest``, at half the bytes a line.
    Chosen by :func:`_choose_dense_lines`; the slab and the device copy
    of ``lines`` come with the first wide plan's build."""
    axis: int
    lines: np.ndarray
    column_of: np.ndarray       # a line's column of the slab, -1: none
    entries: int
    dtype: str = "float32"
    slab: Optional[jax.Array] = None
    lines_dev: Optional[jax.Array] = None

    @property
    def width(self) -> int:
        return -(-self.lines.size // _DENSE_GROUP) * _DENSE_GROUP

    def holds(self, ids: np.ndarray) -> np.ndarray:
        """Which of the entries whose ids along ``axis`` are ``ids``
        lie on a dense line."""
        return self.column_of[ids] >= 0

    def fill(self, ids, others, vals, length: int) -> bool:
        """Add the dense lines' entries (``ids`` along ``axis``,
        ``others`` along the other, of ``length``) into a new slab, one
        device scatter a piece of ``_SLAB_PIECE`` entries. False, and no
        slab, where a bfloat16 slab would not hold the entries exactly:
        every value is a bfloat16's and none is zero (the choice saw to
        that), so it does unless a cell is listed twice — and then it
        has fewer cells that are not zero than it was given entries."""
        n = ids.size
        with trace_lib.phase(
                "coo.slab.fill", entries=int(n), dtype=self.dtype,
                bytes=length * self.width * np.dtype(self.dtype).itemsize):
            piece = min(_SLAB_PIECE, -(-max(n, 1) // 1024) * 1024)
            # whole pieces: what is over adds zeros to cell (0, 0)
            row, col, val = (np.zeros(-(-n // piece) * piece, dt)
                             for dt in (np.int32, np.int32, np.float32))
            row[:n], col[:n], val[:n] = others, self.column_of[ids], vals
            slab = jnp.zeros((length, self.width), self.dtype)
            for s in range(0, row.size, piece):
                slab = _slab_add(slab, row[s:s + piece], col[s:s + piece],
                                 val[s:s + piece])
            if (self.dtype != "float32"
                    and int(jnp.count_nonzero(slab)) != n):
                return False
        self.slab = slab
        self.lines_dev = jnp.asarray(self.lines, jnp.int32)
        return True


@functools.partial(jax.jit, donate_argnums=0)  # matlint: disable=ML010 a plan build's one-off device scatter, not a query path
def _slab_add(slab, row, col, val):
    return slab.at[row, col].add(val.astype(slab.dtype))


def _dense_room(limit: int, shape, left: np.ndarray) -> np.ndarray:
    """The bytes a slab may take on a device that hands out ``limit``,
    for each count ``left`` of entries that would stay in the compact
    tables: ``_DENSE_SHARE`` of the device, less where the rest — both
    orientations' tables, a panel of gathered rows at its share, and the
    dense side and the output of a 128-wide product three times over (the
    aliased scatter's buffers) — leaves less."""
    from matrel_tpu.ops import pallas_spmv as pc
    rest = (2 * pc.TABLE_BYTES_A_SLOT * left + pc._PANEL_SHARE * limit
            + 3 * 4 * pc.WIDE_COLS * (shape[0] + shape[1]))
    return np.minimum(_DENSE_SHARE * limit, limit - rest)


def _dense_groups(deg: np.ndarray, length: int, nnz: int, shape, limit: int,
                  dtype: str):
    """How many of ``deg``'s lines (of ``length`` cells of ``dtype``
    each), taken in order of degree in groups of ``_DENSE_GROUP``,
    pay as dense lines and fit: a group is taken while its entries cost
    the compact path more than 128 dense lines cost the MXU, and the
    slab with it stays in the room :func:`_dense_room` leaves. Returns
    (the lines' ids in order of degree, the entries they hold)."""
    order = np.argsort(-deg, kind="stable")
    held = np.add.reduceat(deg[order], np.arange(0, order.size, _DENSE_GROUP))
    groups = np.arange(1, held.size + 1)
    pays = held * _SPARSE_NS_AN_ENTRY > (
        _DENSE_GROUP * length * _DENSE_NS_A_CELL[dtype])
    cells = 1.0 * length * _DENSE_GROUP * groups
    # XLA's scatter, which fills the slab, indexes its cells in int32
    fits = (cells < 2 ** 31) & (
        np.dtype(dtype).itemsize * cells <= _dense_room(
            limit, shape, nnz - np.cumsum(held)))
    ok = pays & fits
    take = int(ok.size if ok.all() else np.argmin(ok))
    return order[:take * _DENSE_GROUP], int(held[:take].sum())


def _bfloat16_holds(vals: np.ndarray) -> bool:
    """Whether every value is exactly a bfloat16's and none is zero (a
    slab of such values, no cell repeated, is exact in bfloat16, and its
    cells that are not zero count its entries)."""
    bits = np.ascontiguousarray(vals, np.float32).view(np.uint32)
    return bool(vals.size) and not (bits & 0xFFFF).any() and bool(
        (bits << 1).all())


def _choose_dense_lines(rows, cols, vals, shape, flipped: bool,
                        exact: bool = True) -> Optional[DenseLines]:
    """The dense lines of a matrix, from its degrees alone: of the two
    axes the one whose paying lines hold more entries (the columns on a
    tie); None where no group of lines pays. A bfloat16 slab (twice the
    lines in the bytes) where ``exact`` and :func:`_bfloat16_holds`.
    ``flipped``: the matrix is a transpose view, whose memo is the
    first-built matrix's — the axis is recorded as that one has it."""
    from matrel_tpu.ops import pallas_spmv as pc
    limit = pc._hbm_limit()
    dtype = "bfloat16" if exact and _bfloat16_holds(vals) else "float32"
    best = None
    for axis, ids in ((1, cols), (0, rows)):
        deg = np.bincount(ids, minlength=shape[axis])
        lines, entries = _dense_groups(deg, shape[1 - axis], rows.size,
                                       shape, limit, dtype)
        if entries and (best is None or entries > best.entries):
            lines = np.sort(lines[deg[lines] > 0])
            column_of = np.full(shape[axis], -1, np.int32)
            column_of[lines] = np.arange(lines.size, dtype=np.int32)
            best = DenseLines(axis=axis ^ flipped, lines=lines,
                              column_of=column_of, entries=entries,
                              dtype=dtype)
    return best


@dataclasses.dataclass
class PanelledPlan:
    """An orientation's plan for the k-wide product where it is more
    than one compact plan: ``parts`` = ((col0, EdgeSpMVPlan), ...), a
    plan each over the sources ``col0 : col0 + plan.n_cols`` (the ranges
    of ``spmv.source_panels`` where the dense side has too many rows for
    one gather table), all over the same rows; and, where the matrix has
    one, its ``dense`` part, whose lines are this product's
    ``dense_role``, its "sources" or its "destinations", and the
    ``dense_axis`` ("rows" / "columns") of the matrix asked for the
    plan. The parts then hold the entries of every other line."""
    n_rows: int
    n_cols: int
    block: int
    parts: tuple
    dense: Optional[DenseLines] = None
    dense_role: str = ""
    dense_axis: str = ""


def plan_parts(plan) -> tuple:
    """((col0, EdgeSpMVPlan), ...) of either kind of plan."""
    return getattr(plan, "parts", None) or ((0, plan),)


def plan_facts(plan, entries: int) -> dict:
    """What plan.meta["spmm"] and a ``matrel.spmm.plan`` span say of a
    plan of either kind (``entries``: the matrix's nnz): ``layout``,
    ``entries`` (those LAID OUT IN SLOTS: all of them but the dense
    part's), ``slots``, ``chunks`` (table rows), ``source_panels`` and
    ``table`` (the k-wide gather table's form: ``hbm``, one table whole,
    or ``panelled``), ``overflow_edges``, and for the k-wide product on
    one device its ``panels`` (of table rows, over all parts),
    ``plan_bytes`` (the tables, the largest panel's temporaries,
    pallas_spmv.wide_plan_bytes, and the slab, once) and
    ``windowed_chunks``: of the chunks its scatter walks (``chunks`` in
    the chunks layout; a blocks-layout row is walked as several), those
    whose one-hot is a window shorter than the block
    (pallas_spmv.wide_windows; 0 where none is), and ``window_rows``,
    the same chunks by their window's height ({"128": n, "256": n}: the
    ladder ``spmv.WINDOWS``; the rest take the whole block). Where the
    matrix has a dense part (:class:`DenseLines`), also ``dense_lines``,
    ``dense_axis`` (``rows`` / ``columns`` of the matrix the product
    names), ``dense_entries`` (``entries + dense_entries`` is the nnz),
    ``dense_bytes`` and ``dense_dtype`` — the same for both
    orientations, which share the one slab; absent where no line paid."""
    from matrel_tpu.ops import pallas_spmv as pc
    parts = [p for _, p in plan_parts(plan)]
    shapes = [np.asarray(p.src8).shape for p in parts]
    # an orientation's own plan may have hub chunks (the matvec's; a
    # k-wide plan never): counted in ``slots``, not in ``chunks``
    hub_slots = sum(p.hubs.idx.size for p in parts if p.hubs is not None)
    slots = sum(r * c for r, c in shapes)
    per = [pc.wide_panel_rows(r, c) for r, c in shapes]
    dense = getattr(plan, "dense", None)
    tall = [pc.wide_windows(p)[1] for p in parts]
    window_rows = {str(h): sum(t[h] for t in tall)
                   for h in spmv_lib.WINDOWS}
    facts = {
        "layout": ("chunks" if parts[0].chunk_block is not None
                   else "blocks"),
        "entries": int(entries) - (0 if dense is None else dense.entries),
        "slots": int(slots + hub_slots),
        "chunks": int(sum(r for r, _ in shapes)),
        "windowed_chunks": sum(window_rows.values()),
        "window_rows": window_rows,
        "source_panels": len(parts),
        "table": "panelled" if len(parts) > 1 else "hbm",
        "overflow_edges": sum(0 if p.ov_rows is None
                              else int(p.ov_rows.shape[0]) for p in parts),
        "panels": int(sum(-(-r // n) for (r, _), n in zip(shapes, per))),
        "plan_bytes": int(pc.TABLE_BYTES_A_SLOT * slots
                          + pc.HUB_BYTES_A_SLOT * hub_slots + max(
            pc.wide_panel_bytes(r, c) for r, c in shapes)
            + (0 if dense is None else dense.slab.nbytes)),
    }
    if dense is not None:
        facts.update(dense_lines=int(dense.lines.size),
                     dense_axis=plan.dense_axis,
                     dense_entries=int(dense.entries),
                     dense_bytes=int(dense.slab.nbytes),
                     dense_dtype=dense.dtype)
    return facts


def sampled_facts(plan, entries: int, shared: bool) -> dict:
    """What plan.meta["sampled"] and a ``matrel.sampled.plan`` span say
    of the plan a sampled product runs on (executor._sampled_product;
    ``entries``: the matrix's nnz; ``shared``: the product's dense side
    is the factor whose rows its sources name, so one gather serves the
    entry's dot and the scatter): ``entries`` (ALL of them: every one is
    sampled), ``dense_entries`` (those on the slab, whose quotient the
    MXU makes; 0 where the matrix has no dense part),
    ``lines``, ``slab_dtype``, and who multiplies the dense lines
    (ops/sampled_lines.plan, which the lowering asks too): ``lines_by``
    ("kernel": ``matrel_sampled_lines``, the quotient in VMEM alone;
    "xla": the loop of panels, with ``lines_why_not``; "" without a
    slab) and ``panel_rows`` (the slab rows a step of it takes: the
    kernel's row tile, the loop's panel); ``dot`` ("kernel": the scatter
    kernel takes the destination's rows off its block tile and makes the
    entry's dot; no path of this tree gathers them) and of the compact
    parts, which hold the rest, :func:`plan_facts`' ``layout``,
    ``slots``, ``chunks``, ``windowed_chunks``, ``window_rows``,
    ``source_panels``, ``overflow_edges`` and, at this product's own
    panel size (a slot holds one gathered row more where the gather is
    not ``shared``), ``panels``; ``hbm_plan_bytes``: the tables, the
    largest panel's temporaries, the slab once and what its product
    writes beside it — the loop a panel's float32 cells, dot and
    quotient, the kernel nothing."""
    from matrel_tpu.ops import pallas_spmv as pc, sampled_lines
    own = plan_facts(plan, entries)
    more = 0 if shared else 1
    shapes = [np.asarray(p.src8).shape for _, p in plan_parts(plan)]
    dense = getattr(plan, "dense", None)
    facts = {k: own[k] for k in ("layout", "slots", "chunks",
                                 "windowed_chunks", "window_rows",
                                 "source_panels", "overflow_edges")}
    lines = {"lines_by": "", "panel_rows": 0}
    beside = 0
    if dense is not None:
        lines = sampled_lines.plan(dense.width, dense.slab.dtype.itemsize)
        if lines["lines_by"] != "kernel":
            beside = 3 * 4 * lines["panel_rows"] * dense.width
    facts.update(
        entries=int(entries), dense_entries=own.get("dense_entries", 0),
        lines=own.get("dense_lines", 0),
        slab_dtype=own.get("dense_dtype", ""), **lines,
        shared_gather=bool(shared), dot="kernel",
        panels=int(sum(-(-r // pc.wide_panel_rows(r, c, more))
                       for r, c in shapes)),
        hbm_plan_bytes=int(
            pc.TABLE_BYTES_A_SLOT * own["slots"]
            + max(pc.wide_panel_bytes(r, c, more) for r, c in shapes)
            + (0 if dense is None else dense.slab.nbytes + beside)))
    return facts


def semiring_facts(m: "COOMatrix", plan, reduce: str) -> dict:
    """What plan.meta["semiring"] and a ``matrel.semiring.plan`` span
    say of how a (max | min, ×) product of ``m`` (one entry a cell:
    :meth:`COOMatrix.entry_view`) and a column is answered: ``reduce``
    and ``merge``; ``how`` — ``kernel`` (``plan``: its forward plan, in
    chunks whose slots lie by row where the scan reads them, through
    ``matrel_spmv_reduce_chunks``: the kernel's engagement counter) or
    ``xla`` (``plan`` None: ``segment_max`` / ``segment_min`` over the
    entries sorted by row — the CPU, a mesh, a refused plan, the blocks
    layout of a small one); ``entries``; of a plan its ``layout``,
    ``slots`` (the hub chunks' among them), ``chunks`` (those that go
    through the row gather), ``panels`` (of table rows, the matvec's
    own) and ``overflow_edges``, and of its hub chunks, whose slots take
    their value from the hub table in VMEM through
    ``matrel_spmv_reduce_hubs`` (PR 51; all 0 where the build's rule
    took no hub: the hub chunks' engagement counters) ``hub_chunks``,
    ``hub_slots``, ``hub_entry_share`` (the entries in them over
    ``entries``) and ``hub_walk_rows`` (the table rows the kernel walks
    a product); ``full_rows`` (rows that hold every column,
    answered on their own off a dense copy of theirs: the only rows
    whose extremum the 0 of a missing cell has no part in); and
    ``hbm_plan_bytes``: the tables, the slots' weights and the largest
    panel's temporaries (pallas_spmv.plan_bytes, a hub slot at its 12
    B), or the sorted entries and one gather over them, and the full
    rows' copy."""
    from matrel_tpu.ops import pallas_spmv as pc
    full = m.full_rows()
    facts = {"reduce": reduce, "merge": "mul",
             "how": "xla" if plan is None else "kernel",
             "entries": m.nnz,
             "full_rows": 0 if full is None else int(full[0].shape[0])}
    own = 0 if full is None else int(full[1].nbytes)
    if plan is None:
        # out ids, in ids, values, the products; a gathered byte row
        facts["hbm_plan_bytes"] = own + (16 + pc._TEMP_BYTES_A_SLOT) * m.nnz
        return facts
    rows, cap = np.asarray(plan.src8).shape
    hub = plan.hubs
    hub_slots = 0 if hub is None else int(hub.idx.size)
    facts.update(
        layout="chunks", slots=int(rows * cap) + hub_slots, chunks=int(rows),
        panels=int(-(-rows // pc.panel_rows(rows, cap))),
        overflow_edges=0,
        hub_chunks=hub_slots // cap, hub_slots=hub_slots,
        hub_entry_share=(0.0 if hub is None
                         else round(hub.entries / max(m.nnz, 1), 4)),
        hub_walk_rows=0 if hub is None else int(hub.rows.sum()),
        hbm_plan_bytes=own + int(pc.plan_bytes(rows, cap, hub_slots)))
    return facts


def semiring_apply(m: "COOMatrix", plan, x, reduce: str,
                   interpret: bool = False):
    """Traceable: ``y[i] = (max | min)_j m[i, j] · x[j]`` over ALL of
    ``m``'s columns (a missing cell is the 0 it is in the dense matrix),
    (n_rows,) float32, for ``m`` with one entry a cell and ``x``
    (n_cols,). The entries' extrema with 0 in the running of every row
    — from the compact tables (pallas_spmv.reduce_apply) where ``plan``
    is one that kernel reads, else XLA's segment reduction over the
    entries sorted by row (an empty segment's ∓inf meets the 0 there) —
    and then the rows that hold every column, whose extremum has no 0
    in it, from their own dense copy. Products are single float32
    multiplies and nothing is added, so this is what the dense lowering
    gives, bit for bit, for finite values."""
    from matrel_tpu.ops import pallas_spmv as pc
    x = x.astype(jnp.float32)
    of_two, of_segments, of_axis = {
        "max": (jnp.maximum, jax.ops.segment_max, jnp.max),
        "min": (jnp.minimum, jax.ops.segment_min, jnp.min)}[reduce]
    if plan is not None:
        static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
        y = pc.reduce_apply(static, pc.compact_tables(plan), x, reduce,
                            interpret=interpret)
    else:
        out_s, in_s, val_s = m.sorted_entries()
        y = of_two(of_segments(
            val_s * spmv_lib.gather_1d(x, in_s), out_s,
            num_segments=m.shape[0], indices_are_sorted=True), 0.0)
    full = m.full_rows()
    if full is not None:
        ids, rows = full
        y = y.at[ids].set(of_axis(rows * x[None, :], axis=1))
    return y


@dataclasses.dataclass
class COOMatrix:
    """Immutable element-sparse matrix over a fixed coordinate list."""

    rows: np.ndarray          # host int64, unsorted as given
    cols: np.ndarray
    vals: np.ndarray          # float32
    shape: Tuple[int, int]
    _plan: Optional[spmv_lib.EdgeSpMVPlan] = dataclasses.field(
        default=None, repr=False)
    _plan_t: Optional[spmv_lib.EdgeSpMVPlan] = dataclasses.field(
        default=None, repr=False)
    _plan_tried: bool = dataclasses.field(default=False, repr=False)
    _plan_t_tried: bool = dataclasses.field(default=False, repr=False)
    # the k-wide product's plans where they are not the two above: a
    # one-slot list each, shared with the transpose view
    _wide: list = dataclasses.field(default_factory=list, repr=False)
    _wide_t: list = dataclasses.field(default_factory=list, repr=False)
    # the k-wide plans' dense part, or None, once it was asked for: a
    # one-slot list shared with the transpose view, which reads its axis
    # flipped
    _dense: list = dataclasses.field(default_factory=list, repr=False)
    _flipped: bool = dataclasses.field(default=False, repr=False)
    # fallback-path caches: (device out_ids, device in_ids, device vals),
    # sorted by out_ids — fixed per matrix, built once per direction
    _seg_fwd: Optional[tuple] = dataclasses.field(default=None, repr=False)
    _seg_bwd: Optional[tuple] = dataclasses.field(default=None, repr=False)
    # set by .shard(): forward matvec runs this mesh-sharded plan; kept
    # separate from _plan so the DSL/transpose paths (which expect
    # default-placement plans) never see sharded tables
    _mesh: Optional[object] = dataclasses.field(default=None, repr=False)
    _plan_sharded: Optional[spmv_lib.EdgeSpMVPlan] = dataclasses.field(
        default=None, repr=False)
    # True when coordinates are known-unique (outputs of coalesce/
    # select_value/join): lets chained relational ops skip the re-sort
    _coalesced: bool = dataclasses.field(default=False, repr=False)
    # what a (max | min, ×) product reads, each found once: this matrix
    # with one entry a cell (entry_view) and its full rows (full_rows)
    _entry_memo: list = dataclasses.field(default_factory=list, repr=False)
    _full: list = dataclasses.field(default_factory=list, repr=False)

    # ---------------------------------------------------------- build
    @classmethod
    def from_edges(cls, rows, cols, vals=None,
                   shape: Optional[Tuple[int, int]] = None) -> "COOMatrix":
        # cold: the copies to int64 / float32 and the bounds' passes
        # are seconds at 100M entries, once a matrix
        with trace_lib.phase("coo.from_edges") as sp:
            rows = np.asarray(rows, dtype=np.int64).ravel()
            cols = np.asarray(cols, dtype=np.int64).ravel()
            if rows.shape != cols.shape:
                raise ValueError(f"rows/cols length mismatch: "
                                 f"{rows.shape} vs {cols.shape}")
            if vals is None:
                vals = np.ones(rows.shape, np.float32)
            else:
                vals = np.asarray(vals, dtype=np.float32).ravel()
                if vals.shape != rows.shape:
                    raise ValueError("vals length must match rows/cols")
            if shape is None:
                shape = (int(rows.max()) + 1 if rows.size else 1,
                         int(cols.max()) + 1 if cols.size else 1)
            if rows.size and (rows.min() < 0 or rows.max() >= shape[0]
                              or cols.min() < 0 or cols.max() >= shape[1]):
                raise ValueError("edge indices out of bounds for shape")
            sp.set(entries=int(rows.size),
                   bytes=int(rows.nbytes + cols.nbytes + vals.nbytes))
            return cls(rows=rows, cols=cols, vals=vals, shape=tuple(shape))

    @classmethod
    def from_scipy(cls, mat) -> "COOMatrix":
        """From any scipy.sparse matrix (converted to COO)."""
        coo = mat.tocoo()
        return cls.from_edges(coo.row, coo.col, coo.data, shape=coo.shape)

    # ------------------------------------------------------ properties
    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def T(self) -> "COOMatrix":
        """Transpose view — shares this matrix's plan caches swapped, so
        ``A.T.matvec`` costs no rebuild once ``A.rmatvec`` (or a prior
        ``A.T``) compiled a plan."""
        return COOMatrix(rows=self.cols, cols=self.rows, vals=self.vals,
                         shape=(self.shape[1], self.shape[0]),
                         _plan=self._plan_t, _plan_t=self._plan,
                         _plan_tried=self._plan_t_tried,
                         _plan_t_tried=self._plan_tried,
                         _wide=self._wide_t, _wide_t=self._wide,
                         _dense=self._dense, _flipped=not self._flipped,
                         _seg_fwd=self._seg_bwd, _seg_bwd=self._seg_fwd,
                         _coalesced=self._coalesced)

    # ----------------------------------------------------------- plans
    def _build(self, out_ids, in_ids, n_out: int, n_in: int, sel=None,
               col0: int = 0, hubs: bool = False):
        """One compact plan over this matrix's entries (``sel``: a subset
        of them, sources renumbered from ``col0``). ``hubs``: with hub
        chunks where the build's own rule finds the sources skewed
        enough (spmv._hub_rows) — an orientation's own plan, which the
        matvec and the (max | min) reduction read; never a k-wide
        plan's: that product fetches a hub's whole row like any
        other."""
        if sel is not None:
            out_ids, in_ids = out_ids[sel], in_ids[sel] - col0
        return spmv_lib.build_spmv_plan(
            out_ids, in_ids, self.vals if sel is None else self.vals[sel],
            n_rows=n_out, n_cols=n_in, layout=_plan_layout(), hubs=hubs)

    def _get_plan(self) -> Optional[spmv_lib.EdgeSpMVPlan]:
        if not self._plan_tried:
            self._plan = _counted_build(
                lambda: self._build(self.rows, self.cols, *self.shape,
                                    hubs=True),
                orientation="forward")
            self._plan_tried = True
        return self._plan

    def _get_plan_t(self) -> Optional[spmv_lib.EdgeSpMVPlan]:
        if not self._plan_t_tried:
            self._plan_t = _counted_build(
                lambda: self._build(self.cols, self.rows, *self.shape[::-1],
                                    hubs=True),
                orientation="transposed")
            self._plan_t_tried = True
        return self._plan_t

    def _dense_lines(self, exact: bool = True) -> Optional[DenseLines]:
        """The k-wide plans' dense part, chosen once from this matrix's
        own degrees and values (its slab is the first wide plan's build
        to fill; ``exact`` false: chosen again, in float32, by a build
        whose bfloat16 slab met a repeated cell); None where no line
        pays."""
        if not self._dense or not exact:
            self._dense[:] = [_choose_dense_lines(
                self.rows, self.cols, self.vals, self.shape, self._flipped,
                exact)]
        return self._dense[0]

    def _get_wide_plan(self, transposed: bool = False):
        """The plan the k-wide product A·X (``transposed``: Aᵀ·X) runs:
        the orientation's own where X's rows make one gather table, no
        line of the matrix is dense and that plan has no hub chunks
        (then one of its own without them), else a
        :class:`PanelledPlan` (over ranges of X's rows, beside the dense
        part), built once; None where the planner refused the matrix."""
        n_out, n_in = self.shape[::-1] if transposed else self.shape
        own = self._get_plan_t if transposed else self._get_plan
        if _plan_layout() != "auto":
            return own()
        memo = self._wide_t if transposed else self._wide
        if memo:
            return memo[0]
        out_ids, in_ids = ((self.cols, self.rows) if transposed
                           else (self.rows, self.cols))
        panels = spmv_lib.source_panels(n_in)
        if panels == 1 and self._dense_lines() is None:
            # the orientation's own where it was built already and has
            # no hub chunks; else one without them, which IS the own
            # plan where none was built yet and the rule would take no
            # hub: one build serves both, whichever asks first
            tried = self._plan_t_tried if transposed else self._plan_tried
            mine = own() if tried else None
            if tried and (mine is None or mine.hubs is None):
                return mine
            plan = _counted_build(
                lambda: self._build(out_ids, in_ids, n_out, n_in),
                orientation="transposed" if transposed else "forward")
            if tried or (plan is not None and plan.chunk_block is not None
                         and spmv_lib.takes_hubs(in_ids, n_in, n_out)):
                memo.append(plan)
            elif transposed:
                self._plan_t, self._plan_t_tried = plan, True
            else:
                self._plan, self._plan_tried = plan, True
            return plan
        # ranges of whole table rows, as even as they come
        width = -(-n_in // (panels * spmv_lib.WIDTH)) * spmv_lib.WIDTH

        def entries_left():
            """Which entries stay in the tables: all but the dense
            lines', whose slab this build fills where none has."""
            dense = self._dense_lines()
            if dense is None:
                return None
            # the lines' axis as this view has it (1: its columns)
            axis = dense.axis ^ self._flipped
            ids, others = ((self.cols, self.rows) if axis
                           else (self.rows, self.cols))
            mine = dense.holds(ids)
            if dense.slab is None:
                at = np.flatnonzero(mine)
                with jax.ensure_compile_time_eval():
                    if not dense.fill(ids[at], others[at], self.vals[at],
                                      self.shape[1 - axis]):
                        self._dense_lines(exact=False)
                        return entries_left()
            return ~mine

        def build():
            keep = entries_left()
            parts = []
            for col0 in range(0, n_in, width):
                n_part = min(width, n_in - col0)
                here = (in_ids >= col0) & (in_ids < col0 + n_part)
                sel = np.flatnonzero(here if keep is None else here & keep)
                parts.append((col0, self._build(
                    out_ids, in_ids, n_out, n_part, sel, col0)))
            return parts

        said = {"source_panels": panels} if panels > 1 else {}
        parts = _counted_build(
            build, orientation="transposed" if transposed else "forward",
            **said)
        dense, about = self._dense_lines(), {}
        if dense is not None:
            axis = dense.axis ^ self._flipped
            # A·X reads its sources off A's columns, Aᵀ·X off its rows
            about = {"dense": dense,
                     "dense_role": ("sources" if bool(axis) != transposed
                                    else "destinations"),
                     "dense_axis": "columns" if axis else "rows"}
        memo.append(None if any(p is None for _, p in parts)
                    else PanelledPlan(
                        n_rows=n_out, n_cols=n_in, block=parts[0][1].block,
                        parts=tuple(parts), **about))
        return memo[0]

    def shard(self, mesh) -> "COOMatrix":
        """Return a copy whose forward ``matvec`` runs a plan
        row-decomposed over every device of ``mesh``
        (ops/spmv.py::shard_plan): each device contracts its slice of
        output blocks against the replicated x and one tiled all_gather
        assembles the result. DSL/transpose/rmatvec paths keep their own
        default-placement plans.

        Raises when the planner refuses this graph — distribution was
        requested explicitly, and silently degrading to a single-device
        segment-sum would mask the perf cliff; catch and use the
        unsharded matrix if that degradation is acceptable."""
        if self._plan_tried and self._plan is None:
            plan = None                      # known-refused: don't rebuild
        elif (self._plan_tried and self._plan is not None
              and self._plan._tables is None
              and self._plan.chunk_block is None):
            plan = self._plan                # fresh unexpanded plan: reuse
        else:
            plan = spmv_lib.build_spmv_plan(self.rows, self.cols,
                                            self.vals,
                                            n_rows=self.shape[0],
                                            n_cols=self.shape[1])
        if plan is None:
            raise ValueError(
                "degree distribution too heavy-tailed for the one-hot "
                "plan; sharded matvec unavailable for this graph")
        return COOMatrix(rows=self.rows, cols=self.cols, vals=self.vals,
                         shape=self.shape, _mesh=mesh,
                         _plan_sharded=spmv_lib.shard_plan(plan, mesh),
                         _coalesced=self._coalesced)

    # ------------------------------------------------------------ ops
    @staticmethod
    def _compact_mode() -> bool:
        """On real TPU the compact-table Pallas executor wins on both
        time and (17×) memory — the expanded one-hot tables are never
        built (config.pallas_enabled is the single shared gate)."""
        from matrel_tpu.config import pallas_enabled
        return pallas_enabled()

    def matvec(self, x) -> jax.Array:
        """y = A·x, shape (n_rows,)."""
        x = jnp.asarray(x, jnp.float32).ravel()
        if x.shape[0] != self.shape[1]:
            raise ValueError(f"x has {x.shape[0]} entries, A has "
                             f"{self.shape[1]} columns")
        if self._plan_sharded is not None:
            return spmv_lib.spmv_sharded(self._plan_sharded, x,
                                         self._mesh)
        plan = self._get_plan()
        if plan is not None:
            if self._compact_mode():
                from matrel_tpu.ops import pallas_spmv as pc
                return pc.spmv_compact(plan, x)
            return spmv_lib.spmv(plan, x)
        return self._segment_matvec(self.sorted_entries(), x,
                                    self.shape[0])

    def rmatvec(self, y) -> jax.Array:
        """x = Aᵀ·y, shape (n_cols,) — uses the lazily-built transpose
        plan (no re-sort of the forward plan)."""
        y = jnp.asarray(y, jnp.float32).ravel()
        if y.shape[0] != self.shape[0]:
            raise ValueError(f"y has {y.shape[0]} entries, A has "
                             f"{self.shape[0]} rows")
        plan = self._get_plan_t()
        if plan is not None:
            if self._compact_mode():
                from matrel_tpu.ops import pallas_spmv as pc
                return pc.spmv_compact(plan, y)
            return spmv_lib.spmv(plan, y)
        if self._seg_bwd is None:
            self._seg_bwd = self._seg_arrays(self.cols, self.rows)
        return self._segment_matvec(self._seg_bwd, y, self.shape[1])

    def matmat(self, X) -> jax.Array:
        """Y = A·X for dense X (n_cols, k): the k-wide SpMM gathers a
        slot's row of X once for all its columns (ops/pallas_spmv.py on
        a TPU, 128 columns a pass; ops/spmv.py::spmm elsewhere). Falls
        back to a per-column matvec loop only when the planner refused
        the graph."""
        X = jnp.asarray(X, jnp.float32)
        if X.ndim != 2 or X.shape[0] != self.shape[1]:
            raise ValueError(f"X must be ({self.shape[1]}, k), "
                             f"got {X.shape}")
        if X.shape[1] == 0:
            return jnp.zeros((self.shape[0], 0), jnp.float32)
        if self._plan_sharded is not None:
            return spmv_lib.spmm_sharded(self._plan_sharded, X,
                                         self._mesh)
        plan = self._get_wide_plan() if X.shape[1] > 1 else self._get_plan()
        if plan is not None:
            if self._compact_mode():
                from matrel_tpu.ops import pallas_spmv as pc
                return pc.spmm_compact(plan, X)
            return spmv_lib.spmm(plan, X)
        cols = [self.matvec(X[:, j]) for j in range(X.shape[1])]
        return jnp.stack(cols, axis=1)

    def _seg_arrays(self, out_ids, in_ids) -> tuple:
        order = np.argsort(out_ids, kind="stable")
        return (jnp.asarray(out_ids[order], jnp.int32),
                jnp.asarray(in_ids[order], jnp.int32),
                jnp.asarray(self.vals[order]))

    def _segment_matvec(self, seg, x, n_out) -> jax.Array:
        out_s, in_s, val_s = seg
        w = val_s * spmv_lib.gather_1d(x, in_s)
        return jax.ops.segment_sum(w, out_s, num_segments=n_out,
                                   indices_are_sorted=True)

    def entry_view(self) -> "COOMatrix":
        """This matrix with ONE entry a cell: itself where no coordinate
        repeats (found by one sort of the keys, once; from then on it
        knows, ``_coalesced``), else :meth:`coalesce`'s sum of the
        repeats, kept. Sums never ask (a repeated cell adds up in the
        plan as in the dense matrix); an extremum over a row's products
        has to (executor._semiring_product)."""
        if not self._coalesced and not self._entry_memo:
            with trace_lib.phase("coo.entry_view", entries=self.nnz,
                                 bytes=8 * self.nnz) as sp:
                keys = self.rows * self.shape[1] + self.cols
                keys.sort()
                repeats = bool((keys[1:] == keys[:-1]).any())
                if repeats:
                    self._entry_memo.append(self.coalesce())
                else:
                    self._coalesced = True
                sp.set(repeats=repeats)
        return self if self._coalesced else self._entry_memo[0]

    def full_rows(self) -> Optional[tuple]:
        """(ids (f,) int32, rows (f, n_cols) float32) on the device: the
        rows of a matrix with one entry a cell (:meth:`entry_view`) that
        hold EVERY column, as dense rows — f · n_cols of the matrix's
        own entries — or None where there is none (a graph's adjacency
        matrix, any matrix with fewer entries than columns)."""
        if not self._full:
            n, m = self.shape
            found = None
            if m and self.nnz >= m:
                ids = np.flatnonzero(np.bincount(self.rows, minlength=n)
                                     == m)
                if ids.size:
                    at = np.flatnonzero(np.isin(self.rows, ids))
                    dense = np.zeros((ids.size, m), np.float32)
                    dense[np.searchsorted(ids, self.rows[at]),
                          self.cols[at]] = self.vals[at]
                    with jax.ensure_compile_time_eval():
                        found = (jnp.asarray(ids, jnp.int32),
                                 jnp.asarray(dense))
            self._full.append(found)
        return self._full[0]

    def sorted_entries(self) -> tuple:
        """(row ids, column ids, values) on the device, sorted by row:
        the segment paths' tables, made once (committed arrays even when
        first asked for inside an executor trace)."""
        if self._seg_fwd is None:
            with jax.ensure_compile_time_eval():
                self._seg_fwd = self._seg_arrays(self.rows, self.cols)
        return self._seg_fwd

    def to_dense(self) -> np.ndarray:
        """Host densification (small matrices / tests)."""
        out = np.zeros(self.shape, np.float32)
        np.add.at(out, (self.rows, self.cols), self.vals)
        return out

    def to_block(self, mesh=None, config=None):
        """Densify into a mesh-sharded BlockMatrix — the fallback when a
        COO matrix is used where no SpMV lowering applies. O(n·m) memory:
        meant for modest shapes; keep giant graphs on matvec/matmat."""
        from matrel_tpu.core.blockmatrix import BlockMatrix
        return BlockMatrix.from_numpy(self.to_dense(), mesh=mesh,
                                      config=config, nnz=self.nnz)

    # ------------------------------------------------- relational (σ/γ/⋈)
    # Eager, edge-list-native forms of the relational operators — the
    # scale path: a 1M×1M graph cannot take the executor's densifying
    # lowering, but filtering/aggregating its edge list is O(nnz) host
    # work. Semantics match the dense masked model exactly (0 = missing;
    # SURVEY.md §7.6), so results agree with the IR lowerings wherever
    # both are feasible.

    def coalesce(self) -> "COOMatrix":
        """Collapse duplicate coordinates additively (entry-level view).
        Relational σ/γ operate on ENTRIES, not raw edges, so they
        coalesce first; matvec/plans are additive and never need to.
        No-op (returns self) when coordinates are known-unique."""
        if self._coalesced:
            return self
        m = self.shape[1]
        keys, vals = _sum_dups(self.rows * m + self.cols, self.vals)
        out = COOMatrix.from_edges(keys // m, keys % m, vals,
                                   shape=self.shape)
        out._coalesced = True
        return out

    def select_value(self, predicate, fill: float = 0.0) -> "COOMatrix":
        """σ on ENTRY values (duplicates coalesced first — an entry's
        value is the sum of its edges, exactly the dense semantics).
        Only fill=0 keeps the result sparse; other fills would densify —
        use the dense IR path for those."""
        if fill != 0.0:
            raise ValueError("COOMatrix.select_value supports fill=0 "
                             "only (a nonzero fill densifies; use "
                             "to_block(...).select_value)")
        A = self.coalesce()
        keep = np.asarray(predicate(A.vals), bool)
        out = COOMatrix.from_edges(A.rows[keep], A.cols[keep],
                                   A.vals[keep], shape=self.shape)
        out._coalesced = True
        return out

    def select_index(self, *, rows=None, cols=None) -> "COOMatrix":
        """σ on indices: keep edges whose row/col satisfy the
        predicates (vectorised callables over index arrays)."""
        keep = np.ones(self.rows.shape, bool)
        if rows is not None:
            keep &= np.asarray(rows(self.rows), bool)
        if cols is not None:
            keep &= np.asarray(cols(self.cols), bool)
        out = COOMatrix.from_edges(self.rows[keep], self.cols[keep],
                                   self.vals[keep], shape=self.shape)
        out._coalesced = self._coalesced   # subsets stay unique
        return out

    def _axis_agg(self, axis: str, kind: str) -> np.ndarray:
        # count/avg/max/min are entry-level (γ over nonzero TUPLES):
        # duplicates must coalesce first; plain sums are additive anyway
        A = self if kind == "sum" else self.coalesce()
        ids = A.rows if axis == "row" else A.cols
        n = self.shape[0] if axis == "row" else self.shape[1]
        vals = A.vals
        nz = vals != 0
        if kind == "sum":
            out = np.bincount(ids, weights=vals,
                              minlength=n).astype(np.float32)
        elif kind == "count":
            out = np.bincount(ids[nz], minlength=n).astype(np.float32)
        elif kind == "avg":
            sv = np.bincount(ids, weights=vals, minlength=n)
            c = np.bincount(ids[nz], minlength=n)
            out = np.where(c > 0, sv / np.maximum(c, 1), 0.0)
        elif kind in ("max", "min"):
            fill = -np.inf if kind == "max" else np.inf
            out = np.full(n, fill, np.float64)
            op = np.maximum if kind == "max" else np.minimum
            op.at(out, ids[nz], vals[nz].astype(np.float64))
            out = np.where(np.isfinite(out), out, 0.0)
            # dense-lowering parity: a row/col with any MISSING entry
            # includes implicit zeros in its max/min (executor._agg runs
            # over the full logical region), so clamp toward 0 wherever
            # the axis isn't fully populated by nonzeros
            width = self.shape[1] if axis == "row" else self.shape[0]
            cnt = np.bincount(ids[nz], minlength=n)
            partial = cnt < width
            out = np.where(partial, op(out, 0.0), out)
        else:
            raise ValueError(f"unknown aggregate {kind!r}")
        return out.astype(np.float32)

    def row_sum(self) -> np.ndarray:
        """γ: per-row sums as (n, 1) — O(nnz), never densifies."""
        return self._axis_agg("row", "sum")[:, None]

    def col_sum(self) -> np.ndarray:
        return self._axis_agg("col", "sum")[None, :]

    def row_count(self) -> np.ndarray:
        return self._axis_agg("row", "count")[:, None]

    def col_count(self) -> np.ndarray:
        return self._axis_agg("col", "count")[None, :]

    def row_avg(self) -> np.ndarray:
        return self._axis_agg("row", "avg")[:, None]

    def col_avg(self) -> np.ndarray:
        return self._axis_agg("col", "avg")[None, :]

    def row_max(self) -> np.ndarray:
        return self._axis_agg("row", "max")[:, None]

    def row_min(self) -> np.ndarray:
        return self._axis_agg("row", "min")[:, None]

    def col_max(self) -> np.ndarray:
        return self._axis_agg("col", "max")[None, :]

    def col_min(self) -> np.ndarray:
        return self._axis_agg("col", "min")[None, :]

    def sum(self) -> float:
        return float(self.vals.sum())

    def norm(self, kind: str = "fro") -> float:
        """Matrix norm over ENTRIES (duplicates coalesced first —
        absent entries are 0 and contribute nothing to any of these)."""
        v = self.coalesce().vals.astype(np.float64)
        if kind == "fro":
            return float(np.sqrt((v * v).sum()))
        if kind == "l1":
            return float(np.abs(v).sum())
        if kind == "max":
            return float(np.abs(v).max()) if v.size else 0.0
        raise ValueError(f"unknown norm kind {kind!r} "
                         "(expected 'fro', 'l1', or 'max')")

    def trace(self) -> float:
        d = self.rows == self.cols
        return float(self.vals[d].sum())

    def join_on_index(self, other: "COOMatrix", merge) -> "COOMatrix":
        """⋈ on index equality: C[i,j] = merge(A[i,j], B[i,j]) over the
        UNION of both coordinate sets (absent entries read 0, the masked
        semantics). merge must be a vectorised callable; exact zeros in
        the merged result are dropped from the edge list."""
        if tuple(self.shape) != tuple(other.shape):
            raise ValueError(f"join_on_index shape mismatch: "
                             f"{self.shape} vs {other.shape}")
        if float(merge(np.float32(0.0), np.float32(0.0))) != 0.0:
            raise ValueError(
                "merge(0, 0) != 0: the result is dense (every absent "
                "coordinate becomes nonzero) — use the dense IR "
                "join_on_index for such merges")
        m = self.shape[1]
        ka = self.rows * m + self.cols
        kb = other.rows * m + other.cols
        # duplicate coordinates are additive (from_edges semantics)
        ka_u, va = _sum_dups(ka, self.vals)
        kb_u, vb = _sum_dups(kb, other.vals)
        union = np.union1d(ka_u, kb_u)
        a_full = np.zeros(union.shape, np.float32)
        b_full = np.zeros(union.shape, np.float32)
        a_full[np.searchsorted(union, ka_u)] = va
        b_full[np.searchsorted(union, kb_u)] = vb
        merged = np.asarray(merge(a_full, b_full), np.float32)
        nz = merged != 0
        out = COOMatrix.from_edges(union[nz] // m, union[nz] % m,
                                   merged[nz], shape=self.shape)
        out._coalesced = True
        return out

    def join_on_value(self, other: "COOMatrix", merge="mul",
                      predicate="eq", max_pairs: int = 1 << 22):
        """⋈ on values over NONZERO entry tuples — the edge-list-native
        value join (the dense IR's pair matrix ranges over ALL logical
        entries; here only stored nonzeros join, the relational
        entry-tuple semantics of the reference's sparse value joins).

        predicate: "eq"/"lt"/"le"/"gt"/"ge" (sort-based matching,
        O((na+nb)·log nb) before materialising pairs) or a vectorised
        callable over (va, vb) (brute-force, capped). merge: one of
        "left"/"right"/"add"/"mul" or a vectorised callable.

        Returns matched pairs as a tuple of numpy arrays
        ``(ia, ja, ib, jb, value)`` — A-coordinates, B-coordinates,
        merged value per pair. Refuses to materialise more than
        ``max_pairs`` pairs with a clear error.
        """
        A = self.coalesce()
        B = other.coalesce()
        # zero-valued entries (duplicate cancellation) are ABSENT under
        # the masked entry semantics — they never join
        nza = A.vals != 0
        nzb = B.vals != 0
        a_rows, a_cols = A.rows[nza], A.cols[nza]
        b_rows, b_cols = B.rows[nzb], B.cols[nzb]
        va = A.vals[nza].astype(np.float32)
        vb = B.vals[nzb].astype(np.float32)
        merge_np = {"left": lambda x, y: x, "right": lambda x, y: y,
                    "add": np.add, "mul": np.multiply}.get(merge, merge)
        if not callable(merge_np):
            raise ValueError(f"unknown merge {merge!r}")
        if callable(predicate):
            if va.size * vb.size > max_pairs:
                raise ValueError(
                    f"callable-predicate value join must enumerate "
                    f"{va.size}x{vb.size} pairs (> max_pairs = "
                    f"{max_pairs}); use a structured predicate "
                    f"('eq'/'lt'/'le'/'gt'/'ge') or raise max_pairs")
            mask = np.asarray(predicate(va[:, None], vb[None, :]), bool)
            pa, pb = np.nonzero(mask)
        else:
            # shared predicate→range semantics (incl. IEEE NaN
            # handling) with the streaming executor path
            from matrel_tpu.relational.value_join import match_range
            order = np.argsort(vb, kind="stable")   # NaNs sort last
            sv = vb[order]
            lo, hi = match_range(sv, va, predicate, xp=np)
            cnt = hi - lo
            total = int(cnt.sum())
            if total > max_pairs:
                raise ValueError(
                    f"value join matches {total} pairs (> max_pairs = "
                    f"{max_pairs}); tighten the predicate or raise "
                    f"max_pairs")
            pa = np.repeat(np.arange(va.size), cnt)
            # pair k of entry i maps to sorted-B slot lo[i] + offset
            offs = np.arange(total) - np.repeat(
                np.cumsum(cnt) - cnt, cnt)
            pb = order[np.repeat(lo, cnt) + offs]
        vals = np.asarray(merge_np(va[pa], vb[pb]), np.float32)
        return (a_rows[pa], a_cols[pa], b_rows[pb], b_cols[pb], vals)

    # ------------------------------------------------------------ DSL
    def expr(self):
        """Enter the lazy IR as an element-sparse leaf: matmuls against
        narrow dense operands lower to the one-hot SpMV plan; other uses
        densify (see executor)."""
        from matrel_tpu.ir import expr as E
        return E.MatExpr("coo_leaf", (), tuple(self.shape),
                         min(self.nnz, self.shape[0] * self.shape[1]),
                         {"matrix": self})

    def multiply(self, other):
        from matrel_tpu.ir import expr as E
        return E.matmul(self.expr(), E.as_expr(other))


def _sum_dups(keys: np.ndarray, vals: np.ndarray):
    """Collapse duplicate coordinates additively: unique keys + summed
    values (host, O(nnz log nnz))."""
    if keys.size == 0:
        return keys, vals.astype(np.float32)
    uniq, inv = np.unique(keys, return_inverse=True)
    return uniq, np.bincount(inv, weights=vals,
                             minlength=uniq.size).astype(np.float32)
