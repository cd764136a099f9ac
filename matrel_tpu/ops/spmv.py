"""TPU-idiomatic sparse matrix-vector product over edge lists (SpMV).

The reference's graph workloads run matvecs through Spark's shuffle
(SURVEY.md §3.5: the per-round shuffle dominates PageRank). The naive TPU
translation — ``r[src]`` gather + ``segment_sum`` scatter-add — hits XLA's
serialized scalar gather/scatter path (~150M rows/s measured on v5e: 67 ms
for a 10M gather, 88 ms for the matching scatter). Neither the MXU nor the
VPU has per-lane random access, so this module reshapes the irregular ops
into the two forms the hardware executes well:

* **Row gather of bytes** (``gather_1d``): XLA's TPU gather runs ~4× faster
  a row than the scalar path (measured: 10.5M rows in 13.9-16.6 ms against
  69.6 ms) — but only while the padded table sits in fast memory, and its
  cost is flat in the row's width because every row of up to 128 elements
  occupies 128 lanes. That padding is what the step after the gather pays
  for: 8 float32 a row are 512 B a slot (5.38 GB a matvec at 10.5M slots,
  and 9.2 ms of vector work to select one lane of it). So rows travel as
  uint8 (128 B a slot), 2 values a row where the table allows, and an MXU
  product plus integer shifts rebuild the value bit for bit.

* **Blocked one-hot MXU scatter** (``EdgeSpMVPlan``): destination indices,
  pre-sorted and padded into fixed-capacity rows of 512-node blocks, are
  factored as ``off = hi*16 + lo``; the segment sum becomes a batched
  ``dot_general`` of two one-hot factors:

      y[b, hi, lo] = Σ_c OH_hi[b, c, hi] · (OH_lo[b, c, lo] · w[b, c])

  All FLOPs ride the MXU; there is no scatter anywhere. Per-edge weights
  (e.g. 1/outdeg for PageRank) are folded into the gather-select table for
  free.

Everything is static-shaped per plan (one compile per graph), matching the
reference's plan-per-query model. A plan has one of two layouts:

* ``blocks`` (the default): every 512-node destination block owns ONE row
  of ``capacity`` slots, the 0.995 quantile of the blocks' edge counts.
  A uniform graph pads 5% that way. Edges past a block's capacity go to a
  small overflow COO handled by ``segment_sum`` (the scalar path above).
* ``chunks`` (``layout="auto"`` picks it where it pads less; PR 33): the
  slots come in fixed chunks of ``CHUNK``; a block owns ⌈edges / CHUNK⌉
  consecutive chunks (at least one), ``chunk_block`` is the static chunk →
  block table, and there is NO overflow: a hub block simply owns more
  chunks. On a Graph500 Kronecker graph of scale 22 (largest block 189k
  edges, median 25k) that is 1.04 slots an edge where one capacity for
  every block takes 2.98 and still leaves 362k edges to the scalar tail.
  Only the compact-table Pallas executors (ops/pallas_spmv.py, one
  device: the matvec and, since PR 37, the k-wide product) take it; the
  expanded tables and ``shard_plan`` say so by name. A block's slots
  lie by destination row (PR 38; beside hub chunks too since PR 51), so
  a chunk names few rows and the k-wide scatter's one-hot is as tall as
  the shortest rung of ``WINDOWS`` that holds them (``chunk_windows``;
  PR 49: a ladder), and the slots of one row lie side by side, which is
  what the (max | min) reduction's scan reads (``rows_in_order``).
  Where the sources are skewed too (PR 36), the
  edges whose source is among the ``128·M`` of largest out-degree, the *hubs*, lie in
  a second set of chunks (``HubChunks``): a slot there names its source
  by rank, and the matvec takes ``x`` for it from a ``(M, 128)`` table in
  VMEM by lane permutes inside the scatter kernel, so these slots never
  reach the row gather above (2.2 ns a slot on a v5e against 0.2 to 0.7).
  A block's hub slots lie by table row (PR 42), so a vector register of
  1,024 of them names a short run of rows, recorded beside the chunk
  (``hub_walks``), and the kernel walks those rows and not the table: a
  row costs a walk step a block and no longer a permute every hub slot,
  which is what lets the table grow. Inside a register the slots lie
  by destination row (PR 51): the walk and the matvec's sum do not see
  that order, and the reduction's scan needs it. On that Graph500 graph 32,768 of
  2.4M sources hold 52% of the edges and 286,720 hold 86%; ``_hub_rows``
  chooses M from the degrees and the block count, 0 on a flat graph.

Either layout is refused (build returns None) when it pads past
``max_padding`` or ``max_slots``, so callers can use the plain path.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from matrel_tpu.obs import trace as trace_lib

WIDTH = 8        # values a table row of the plan layout (src8, lane): the
                 # gather's cost is flat for 8..128 because every such row
                 # is padded to 128 lanes; gather_1d picks its own row
BLOCK = 512      # scatter block: nodes per one-hot block row
HI = 32          # off = hi*LO + lo one-hot factor sizes; HI*LO == BLOCK
LO = 16
CHUNK = 2048     # slots a chunk of the ``chunks`` layout: one grid step of
                 # the Pallas scatter, a (16, 128) tile
WINDOWS = (128, 256)    # the ladder of heights, in rows of its block, a
                        # chunk's window takes (chunk_windows): at most 8
                        # rungs, rising, each a multiple of 8
# ``layout="auto"`` weighs the two layouts by the slots a matvec walks.
# An overflow edge rides XLA's scalar gather and segment_sum (~13 ns,
# module docstring) where a slot costs ~2 ns (PERF.md §5): 8 slots. A
# plan under a million slots is cheap whatever its padding and keeps
# the blocks layout, which every executor takes.
_OVERFLOW_EDGE_SLOTS = 8
_SMALL_PLAN_SLOTS = 1 << 20
HUB_ROW = 128    # hubs a row of the hub table: the lanes of a vector register
HUB_REG = 1024   # slots a vector register (8 sublanes of 128 lanes): the
                 # unit whose table rows a hub chunk records (hub_walks)
HUB_TILE = 8     # table rows an aligned (8, 128) load of the table takes: a
                 # walk starts on a multiple
HUB_WALK = 64    # table rows a step of the hub kernel's walk takes (so many
                 # lane permutes in flight a loop trip: PERF.md section 6,
                 # PR 42); a walk, and the table as the kernel holds it,
                 # is a whole number of steps
# The hub kernel walks, for every register of slots, the table rows the
# register names (PR 42; until then all of them, which is why the table
# stopped at 256 rows). A block's hub slots lie by table row, so its
# registers share the table out among them: a row is walked about once
# a block (3.1 ns a walked row on a v5e, loop trips of ``HUB_WALK`` rows
# and all) and costs the matvec its own entries of ``x[ids]`` (XLA's
# scalar gather, 128 values at 8.6 ns), and it saves the main path's
# 2.4 ns (row gather, byte product, select) less a hub slot's 0.2 for
# each of its own edges. So a row pays while its edges outnumber
# ``_HUB_ROW_EDGES_A_BLOCK`` for every block and ``_HUB_ROW_EDGES``
# besides: 7,500 edges a row on the Graph500 scale-22 graph's 4,681
# blocks, where the chip's sweep of forced widths put the break-even at
# about 7,000 (PERF.md section 6, PR 42: a query took 1.48 / 1.36 / 1.36
# / 1.41 / 1.45 s at 1,024 / 2,048 / the rule's own 2,240 / 3,072 /
# 4,096 rows, 2.15 at PR 36's 256). One graph and one block count stand
# behind the two constants: the split between them is the
# microbenchmark's, not a sweep's. ``_HUB_ROWS_MAX`` is the widest table
# that sweep ran (and the tallest whose walks fit their packed word,
# ``pallas_spmv._pack_walks``). No hubs where they would hold under
# ``_HUB_MIN_SHARE`` of the edges: a second ragged set and a second
# kernel for little.
_HUB_ROW_EDGES_A_BLOCK = 1.5
_HUB_ROW_EDGES = 500
_HUB_ROWS_MAX = 4096
_HUB_MIN_SHARE = 0.1
# The hub kernel's two scalar-prefetched words a chunk (its block, its
# registers' walks) lie in SMEM, 1 MiB on a v5e: 125,000 chunks compiled
# for the described chip and 131,072 did not (PR 42). The rule stops
# widening the table before a graph's hub chunks pass this.
_HUB_CHUNKS_MAX = 120_000

# probed once at import (os.umask is process-global; toggling it per save
# would race concurrent file creation in other threads)
_UMASK = os.umask(0)
os.umask(_UMASK)


def _ext_table(x: jax.Array, width: int = WIDTH) -> jax.Array:
    """Pad a 1-D table to (rows, width) with ≥1 zero row so index ``n``
    (the sentinel) and any padded slot read 0."""
    n = x.shape[0]
    rows = n // width + 1
    pad = rows * width - n
    return jnp.concatenate([x, jnp.zeros((pad,), x.dtype)]).reshape(
        rows, width)


# gather_1d's rows. A uint8 row of up to 128 elements occupies 128 B of
# an (8,128)(4,1) tile, and XLA's TPU gather runs at its row rate
# (~1.6 ns a row) only while the padded table sits in fast memory:
# 64 MB tables did (f32[125001,8], u8[500004,8]: 13.9 and 16.6 ms for
# 10.5M rows), 128 MB and 512 MB tables did not (61 and 48 ms), whatever
# the row (PERF.md §6, PR 28).
_ROW_BYTES_PADDED = 128
_FAST_TABLE_BYTES = 64 << 20


def source_panels(n_cols: int) -> int:
    """Into how many column ranges a k-wide product over ``n_cols``
    sources is split so that each range's gather table keeps the row
    rate: a float32 row of up to 128 columns is 512 B in the chip's
    tiles whatever it holds, and the table (the range's rows and the
    zero row of the padded slots) has to stay within
    ``_FAST_TABLE_BYTES``. One below 131,065 sources."""
    return -(-(n_cols + WIDTH) * 4 * _ROW_BYTES_PADDED // _FAST_TABLE_BYTES)


def _row_values(n: int) -> int:
    """Values a gathered row: the fewest (a power of two from 2 to 32)
    whose table of ``n + 1`` entries still fits fast memory. Fewer
    values a row leave less to select from after the gather."""
    w = 2
    while w < 32 and (n // w + 1) * _ROW_BYTES_PADDED > _FAST_TABLE_BYTES:
        w *= 2
    return w


def _halves_matrix(w: int) -> np.ndarray:
    """(2w, 4w) of 0, 1 and 256: a row of bytes (value l's b0..b3 at
    4l..4l+3) times it gives the low 16 bits of the w values, then
    their high 16 bits, each an exact f32 sum of two terms."""
    byte_weights = np.array([[1, 256, 0, 0], [0, 0, 1, 256]], np.float32)
    return np.einsum("lm,hb->hlmb", np.eye(w, dtype=np.float32),
                     byte_weights).reshape(2 * w, 4 * w)


def gather_1d(table: jax.Array, idx: jax.Array,
              width: Optional[int] = None) -> jax.Array:
    """``table[idx]`` for a 1-D table of a 4-byte dtype, bit for bit.
    ``idx == table.shape[0]`` is a valid sentinel reading 0; ``idx`` has
    at least one axis.

    The scalar gather is XLA's slow path on TPU (70 ms for 10M indices
    on v5e), a row gather its fast one. But a gathered row of 8 float32
    is laid out in 128 lanes: ``f32[slots, 8]`` in (8,128) tiles, 512 B
    a slot, and selecting the wanted lane from it is vector work on
    15/16 padding (9.2 ms for 10.5M slots beside the gather's 13.9).
    So the row travels as **bytes**: ``width`` values a row are
    ``4 * width`` uint8 (128 B a slot, 32 slots a vector register), an
    MXU product with a 0/1/256 matrix moves the bytes from slot-major
    onto the lanes as exact 16-bit halves (bytes are exact in bfloat16,
    each sum has at most two terms), and integer shifts, ors and one
    select among ``width`` rebuild the value: no float arithmetic ever
    touches it. Row r holds ``table[r], table[r + rows], ...`` (value l
    of a row lies l * rows further on), so the byte table is built from
    contiguous slices. ``width`` defaults to :func:`_row_values`.
    """
    byte_rows = byte_table(table, width)
    return gather_rows(byte_rows, idx, table.dtype)


def byte_table(table: jax.Array, width: Optional[int] = None) -> jax.Array:
    """The ``(rows, 4 * width)`` uint8 table :func:`gather_1d` gathers
    from: built once a matvec, gathered from once a panel."""
    n = table.shape[0]
    w = width or _row_values(n)
    rows = n // w + 1                                  # w * rows >= n + 1
    padded = jnp.concatenate(
        [table, jnp.zeros((w * rows - n,), table.dtype)]).reshape(w, rows)
    return jax.lax.bitcast_convert_type(padded, jnp.uint8).transpose(
        1, 0, 2).reshape(rows, 4 * w)


def gather_rows(byte_rows: jax.Array, idx: jax.Array, dtype) -> jax.Array:
    """``table[idx]`` from :func:`byte_table`'s rows (see
    :func:`gather_1d`)."""
    rows, w = byte_rows.shape[0], byte_rows.shape[1] // 4
    # which value of its row: idx // rows, as w - 1 compares
    sub = sum((idx >= l * rows).astype(jnp.int32) for l in range(1, w))
    g = byte_rows.at[idx - sub * rows].get(mode="promise_in_bounds")
    halves = jnp.einsum("kj,...sj->...ks",
                        jnp.asarray(_halves_matrix(w), jnp.bfloat16),
                        g.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    halves = halves.astype(jnp.uint32).reshape(
        idx.shape[:-1] + (2, w, idx.shape[-1]))
    vals = halves[..., 0, :, :] | (halves[..., 1, :, :] << 16)  # (..., w, s)
    sel = sub[..., None, :] == jnp.arange(w, dtype=jnp.int32)[:, None]
    out = jnp.sum(jnp.where(sel, vals, 0), axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(out, dtype)


@dataclasses.dataclass
class HubChunks:
    """The edges of a ``chunks`` plan whose source is a hub, in chunks of
    their own (B = #hub chunks; a block without hub edges owns none):
      ids          (128·M,) int32 — the hubs' column ids by falling edge
                   count: entry k is row ``k // 128``, lane ``k % 128`` of
                   the hub table ``x[ids]`` a matvec builds
      idx          (B, CHUNK) int32 — the slot's hub, as its place in
                   ``ids``; ``128·M`` in padded slots, which no table row
                   answers, so they weigh 0 whatever ``x`` holds
      off, val     (B, CHUNK) — as the plan's own
      chunk_block  (B,) int32 — ascending
      first, rows  (B, CHUNK // HUB_REG) int32 — the table rows
                   ``first : first + rows`` a register of the chunk's
                   slots names (:func:`hub_walks`), whole steps of
                   ``HUB_WALK``: what the hub kernel walks for it.
      entries      how many slots are real
    A block's real slots lie by table row (``idx // 128``) into
    registers of ``HUB_REG``, inside a register by destination row
    (``off``), inside a row by table row and then in input order, the
    padding after them, its ``off`` the last real slot's (PR 51)."""
    ids: np.ndarray
    idx: np.ndarray
    off: np.ndarray
    val: np.ndarray
    chunk_block: np.ndarray
    first: np.ndarray
    rows: np.ndarray
    entries: int

    @classmethod
    def of(cls, ids, idx, off, val, chunk_block) -> "HubChunks":
        """With the walks and the count reckoned from ``idx``."""
        first, rows = hub_walks(idx, ids.shape[0])
        return cls(ids, idx, off, val, chunk_block, first, rows,
                   int(np.count_nonzero(idx < ids.shape[0])))


@dataclasses.dataclass
class EdgeSpMVPlan:
    """Compiled layout for ``y[i] = Σ_{e: rows[e]=i} vals[e] · x[cols[e]]``.

    The host build stores only compact per-slot integers (~13 bytes/slot);
    the fat one-hot tables (~192 bytes/slot) are expanded ON DEVICE once,
    lazily — the host build and the host→device transfer stay ~15x
    smaller than the expanded tables.

    Shapes: B = #row blocks, C = per-block capacity — or, in the
    ``chunks`` layout (``chunk_block`` not None), B = #chunks, C = CHUNK,
    and ``chunk_block[i]`` is the row block chunk i adds into (ascending;
    every block owns at least one chunk; no overflow). ``hubs`` (chunks
    layout only) holds the edges whose source is a hub; the tables below
    then hold the others.
      src8    (B, C) int32 — width-row index of x per padded edge slot
      lane    (B, C) int8  — cols[e] % WIDTH
      off     (B, C) int32 — rows[e] % block
      val     (B, C) f32   — vals[e] (0 in padded slots)
    Materialized device tables:
      sel (B, C, WIDTH) f32; oh_hi (B, C, block//LO) f32; oh_lo (B, C, LO).
    Overflow: optional (cols, rows, vals) COO for edges beyond capacity,
    rows sorted ascending, handled by segment_sum.
    """
    n_rows: int
    n_cols: int
    block: int
    capacity: int
    src8: "np.ndarray | jax.Array"    # host until expansion/shard_plan
    lane: Optional["np.ndarray | jax.Array"]
    off: Optional["np.ndarray | jax.Array"]
    val: Optional["np.ndarray | jax.Array"]
    ov_cols: Optional[jax.Array]
    ov_rows: Optional[jax.Array]
    ov_vals: Optional[jax.Array]
    padding_ratio: float
    chunk_block: Optional[np.ndarray] = None    # (B,) int32: chunks layout
    hubs: Optional[HubChunks] = None
    _tables: Optional[tuple] = dataclasses.field(default=None, repr=False)
    _spmm_tables: Optional[tuple] = dataclasses.field(default=None,
                                                      repr=False)

    @property
    def overflow(self):
        """Overflow COO triple (cols, rows, vals), or () when none."""
        return (() if self.ov_cols is None
                else (self.ov_cols, self.ov_rows, self.ov_vals))

    def arrays(self):
        """Flat device-array tuple for passing through jit boundaries.
        First call expands the one-hot tables on device (one fused jitted
        program; ~130 MB shipped instead of ~2.4 GB). The compact tables
        stay HOST numpy until then, so ``shard_plan`` can place them
        sharded without ever materialising on a single device."""
        ov = () if self.ov_cols is None else (self.ov_cols, self.ov_rows,
                                              self.ov_vals)
        _blocks_layout_only(self, "the expanded one-hot tables")
        if self._tables is None:
            src8 = jnp.asarray(self.src8)        # no-op if pre-placed
            sel, oh_hi, oh_lo = _expand_tables(self.block // LO)(
                src8, jnp.asarray(self.lane), jnp.asarray(self.off),
                jnp.asarray(self.val))
            if isinstance(sel, jax.core.Tracer):
                # called inside an outer trace (executor lowering): the
                # expansion was staged and returned tracers — caching
                # them would poison the plan for every later use
                return (src8, sel, oh_hi, oh_lo) + ov
            self.src8 = src8
            self._tables = (src8, sel, oh_hi, oh_lo)
            # compact host tables are KEPT (~9 B/slot of host RAM): the
            # compact-table Pallas path (ops/pallas_spmv.py) reads them,
            # and dropping them made path order matter
        return self._tables + ov

    def spmm_extra(self, arrays=None):
        """(src_full, val) tables for the k-wide SpMM path, derived once
        from the expanded tables (src8·W + the lane sel marks; padded
        slots have all-zero sel, so they read a real-but-ignored row —
        val 0 kills the contribution). In-trace callers pass their
        already-staged ``arrays`` so the expansion isn't staged twice."""
        if self._spmm_tables is None:
            src8, sel = (arrays or self.arrays())[:2]
            tables = _derive_spmm_tables(src8, sel)
            if isinstance(tables[0], jax.core.Tracer):
                return tables                # in-trace: don't cache
            self._spmm_tables = tables
        return self._spmm_tables


def _blocks_layout_only(plan: EdgeSpMVPlan, who: str) -> None:
    """Only the compact-table Pallas executors of one device walk
    chunks; every other executor reads row i of the tables as block
    i."""
    if plan.chunk_block is not None:
        raise ValueError(
            f"{who} take only the blocks layout of an EdgeSpMVPlan; this "
            "plan is laid out in chunks (build_spmv_plan layout='auto' "
            "or 'chunks'): build it with layout='blocks', or run it "
            "through ops.pallas_spmv.spmv_compact / spmm_compact")


@jax.jit  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
def _derive_spmm_tables(src8, sel):
    lane = jnp.argmax(sel != 0.0, axis=-1).astype(jnp.int32)
    src_full = src8 * WIDTH + lane
    val = jnp.sum(sel, axis=-1)
    return src_full, val


@functools.lru_cache(maxsize=8)
def _expand_tables(hi_n: int):
    @jax.jit  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
    def expand(src8, lane, off, val):
        sel = jnp.where(
            lane[..., None] == jnp.arange(WIDTH, dtype=lane.dtype),
            val[..., None], 0.0)
        oh_hi = ((off // LO)[..., None] ==
                 jnp.arange(hi_n, dtype=off.dtype)).astype(jnp.float32)
        oh_lo = ((off % LO)[..., None] ==
                 jnp.arange(LO, dtype=off.dtype)).astype(jnp.float32)
        return sel, oh_hi, oh_lo

    return expand


def build_spmv_plan(rows, cols, vals=None, n_rows: int = None,
                    n_cols: int = None, *, block: int = BLOCK,
                    capacity_quantile: float = 0.995,
                    max_padding: float = 4.0,
                    max_slots: Optional[int] = None,
                    layout: str = "blocks",
                    refusals: Optional[list] = None,
                    hubs: bool = True
                    ) -> Optional[EdgeSpMVPlan]:
    """Host-side plan build (numpy, once per graph).

    ``layout="blocks"``: capacity is the ``capacity_quantile`` of
    per-block edge counts rounded up to a multiple of 128; edges past it
    go to the overflow COO. ``"chunks"``: every block owns as many
    chunks of ``CHUNK`` slots as its edges need, and nothing overflows;
    the edges from the sources of largest out-degree go to chunks of
    their own (``plan.hubs``) where :func:`_hub_rows` finds the sources
    skewed enough, a choice made from ``cols`` alone (``hubs=False``:
    never — a plan for the k-wide product, which fetches a hub's whole
    row like any other, gains nothing from a second set).
    ``"auto"`` (for a caller whose executor takes both: the compact
    Pallas matvec on one device) picks ``chunks`` where that walks
    fewer slots, an overflow edge counted as ``_OVERFLOW_EDGE_SLOTS``,
    and the blocks layout is not small anyway. Returns None when the
    layout pads worse than ``max_padding``× the edge count, or when the
    padded slot count exceeds ``max_slots`` (the expanded device tables
    cost ~224 B/slot of HBM, the compact ones 13 — pass a cap when the
    caller would rather fall back than spend that) — callers should
    then use the plain segment_sum path; which of the two it was is
    appended to ``refusals`` where the caller hands a list.
    """
    if layout not in ("blocks", "chunks", "auto"):
        raise ValueError(f"unknown layout {layout!r}")
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    m = rows.shape[0]
    if n_rows is None:
        n_rows = int(rows.max()) + 1 if m else 1
    if n_cols is None:
        n_cols = int(cols.max()) + 1 if m else 1
    if vals is not None:
        vals = np.asarray(vals, dtype=np.float32)
    if block % LO:
        raise ValueError("block must be a multiple of LO")
    if m and (rows.min() < 0 or rows.max() >= n_rows
              or cols.min() < 0 or cols.max() >= n_cols):
        raise ValueError("edge indices out of bounds for "
                         f"({n_rows}, {n_cols})")

    nb = -(-n_rows // block)
    from matrel_tpu.utils import native
    cnt = native.spmv_counts(rows, block, nb)
    use_native = cnt is not None
    if not use_native:
        cnt = np.bincount(rows // block, minlength=nb)
    if m == 0:
        cap = 128
    else:
        cap_q = int(np.quantile(cnt[cnt > 0], capacity_quantile)) \
            if (cnt > 0).any() else 0
        cap = max(128, -(-cap_q // 128) * 128)
    n_ov = int(np.maximum(cnt - cap, 0).sum())
    if layout == "auto":
        layout = "chunks" if (
            nb * cap + n_ov > _SMALL_PLAN_SLOTS
            and int(_chunks_owned(cnt).sum()) * CHUNK
            < nb * cap + _OVERFLOW_EDGE_SLOTS * n_ov
        ) else "blocks"
    hub_ids = None
    if layout == "chunks":
        if hubs:
            hub_ids, hub_rank = _choose_hubs(cols, n_cols, nb)
        if hub_ids is not None:
            hub_cnt = (native.spmv_counts_hubs(rows, cols, hub_rank, block,
                                               nb) if use_native else None)
            if hub_cnt is None:
                hub_cnt = np.bincount(rows[hub_rank[cols] >= 0] // block,
                                      minlength=nb)
            cnt = cnt - hub_cnt
            hub_first = _first_slots(_chunks_owned(hub_cnt, least=0))
        owned = _chunks_owned(cnt)
        n_rows_t, cap, n_ov = int(owned.sum()), CHUNK, 0
        first = _first_slots(owned)
    else:
        n_rows_t = nb
        first = np.arange(nb + 1, dtype=np.int64) * cap
    hub_slots = 0 if hub_ids is None else int(hub_first[-1])
    slots = n_rows_t * cap + hub_slots
    # Refuse only when padding hurts at scale: small plans are cheap no
    # matter the ratio, so the gate needs both the relative and an
    # absolute (1M padded slots) threshold. Callers fall back to the
    # plain segment_sum path on None. A hub slot holds less of the
    # device than another (pallas_spmv.HUB_BYTES_A_SLOT) and is counted
    # as one all the same.
    refused = None
    if m and slots > max_padding * m and slots > (1 << 20):
        refused = (f"padding: the {layout} layout takes {slots} slots for "
                   f"{m} edges, more than max_padding {max_padding:g} an "
                   "edge")
    elif max_slots is not None and slots > max_slots:
        refused = (f"bytes: the {layout} layout takes {slots} slots, the "
                   f"caller's memory gate holds {max_slots}")
    if refused:
        if refusals is not None:
            refusals.append(refused)
        return None

    # Native counting-sort fill (O(m), no argsort). The chunks layout
    # lies by row inside a block, slot for slot as the numpy path lays
    # it (the k-wide scatter's windows and the reduction's scan read
    # that order: chunk_windows, rows_in_order), and a block's hub
    # slots by table row into registers and by row inside a register,
    # slot for slot too (the hub kernel's walks read the one, hub_walks,
    # the scan the other); the blocks layout keeps input order inside a
    # block — the matvec's one-hot contraction is order-agnostic, so its
    # results match the numpy path
    # its seconds go on the build's record (a caller's cold
    # ``*.plan.build`` span) as ``fill_s``
    with trace_lib.part("fill_s"):
        filled = hub_filled = None
        if hub_ids is not None:
            both = (native.spmv_fill_ragged_hubs(
                rows, cols, vals, hub_rank, hub_ids.size, block, first,
                hub_first, WIDTH) if use_native else None)
            if both is not None:        # both sets in one walk over the edges
                filled, hub_filled = both
            else:
                # the edge list split, each set filled on its own: the hub
                # set over the table's columns, a value a "row" (width 1:
                # ``src8`` comes back as the rank, ``len(hub_ids)`` in
                # padded slots)
                of_edge = hub_rank[cols]
                at = np.flatnonzero(of_edge >= 0)
                rest = np.flatnonzero(of_edge < 0)
                at = at[np.argsort(
                    rows[at] // block * (hub_ids.size // HUB_ROW)
                    + of_edge[at] // HUB_ROW, kind="stable")]
                # and inside each register of its block by destination row
                blk = rows[at] // block
                reg = (np.arange(at.size) - (np.cumsum(hub_cnt) - hub_cnt)[blk]
                       ) // HUB_REG
                at = at[np.argsort(
                    (blk * (int(hub_cnt.max()) // HUB_REG + 1) + reg) * block
                    + rows[at] % block, kind="stable")]
                idx, _, hub_off, hub_val = _numpy_fill(
                    rows[at], of_edge[at].astype(np.int64),
                    None if vals is None else vals[at], hub_ids.size, block,
                    hub_first, hub_cnt, width=1, in_order=True,
                    pad_rows=True)[:4]
                hub_filled = idx, hub_off, hub_val
                rows, cols = rows[rest], cols[rest]
                vals = None if vals is None else vals[rest]
        elif use_native and layout == "chunks":
            filled = native.spmv_fill_ragged(rows, cols, vals, n_cols, block,
                                             first, WIDTH)
        elif use_native:
            filled = native.spmv_fill(rows, cols, vals, n_cols, block, nb, cap,
                                      WIDTH, n_ov)
        if filled is None:
            filled = _numpy_fill(rows, cols, vals, n_cols, block, first, cnt,
                                 pad_rows=layout == "chunks")
    src8, lane, off, val, ov_r64, ov_c64, ov_v = filled

    if n_ov:
        ov_c = jnp.asarray(ov_c64, jnp.int32)
        ov_r = jnp.asarray(ov_r64, jnp.int32)
        ov_v = jnp.asarray(ov_v, jnp.float32)
    else:
        ov_c = ov_r = ov_v = None

    hubs = None
    if hub_ids is not None:
        hub_shape = (hub_slots // CHUNK, CHUNK)
        idx, hub_off, hub_val = hub_filled
        with trace_lib.part("hub_walks_s"):
            hubs = HubChunks.of(
                hub_ids,
                np.ascontiguousarray(idx, np.int32).reshape(hub_shape),
                np.ascontiguousarray(hub_off, np.int32).reshape(hub_shape),
                np.ascontiguousarray(hub_val, np.float32).reshape(hub_shape),
                np.repeat(np.arange(nb, dtype=np.int32),
                          np.diff(hub_first) // CHUNK))

    # compact tables stay host-side numpy; they move to device (default
    # placement or sharded via shard_plan) at expansion time
    shape = (n_rows_t, cap)
    return EdgeSpMVPlan(
        n_rows=n_rows, n_cols=n_cols, block=block, capacity=cap,
        src8=np.ascontiguousarray(src8, np.int32).reshape(shape),
        lane=np.ascontiguousarray(lane, np.int8).reshape(shape),
        off=np.ascontiguousarray(off, np.int32).reshape(shape),
        val=np.ascontiguousarray(val, np.float32).reshape(shape),
        ov_cols=ov_c, ov_rows=ov_r, ov_vals=ov_v,
        padding_ratio=(slots + n_ov) / max(m, 1),
        chunk_block=(np.repeat(np.arange(nb, dtype=np.int32), owned)
                     if layout == "chunks" else None),
        hubs=hubs)


def _chunks_owned(cnt: np.ndarray, least: int = 1) -> np.ndarray:
    """Chunks a block of ``cnt`` edges owns: as many as they need, and
    at least ``least``."""
    return np.maximum(-(-cnt // CHUNK), least)


def _first_slots(owned: np.ndarray) -> np.ndarray:
    """Block b's chunks are the flat slots ``first[b]:first[b + 1]``."""
    first = np.zeros(owned.shape[0] + 1, np.int64)
    np.cumsum(owned * CHUNK, out=first[1:])
    return first


def chunk_windows(off: np.ndarray, real: np.ndarray,
                  block: int) -> np.ndarray:
    """``win`` (chunks,) int32 of a chunk table ``off`` (chunks, slots):
    for every chunk the SHORTEST rung of the ladder ``WINDOWS`` whose
    rows, from a start inside the chunk's block, hold every real slot of
    the chunk (``real``: not padding) — the k-wide scatter
    (ops/pallas_spmv.py) then builds that chunk's one-hot that many rows
    tall and not ``block`` — as ``start + rung``: the start is a
    multiple of 8, so the rung's index in ``WINDOWS`` rides in the three
    low bits (:func:`window_of` reads them apart). −1 where no rung
    holds the chunk's rows: the whole block. A rung not below ``block``
    drops out, so a block no taller than the first has no windows. The
    start is the chunk's least row rounded down to 8, moved back where
    the rung would reach past the block's end. A padded slot's ``off``
    (0) takes no part: outside the window it matches no row of the
    one-hot, and it adds 0 wherever it lands; a chunk that is all
    padding takes the block's last window of the first rung. In row
    order (the chunks fill without hubs, the numpy fills) the chunks of
    a block overlap in one row at most, so at most ``block / (h − 8)``
    of them spread past a rung of ``h`` rows whatever the data; tables
    in input order read −1 throughout."""
    low = np.where(real, off, block).min(axis=1)
    high = np.where(real, off, -1).max(axis=1)
    win = np.full(off.shape[0], -1, np.int32)
    # the tallest first: a shorter rung that holds the chunk overrides
    for rung, height in reversed(list(enumerate(WINDOWS))):
        if height < block:
            at = np.minimum(low // 8 * 8, block - height)
            win = np.where(high - at < height, at + rung, win)
    return win.astype(np.int32)


def rows_in_order(plan: "EdgeSpMVPlan") -> bool:
    """Whether the (max | min) reduction over the chunk grid
    (ops/pallas_spmv.reduce_apply) may read this plan: laid out in
    chunks, and in every row of 128 slots — of its own tables and of
    its hub chunks', where it has any (padding there: ``idx`` =
    ``len(ids)``) — the real slots first and ``off`` never falling,
    padding included, so the slots that name one destination row lie
    side by side there, which is what its segmented scan takes for
    granted (the matvec's one-hot SUM is order-agnostic and never
    asked). It asks nothing across rows of 128: a block's main slots lie
    by row throughout (PR 38), its hub slots by table row into
    registers of ``HUB_REG`` and by row inside each (PR 51), and either
    passes; a padded slot's ``off`` is its block's last real one's. The
    chunks fills lay plans so; checked on the tables themselves, once a
    plan."""
    if plan.chunk_block is None:
        return False
    said = getattr(plan, "_rows_in_order", None)
    if said is None:
        # a padded slot names the sentinel source ``n_cols``
        real = ~((np.asarray(plan.src8) == plan.n_cols // WIDTH)
                 & (np.asarray(plan.lane) == plan.n_cols % WIDTH))
        said = _runs_side_by_side(np.asarray(plan.off), real)
        hub = plan.hubs
        if said and hub is not None:
            said = _runs_side_by_side(hub.off, hub.idx < hub.ids.shape[0])
        plan._rows_in_order = said
    return said


def _runs_side_by_side(off: np.ndarray, real: np.ndarray) -> bool:
    """:func:`rows_in_order`'s rule over one set of chunk tables."""
    off, real = off.reshape(-1, 128), real.reshape(-1, 128)
    return bool(np.all(real[:, 1:] <= real[:, :-1])
                and np.all(off[:, 1:] >= off[:, :-1]))


def window_of(win):
    """(start, height) of every chunk of a table :func:`chunk_windows`
    made: (−1, 0) where the chunk takes the whole block."""
    win = np.asarray(win)
    has = win >= 0
    rung = np.where(has, win & 7, 0)
    return (np.where(has, win & ~7, -1),
            np.where(has, np.asarray(WINDOWS)[rung], 0))


def _hub_rows(deg_desc: np.ndarray, edges: int, blocks: int) -> int:
    """How many rows of ``HUB_ROW`` the hub table gets, from the sources'
    edge counts in falling order and the number of destination blocks,
    each of which walks a row once: rows are taken while one still pays
    (see ``_HUB_ROW_EDGES_A_BLOCK``) and the hub chunks there would be
    at the most stay under ``_HUB_CHUNKS_MAX``; the walk step the last
    of them opens is walked whole whatever it holds, so its other rows
    cost their entries of ``x[ids]`` alone and are taken while they pay
    those (``_HUB_ROW_EDGES``); ``_HUB_ROWS_MAX`` at the most, and none
    where all of them would hold under ``_HUB_MIN_SHARE`` of the
    edges."""
    most = min(_HUB_ROWS_MAX, -(-int(np.count_nonzero(deg_desc)) // HUB_ROW))
    a_row = np.zeros(most * HUB_ROW, np.int64)
    top = deg_desc[:a_row.size]
    a_row[:top.size] = top
    a_row = a_row.reshape(most, HUB_ROW).sum(1)
    held = np.cumsum(a_row)
    # a block's hub slots round up to whole chunks: one more at the most
    fits = held // CHUNK + blocks <= _HUB_CHUNKS_MAX

    def taken(rows: int, upto: int, price: float) -> int:
        pays = (a_row[rows:upto] >= price) & fits[rows:upto]
        return upto if pays.all() else rows + int(np.argmin(pays))

    rows = taken(0, most, _HUB_ROW_EDGES_A_BLOCK * blocks + _HUB_ROW_EDGES)
    rows = taken(rows, min(hub_table_rows(rows), most), _HUB_ROW_EDGES)
    return rows if rows and held[rows - 1] >= _HUB_MIN_SHARE * edges else 0


def _choose_hubs(cols: np.ndarray, n_cols: int, blocks: int):
    """(the hubs' column ids by falling edge count, a whole number of
    table rows; every column's place among them, −1 where it is no hub)
    — or (None, None) where :func:`_hub_rows` takes no row. Ties fall
    to the smaller id, so a graph has one answer."""
    deg = np.bincount(cols, minlength=n_cols)
    by_deg = np.argsort(-deg, kind="stable")[:_HUB_ROWS_MAX * HUB_ROW]
    rows = _hub_rows(deg[by_deg], cols.shape[0], blocks)
    if rows == 0:
        return None, None
    # a table row is whole: past the last source the ids name column 0,
    # which no slot asks them for
    ids = np.zeros(rows * HUB_ROW, np.int32)
    real = by_deg[:ids.size]
    ids[:real.size] = real
    rank = np.full(n_cols, -1, np.int32)
    rank[real] = np.arange(real.size, dtype=np.int32)
    return ids, rank


def takes_hubs(cols, n_cols: int, n_rows: int, block: int = BLOCK) -> bool:
    """Whether a plan in chunks of these entries gets hub chunks
    (``build_spmv_plan(hubs=True)``): the rule's own answer, from the
    sources' edge counts and the block count alone, without a build."""
    return _choose_hubs(np.asarray(cols, np.int64), n_cols,
                        -(-n_rows // block))[0] is not None


def hub_walks(idx: np.ndarray, n_hubs: int):
    """(``first``, ``rows``), each (chunks, CHUNK // HUB_REG) int32, of a
    hub chunk table ``idx`` (chunks, CHUNK): the run of table rows,
    ``first : first + rows``, that holds the row ``idx // 128`` of every
    real slot of a register of ``HUB_REG`` slots; ``first`` a multiple
    of ``HUB_TILE``, ``rows`` a whole number of steps of ``HUB_WALK``,
    one at the least, and the run inside the table as the kernel holds
    it (:func:`hub_table_rows`). The hub kernel walks that run for the
    register and no other row. A padded slot (``idx`` = ``n_hubs``)
    takes no part; a register of padding alone walks the table's first
    step, as the kernel walks one whatever it is told. Whatever the
    order of the slots the run covers them; as the build lays them (a
    block's slots by table row into registers; the order inside a
    register, by destination row since PR 51, is nothing to a run) the
    runs of a block's registers share the table out and overlap in a
    step at most."""
    row = idx.reshape(idx.shape[0], -1, HUB_REG) >> 7
    padded = n_hubs >> 7           # one past the table's last row
    low = row.min(axis=2)          # padding is the largest row there is
    high = np.where(row < padded, row, -1).max(axis=2)
    low = np.where(high < 0, 0, low)
    first = low // HUB_TILE * HUB_TILE
    rows = np.maximum(-(-(high + 1 - first) // HUB_WALK), 1) * HUB_WALK
    first = np.minimum(first, hub_table_rows(padded) - rows)
    return first.astype(np.int32), rows.astype(np.int32)


def hub_table_rows(rows: int) -> int:
    """Rows of the hub table as the kernel holds it: a whole number of
    walk steps (past the table's own the rows are zeros, which no slot
    names)."""
    return -(-rows // HUB_WALK) * HUB_WALK


def _numpy_fill(rows, cols, vals, n_cols, block, first, cnt,
                width: int = WIDTH, in_order: bool = False,
                pad_rows: bool = False):
    """Pure-numpy plan fill (fallback when the native library is
    unavailable): stable argsort by row (``in_order``: the edges come
    block by block already, in the order their slots shall have), then
    fancy-indexed scatters. Block b owns the flat slots
    ``first[b]:first[b + 1]`` (one row of ``cap`` in the blocks layout,
    its chunks in the other); edges past them are the overflow. A column
    is a row of ``width`` and a lane. ``pad_rows`` (the chunks layout,
    where nothing overflows): a padded slot's ``off`` is that of its
    block's last edge and not 0, so ``off`` never falls along a row of
    128 (:func:`rows_in_order`)."""
    m = rows.shape[0]
    if vals is None:
        vals = np.ones((m,), np.float32)
    if in_order:
        rows_s, cols_s, vals_s = rows, cols, vals
    else:
        order = np.argsort(rows, kind="stable")
        rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    blk = rows_s // block
    starts = np.zeros(first.shape[0], np.int64)
    np.cumsum(cnt, out=starts[1:])
    pos = first[blk] + np.arange(m, dtype=np.int64) - starts[blk]
    in_main = pos < first[blk + 1]

    slots = int(first[-1])
    src_pad = np.full(slots, n_cols, np.int64)       # sentinel -> reads 0
    val_pad = np.zeros(slots, np.float32)
    off_pad = np.zeros(slots, np.int64)
    if pad_rows and m:
        last = rows_s[np.maximum(starts[1:] - 1, 0)] % block
        off_pad = np.repeat(np.where(cnt > 0, last, 0), np.diff(first))
    p_main = pos[in_main]
    src_pad[p_main] = cols_s[in_main]
    val_pad[p_main] = vals_s[in_main]
    off_pad[p_main] = rows_s[in_main] % block
    return ((src_pad // width).astype(np.int32),
            (src_pad % width).astype(np.int8),
            off_pad.astype(np.int32), val_pad,
            rows_s[~in_main], cols_s[~in_main], vals_s[~in_main])


def _onehot_contrib(src8, sel, oh_hi, oh_lo, x_ext) -> jax.Array:
    """The core contraction: flat (B·block,) partial sums for the blocks
    these tables describe. ``x_ext`` is the width-padded 2-D table of x."""
    g = jnp.take(x_ext, src8, axis=0)                  # (B, C, W) row gather
    w = jnp.sum(g * sel, axis=-1)                      # exact f32 select
    # MXU segment-sum: batch B, contract C. bf16_3x ≈ f32 accuracy at 3
    # passes; the one-hots are exact in bf16.
    contrib = jax.lax.dot_general(
        oh_hi, oh_lo * w[..., None],
        (((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGH)              # (B, HI', LO)
    return contrib.reshape(-1)


def _overflow_add(y, ov, x, n_rows):
    """Accumulate the overflow COO triple (cols, rows, vals)."""
    ov_c, ov_r, ov_v = ov
    w_ov = gather_1d(x.astype(jnp.float32), ov_c) * ov_v
    return y + jax.ops.segment_sum(w_ov, ov_r, num_segments=n_rows,
                                   indices_are_sorted=True)


def spmv_apply(plan_static, arrays, x: jax.Array) -> jax.Array:
    """Traceable body: y = A·x given a plan. ``plan_static`` is the
    (n_rows, n_cols, block) tuple; ``arrays`` is plan.arrays(). Safe to
    call inside jit/fori_loop with the arrays as loop-invariant args."""
    n_rows, n_cols, block = plan_static
    src8, sel, oh_hi, oh_lo = arrays[:4]
    y = _onehot_contrib(src8, sel, oh_hi, oh_lo,
                        _ext_table(x.astype(jnp.float32)))[:n_rows]
    if len(arrays) > 4:
        y = _overflow_add(y, arrays[4:], x, n_rows)
    return y


_SPMM_B_CHUNK = 128   # blocks per scatter chunk: bounds the (chunk, C,
                      # LO·k) one-hot⊗w intermediate to a few hundred MB


def spmm_apply(plan_static, arrays, extra, X: jax.Array) -> jax.Array:
    """Traceable k-wide SpMM body: Y = A·X for dense X (n_cols, k).

    One shared row gather serves every column (vs k full passes of
    ``spmv_apply``); the scatter contracts oh_hi against (oh_lo ⊗ w)
    per B-chunk so the widened one-hot never materialises whole.
    Traffic scales ~linearly in k; callers chunk very wide X.
    """
    n_rows, n_cols, block = plan_static
    _, _, oh_hi, oh_lo = arrays[:4]
    src_full, val = extra
    k = X.shape[1]
    x_ext = jnp.concatenate(
        [X.astype(jnp.float32), jnp.zeros((WIDTH, k), jnp.float32)])
    g = jnp.take(x_ext, src_full, axis=0)              # (B, C, k)
    w = g * val[..., None]
    nb, cap = src_full.shape
    ch = min(_SPMM_B_CHUNK, max(nb, 1))   # don't pad tiny plans up to 128
    nch = -(-nb // ch)
    pad = nch * ch - nb

    def pad_b(a):
        if pad == 0:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])

    hh = pad_b(oh_hi).reshape(nch, ch, cap, -1)
    ll = pad_b(oh_lo).reshape(nch, ch, cap, LO)
    ww = pad_b(w).reshape(nch, ch, cap, k)

    def chunk(args):
        h, l, v = args
        rhs = (l[..., :, None] * v[..., None, :]).reshape(ch, cap, LO * k)
        return jax.lax.dot_general(
            h, rhs, (((1,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGH)          # (ch, H, LO·k)

    out = jax.lax.map(chunk, (hh, ll, ww))             # (nch, ch, H, LO·k)
    y = out.reshape(nch * ch, -1, LO, k).reshape(-1, k)[:n_rows]
    if len(arrays) > 4:
        y = _overflow_add_wide(y, arrays[4:], X, n_rows)
    return y


def _overflow_add_wide(y, ov, X, n_rows):
    """k-wide overflow COO accumulation of the (cols, rows, vals)
    triple. Overflow indices are always real columns (< n_cols —
    sentinels never overflow), so gather straight from X, no padded
    copy."""
    ov_c, ov_r, ov_v = ov
    w_ov = jnp.take(X.astype(jnp.float32), ov_c, axis=0) * ov_v[:, None]
    return y + jax.ops.segment_sum(w_ov, ov_r, num_segments=n_rows,
                                   indices_are_sorted=True)


_spmm_jitted = jax.jit(spmm_apply, static_argnums=0)  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)


def spmm(plan: EdgeSpMVPlan, X: jax.Array,
         col_chunk: int = 64) -> jax.Array:
    """Y = A·X for dense X (n_cols, k), k columns processed ``col_chunk``
    at a time (scatter traffic grows linearly in k). k == 1 takes the
    matvec kernel — its width-8 row gather beats spmm's width-1."""
    X = jnp.asarray(X, jnp.float32)
    static = (plan.n_rows, plan.n_cols, plan.block)
    if X.shape[1] == 0:
        return jnp.zeros((plan.n_rows, 0), jnp.float32)
    if X.shape[1] == 1:
        return spmv(plan, X[:, 0])[:, None]
    outs = [_spmm_jitted(static, plan.arrays(), plan.spmm_extra(),
                         X[:, j:j + col_chunk])
            for j in range(0, X.shape[1], col_chunk)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def spmv_sharded_apply(plan_static, arrays, x: jax.Array,
                       mesh) -> jax.Array:
    """Traceable body for a MESH-SHARDED plan, to be called INSIDE a
    ``shard_map`` over all of ``mesh``'s axes: ``arrays`` tables arrive as
    per-device shards (the device's slice of destination blocks), x is
    replicated; one tiled all_gather assembles the output. Overflow COO
    is replicated — every device computes it identically (it is small by
    construction)."""
    n_rows, n_cols, block = plan_static
    src8, sel, oh_hi, oh_lo = arrays[:4]
    axes = tuple(mesh.axis_names)
    y_loc = _onehot_contrib(src8, sel, oh_hi, oh_lo,
                            _ext_table(x.astype(jnp.float32)))
    y = jax.lax.all_gather(y_loc, axes, axis=0, tiled=True)[:n_rows]
    if len(arrays) > 4:
        y = _overflow_add(y, arrays[4:], x, n_rows)
    return y


def spmm_sharded_apply(plan_static, arrays, extra, X: jax.Array,
                       mesh) -> jax.Array:
    """k-wide variant of ``spmv_sharded_apply`` (call inside shard_map
    over all mesh axes): per-device block-slice contraction of the
    replicated X, one tiled all_gather of the (n, k) result."""
    n_rows, n_cols, block = plan_static
    axes = tuple(mesh.axis_names)
    # local contribution: full spmm body minus overflow/slicing
    y_loc = spmm_apply((block * arrays[0].shape[0], n_cols, block),
                       arrays[:4], extra, X)
    y = jax.lax.all_gather(y_loc, axes, axis=0, tiled=True)[:n_rows]
    if len(arrays) > 4:
        y = _overflow_add_wide(y, arrays[4:], X, n_rows)
    return y


@functools.lru_cache(maxsize=32)
def _sharded_spmm_runner(plan_static, mesh, has_overflow: bool):
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    table_specs = sharded_table_specs(axes, 7 if has_overflow else 4)
    # the spmm extra tables are derived from sharded tables elementwise,
    # so they carry the same block-axis sharding
    in_specs = (table_specs[:4]
                + (P(axes, None), P(axes, None))   # src_full, val
                + (P(),)                            # X replicated
                + table_specs[4:])

    def kernel(src8, sel, oh_hi, oh_lo, src_full, val, x, *ov):
        arrays = (src8, sel, oh_hi, oh_lo) + ov
        return spmm_sharded_apply(plan_static, arrays, (src_full, val),
                                  x, mesh)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=in_specs,  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
                             out_specs=P(), check_vma=False))


def spmm_sharded(plan: EdgeSpMVPlan, X: jax.Array, mesh,
                 col_chunk: int = 64) -> jax.Array:
    """Y = A·X over a mesh-sharded plan (see ``shard_plan``)."""
    X = jnp.asarray(X, jnp.float32)
    if X.shape[1] == 0:
        return jnp.zeros((plan.n_rows, 0), jnp.float32)
    if X.shape[1] == 1:
        return spmv_sharded(plan, X[:, 0], mesh)[:, None]
    arrays = plan.arrays()
    extra = plan.spmm_extra(arrays)
    run = _sharded_spmm_runner((plan.n_rows, plan.n_cols, plan.block),
                               mesh, len(arrays) > 4)
    outs = []
    for j in range(0, X.shape[1], col_chunk):
        outs.append(run(*arrays[:4], *extra, X[:, j:j + col_chunk],
                        *arrays[4:]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def compact_pad_fills(n_cols: int) -> dict:
    """Sentinel fill values for padded slots/blocks of the compact
    layout, shared by every sharding path: src8 rows point at the
    zero sentinel row of _ext_table, val 0 kills any contribution."""
    return {"src8": n_cols // WIDTH, "lane": n_cols % WIDTH,
            "off": 0, "val": 0.0}


def shard_plan(plan: EdgeSpMVPlan, mesh) -> EdgeSpMVPlan:
    """Row-decompose a plan over all devices of ``mesh``: the block axis
    pads to the device count and the compact tables are placed with
    ``P((axes...), None)`` sharding; the one-hot expansion (elementwise)
    preserves it, so each device holds ~1/P of the ~224 B/slot tables.
    Use with ``spmv_sharded_apply`` inside shard_map. Must be called
    before the plan's tables are expanded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    _blocks_layout_only(plan, "shard_plan and the sharded executors")
    if plan._tables is not None:
        raise ValueError("shard_plan must run before table expansion "
                         "(call it on a freshly built plan)")
    axes = tuple(mesh.axis_names)
    p = mesh.size
    nb, cap = plan.src8.shape
    nb_pad = -(-nb // p) * p
    pad = nb_pad - nb

    def padded(a, fill):
        if pad == 0:
            return np.asarray(a)
        return np.concatenate(
            [np.asarray(a),
             np.full((pad, *a.shape[1:]), fill, np.asarray(a).dtype)])

    fills = compact_pad_fills(plan.n_cols)
    sh2 = NamedSharding(mesh, P(axes, None))
    return dataclasses.replace(
        plan,
        src8=jax.device_put(padded(plan.src8, fills["src8"]), sh2),  # matlint: disable=ML008 host-built compact table placed on its sharded layout at plan build
        lane=jax.device_put(padded(plan.lane, fills["lane"]), sh2),  # matlint: disable=ML008 host-built compact table placed on its sharded layout at plan build
        off=jax.device_put(padded(plan.off, fills["off"]), sh2),  # matlint: disable=ML008 host-built compact table placed on its sharded layout at plan build
        val=jax.device_put(padded(plan.val, fills["val"]), sh2))  # matlint: disable=ML008 host-built compact table placed on its sharded layout at plan build


_spmv_jitted = jax.jit(spmv_apply, static_argnums=0)  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)


def spmv(plan: EdgeSpMVPlan, x: jax.Array) -> jax.Array:
    """y = A·x (convenience wrapper; jit-cached per plan shape)."""
    return _spmv_jitted((plan.n_rows, plan.n_cols, plan.block),
                        plan.arrays(), x)


def sharded_table_specs(axes, n_arrays: int):
    """PartitionSpecs for plan.arrays() under the row decomposition:
    the four tables sharded on the block axis, overflow COO replicated."""
    from jax.sharding import PartitionSpec as P
    specs = (P(axes, None), P(axes, None, None), P(axes, None, None),
             P(axes, None, None))
    if n_arrays > 4:
        specs = specs + (P(), P(), P())
    return specs


@functools.lru_cache(maxsize=32)
def _sharded_spmv_runner(plan_static, mesh, has_overflow: bool):
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    table_specs = sharded_table_specs(axes, 7 if has_overflow else 4)
    in_specs = table_specs[:4] + (P(),) + table_specs[4:]  # x after tables

    def kernel(src8, sel, oh_hi, oh_lo, x, *ov):
        return spmv_sharded_apply(plan_static, (src8, sel, oh_hi, oh_lo)
                                  + ov, x, mesh)

    # check_vma=False: the tiled all_gather output is value-identical on
    # every device but typed "varying", which the replication check
    # cannot statically see through
    return jax.jit(shard_map(  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)
        kernel, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False))


def spmv_sharded(plan: EdgeSpMVPlan, x: jax.Array, mesh) -> jax.Array:
    """y = A·x over a mesh-sharded plan (see ``shard_plan``): each device
    contracts its slice of destination blocks against the replicated x;
    one tiled all_gather of the (n,) result rides ICI."""
    arrays = plan.arrays()
    run = _sharded_spmv_runner((plan.n_rows, plan.n_cols, plan.block),
                               mesh, len(arrays) > 4)
    return run(*arrays[:4], jnp.asarray(x, jnp.float32), *arrays[4:])


# -- plan persistence --------------------------------------------------------


# what a plan file holds of its hub chunks
_HUB_FILE_FIELDS = ("ids", "idx", "off", "val", "chunk_block")


def save_plan(path: str, plan: EdgeSpMVPlan) -> None:
    """Persist a plan's compact layout (one .npz). The expensive build
    (host sort/fill) is skipped on load; table expansion (or the compact
    executor's device copy) happens on the loading process's device.
    Plans keep their compact tables for life, so saving works before OR
    after any executor has used the plan."""
    chunked = plan.chunk_block is not None
    payload = dict(
        # trailing fields: format version + the WIDTH/LO constants baked
        # into src8/lane/off at build time — loading under different
        # constants must fail loudly, not gather from wrong rows. The
        # chunks layout is version 2: a reader that knows only version 1
        # would take chunk i for block i; with hub chunks version 3: a
        # reader of version 2 would leave their edges out (their walks
        # are reckoned from the slots on load: a file of PR 36, whose
        # hub slots lie in input order, loads and runs)
        meta=np.asarray([plan.n_rows, plan.n_cols, plan.block,
                         plan.capacity,
                         3 if plan.hubs is not None else 2 if chunked else 1,
                         WIDTH, LO],
                        np.int64),
        padding_ratio=np.asarray([plan.padding_ratio], np.float64),
        src8=np.asarray(plan.src8), lane=np.asarray(plan.lane),
        off=np.asarray(plan.off), val=np.asarray(plan.val))
    if chunked:
        payload.update(chunk_block=np.asarray(plan.chunk_block, np.int32))
    if plan.hubs is not None:
        payload.update({f"hub_{name}": getattr(plan.hubs, name)
                        for name in _HUB_FILE_FIELDS})
    if plan.ov_rows is not None:
        payload.update(ov_rows=np.asarray(plan.ov_rows),
                       ov_cols=np.asarray(plan.ov_cols),
                       ov_vals=np.asarray(plan.ov_vals))
    import tempfile
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)),
                               suffix=".tmp")
    try:
        os.fchmod(fd, 0o666 & ~_UMASK)  # mkstemp's 0600 ignores the umask
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **payload)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_plan(path: str) -> EdgeSpMVPlan:
    """Load a plan saved by ``save_plan``."""
    with np.load(path) as z:
        meta = [int(v) for v in z["meta"]]
        n_rows, n_cols, block, cap = meta[:4]
        version, width, lo = (meta[4:7] if len(meta) >= 7 else (0, -1, -1))
        if version not in (1, 2, 3) or width != WIDTH or lo != LO:
            raise ValueError(
                f"plan file {path!r} was saved with format v{version} "
                f"(WIDTH={width}, LO={lo}); this build expects v1 to v3 "
                f"(WIDTH={WIDTH}, LO={LO}) — rebuild the plan")
        has_ov = "ov_rows" in z.files
        return EdgeSpMVPlan(
            n_rows=n_rows, n_cols=n_cols, block=block, capacity=cap,
            src8=z["src8"], lane=z["lane"], off=z["off"], val=z["val"],
            ov_rows=jnp.asarray(z["ov_rows"]) if has_ov else None,
            ov_cols=jnp.asarray(z["ov_cols"]) if has_ov else None,
            ov_vals=jnp.asarray(z["ov_vals"]) if has_ov else None,
            padding_ratio=float(z["padding_ratio"][0]),
            chunk_block=z["chunk_block"] if version >= 2 else None,
            hubs=HubChunks.of(*(z[f"hub_{name}"]
                                for name in _HUB_FILE_FIELDS))
            if version == 3 else None)
