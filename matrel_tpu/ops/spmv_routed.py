"""Routed SpMV: sparse matvec as pure MXU matmuls — no gather engine.

The one-hot SpMV plan (ops/spmv.py) is scatter-free but still pays the
TPU gather engine 1.3-1.6 ns per edge slot for the x-row fetch; at
BASELINE row-5 scale that gather is 13.9-16.6 ms of the round (PERF.md
§6, PR 28). The engine is rate-limited per index: locality does not move
it, a row's width does not (up to 128 elements), and its element type
moves it the wrong way (10.5M rows: float32 13.9 ms, uint8 16.6, uint16
17.5) — what the element type does decide is the bytes a gathered row is
padded to, and so the cost of the step after it. This module
removes the gather entirely by reshaping SpMV into the two dense
contractions the MXU executes well, the same way the reference reshapes
its matvec into shuffle + per-block kernels (SURVEY.md §3.5).

**Measured outcome (v5e, 1M nodes / 10M edges): 52 ms vs 29 ms for the
gather-based plan — the routed path does NOT win on this hardware.** The
kernels are matmul-light but must GENERATE four ~(slots, 128) one-hot/
mask tensors per matvec on the VPU (~2.7 ns/slot at ~5 vector ops per
lane), which costs as much as the gather engine it replaces; `passes`=2
vs 3 timing is identical, confirming mask generation, not MXU work, is
the bound. Lane padding makes narrower masks free-of-charge impossible
(<128-wide vectors occupy full lanes). The module is kept as a correct,
tested reference formulation: it is the shape a multi-chip all_to_all
SpMV takes (phase 2's layout transpose IS the shuffle), and the
trade-off flips wherever index-gather is slower relative to VPU/MXU
than on v5e. Algorithm:

* Edges are bucketed by (source group, destination group), both groups
  ``span = 128·128`` wide, with a fixed per-cell capacity (large cells →
  tiny padding: Poisson concentration gives ~1.1× at 10M edges).

* **Phase 1 — gather as matmul.** For cell (gs, gd), each edge's source
  offset inside its group factors as ``a·128 + b``. With x's group
  reshaped to a (128, 128) tile X2, ``x[src] = Σ_a oh_a · X2[a, b]``:
  one (cap, 128) one-hot GENERATED IN VMEM (never stored to HBM)
  contracts against X2 on the MXU, and a cheap VPU one-hot select reads
  lane b. f32 accuracy from bf16 passes: X2 ships as [hi | lo] bf16
  halves (hi = bf16(x), lo = bf16(x − hi)) in one 256-wide matmul —
  exact because one-hot rows have a single 1.

* **Phase 2 — the shuffle is a BlockSpec.** Phase 1 writes per-edge
  products W in (gs, gd, cap) source-major layout; phase 3 simply reads
  block (gs, gd) via its index map while iterating destination-major.
  The layout transpose (Spark's shuffle; all_to_all on a mesh) costs one
  11 KB DMA per cell — there is no transpose pass at all.

* **Phase 3 — scatter as matmul.** Destination offsets factor as
  ``c·128 + d``; the cell's contribution to its destination group's
  (128, 128) accumulator tile is ``oh_cᵀ @ (oh_d ⊙ w)`` — one MXU
  contraction over the cell's slots, accumulated in VMEM scratch across
  all source groups, flushed once per destination group. w rides as
  [hi | lo] bf16 halves for f32 accuracy.

Everything static-shaped per plan; overflow edges beyond cell capacity
go to a small COO handled by segment_sum (same contract as
ops/spmv.py). Build returns None when padding would blow past
``max_padding`` so callers can fall back.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SPAN = 128 * 128          # source/destination group width (a, b each 128)
LANE = 128


@dataclasses.dataclass
class RoutedSpMVPlan:
    """Compiled routed layout for ``y[i] = Σ_{e: rows[e]=i} vals[e]·x[cols[e]]``.

    Tables are (G_s, G_d, cap//128, 128) in source-major order (the
    trailing two dims are the cell's slots in TPU tile layout);
    ``loc_src``/``loc_dst`` hold offsets inside the edge's source/
    destination group (< SPAN, packed a·128+b), ``val`` is 0 in padded
    slots so they contribute nothing in either phase.
    """
    n_rows: int
    n_cols: int
    g_src: int
    g_dst: int
    cap: int
    loc_src: "np.ndarray | jax.Array"   # (G_s, G_d, cap/128, 128) int32
    loc_dst: "np.ndarray | jax.Array"   # (G_s, G_d, cap/128, 128) int32
    val: "np.ndarray | jax.Array"       # (G_s, G_d, cap/128, 128) f32
    ov_rows: Optional[jax.Array]        # overflow COO (dst-sorted)
    ov_cols: Optional[jax.Array]
    ov_vals: Optional[jax.Array]
    padding_ratio: float
    _dev: Optional[tuple] = dataclasses.field(default=None, repr=False)

    @property
    def slots(self) -> int:
        return self.g_src * self.g_dst * self.cap

    def arrays(self):
        """Device-array tuple for jit boundaries (placed on first use).
        The tables are host numpy from the build, so jnp.asarray yields
        concrete constants even inside an outer trace — safe to cache."""
        ov = () if self.ov_rows is None else (self.ov_rows, self.ov_cols,
                                              self.ov_vals)
        if self._dev is None:
            self._dev = (jnp.asarray(self.loc_src),
                         jnp.asarray(self.loc_dst), jnp.asarray(self.val))
            self.loc_src = self.loc_dst = self.val = None
        return self._dev + ov


def build_routed_plan(rows, cols, vals=None, n_rows: int = None,
                      n_cols: int = None, *,
                      capacity_quantile: float = 0.997,
                      max_padding: float = 3.0,
                      max_slots: Optional[int] = None,
                      max_cap: int = 4096
                      ) -> Optional[RoutedSpMVPlan]:
    """Host-side plan build (numpy, once per graph).

    Cell capacity is the ``capacity_quantile`` of per-cell edge counts
    rounded up to a multiple of 128 (the matmul row dim); edges past it
    go to the overflow COO. Returns None when the padded slot count
    exceeds ``max_padding``× the edge count (sparse cells — small or
    very skewed graphs are better served by ops/spmv.py), ``max_slots``,
    or when capacity exceeds ``max_cap`` — the kernels keep ~(cap, 128)
    one-hot and (cap, 128·passes) contraction buffers in VMEM (~16 MB),
    so edge-dense cells must fall back rather than fail at Mosaic
    compile time.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    m = rows.shape[0]
    if n_rows is None:
        n_rows = int(rows.max()) + 1 if m else 1
    if n_cols is None:
        n_cols = int(cols.max()) + 1 if m else 1
    if m and (rows.min() < 0 or rows.max() >= n_rows
              or cols.min() < 0 or cols.max() >= n_cols):
        raise ValueError("edge indices out of bounds for "
                         f"({n_rows}, {n_cols})")
    if vals is None:
        vals = np.ones((m,), np.float32)
    else:
        vals = np.asarray(vals, dtype=np.float32)

    g_s = max(1, -(-n_cols // SPAN))
    g_d = max(1, -(-n_rows // SPAN))
    n_cells = g_s * g_d
    cell = (cols // SPAN) * g_d + rows // SPAN
    cnt = np.bincount(cell, minlength=n_cells)
    if m == 0:
        cap = LANE
    else:
        pos = cnt[cnt > 0]
        cap_q = int(np.quantile(pos, capacity_quantile)) if pos.size else 0
        cap = max(LANE, -(-cap_q // LANE) * LANE)
    if cap > max_cap:
        return None
    if m and n_cells * cap > max_padding * m:
        return None
    if max_slots is not None and n_cells * cap > max_slots:
        return None

    order = np.argsort(cell, kind="stable")
    cell_s = cell[order]
    starts = np.zeros(n_cells + 1, np.int64)
    np.cumsum(cnt, out=starts[1:])
    slot = np.arange(m, dtype=np.int64) - starts[cell_s]
    in_main = slot < cap

    loc_src = np.zeros((n_cells, cap), np.int32)
    loc_dst = np.zeros((n_cells, cap), np.int32)
    val_t = np.zeros((n_cells, cap), np.float32)
    cm, sm = cell_s[in_main], slot[in_main]
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    loc_src[cm, sm] = (cols_s % SPAN)[in_main]
    loc_dst[cm, sm] = (rows_s % SPAN)[in_main]
    val_t[cm, sm] = vals_s[in_main]

    n_ov = int(np.count_nonzero(~in_main))
    if n_ov:
        ov_r, ov_c, ov_v = (rows_s[~in_main], cols_s[~in_main],
                            vals_s[~in_main])
        o = np.argsort(ov_r, kind="stable")
        ov = (jnp.asarray(ov_r[o], jnp.int32),
              jnp.asarray(ov_c[o], jnp.int32),
              jnp.asarray(ov_v[o], jnp.float32))
    else:
        ov = (None, None, None)

    shp = (g_s, g_d, cap // LANE, LANE)   # TPU tile layout (see kernels)
    return RoutedSpMVPlan(
        n_rows=n_rows, n_cols=n_cols, g_src=g_s, g_dst=g_d, cap=cap,
        loc_src=loc_src.reshape(shp), loc_dst=loc_dst.reshape(shp),
        val=val_t.reshape(shp),
        ov_rows=ov[0], ov_cols=ov[1], ov_vals=ov[2],
        padding_ratio=(n_cells * cap + n_ov) / max(m, 1))


# -- kernels -----------------------------------------------------------------


def _bf16_split(v, passes: int):
    """Residual bf16 decomposition: Σ parts ≈ v with error ~2^(-8·passes).
    The one-hot factor of each routed matmul is exact in bf16, so the
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


def _make_gather_kernel(passes: int):
    def _gather_kernel(loc_ref, val_ref, x_ref, w_ref):
        """Phase 1, one cell: w = x[src] · val via one-hot matmul.

        Slot tables arrive as (cap_r, 128) tiles (TPU block layout: the
        last two dims must tile (8, 128) or equal the array's); the
        one-hot is built 3D and contracted with a single dot, no
        in-kernel reshapes. x_ref block is this source group's
        (128, 128·passes) bf16 tile of residual splits; summing the
        split lanes reconstructs f32(x) to ~2^(-8·passes).
        """
        loc = loc_ref[0, 0]                            # (cap_r, 128)
        cap_r = loc.shape[0]
        ids3 = jax.lax.broadcasted_iota(
            jnp.int32, (cap_r, LANE, LANE), 2)
        oh_a = ((loc // LANE)[:, :, None] == ids3).astype(jnp.bfloat16)
        g = jax.lax.dot_general(
            oh_a, x_ref[0],
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (cap_r, 128, 128·passes)
        ghl = g[..., :LANE]
        for p in range(1, passes):
            ghl = ghl + g[..., p * LANE:(p + 1) * LANE]
        sel = jnp.where((loc % LANE)[:, :, None] == ids3, ghl, 0.0)
        w_ref[0, 0] = jnp.sum(sel, axis=2) * val_ref[0, 0]

    return _gather_kernel


def _make_scatter_kernel(g_src: int, passes: int):
    def _scatter_kernel(loc_ref, w_ref, y_ref, acc_ref):
        """Phase 3, one cell: acc += oh_cᵀ @ (oh_d ⊙ [w splits]) — a
        double contraction over both slot dims of the (cap_r, 128)
        tile."""
        gs = pl.program_id(1)
        loc = loc_ref[0, 0]                            # (cap_r, 128)
        w = w_ref[0, 0]
        cap_r = loc.shape[0]
        ids3 = jax.lax.broadcasted_iota(
            jnp.int32, (cap_r, LANE, LANE), 2)
        oh_c = ((loc // LANE)[:, :, None] == ids3).astype(jnp.bfloat16)
        mask = (loc % LANE)[:, :, None] == ids3
        rhs = jnp.concatenate(
            [jnp.where(mask, wp[:, :, None], 0.0)
             for wp in _bf16_split(w, passes)],
            axis=2).astype(jnp.bfloat16)       # lossless: bf16-grid values
        # Mosaic's matmul takes exactly one contracting dim per side:
        # collapse the (cap_r, 128) slot dims (contiguous merge) and
        # contract over dim 0 of both operands
        t = jax.lax.dot_general(
            oh_c.reshape(cap_r * LANE, LANE),
            rhs.reshape(cap_r * LANE, passes * LANE),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (128, 128·passes)

        @pl.when(gs == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        th = t[:, :LANE]
        for p in range(1, passes):
            th = th + t[:, p * LANE:(p + 1) * LANE]
        acc_ref[:] += th

        @pl.when(gs == g_src - 1)
        def _flush():
            y_ref[0] = acc_ref[:]

    return _scatter_kernel


@functools.lru_cache(maxsize=32)
def _routed_runner(g_s: int, g_d: int, cap: int, passes: int,
                   interpret: bool):
    """pallas_call pair bound to a plan's static shape. Tables are
    (G_s, G_d, cap//128, 128)."""
    cap_r = cap // LANE
    cell = (1, 1, cap_r, LANE)

    gather = pl.pallas_call(  # matlint: disable=ML009 legacy routed-SpMV reference kernel, unported to the registry this round (kept as a reference formulation)
        _make_gather_kernel(passes),
        name="matrel_spmv_routed_gather",
        grid=(g_s, g_d),
        in_specs=[
            pl.BlockSpec(cell, lambda gs, gd: (gs, gd, 0, 0)),
            pl.BlockSpec(cell, lambda gs, gd: (gs, gd, 0, 0)),
            pl.BlockSpec((1, LANE, passes * LANE), lambda gs, gd: (gs, 0, 0)),
        ],
        out_specs=pl.BlockSpec(cell, lambda gs, gd: (gs, gd, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g_s, g_d, cap_r, LANE),
                                       jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )
    # destination-major iteration; the (gs, gd) index maps read the
    # source-major tables directly — the shuffle is this index map
    scatter = pl.pallas_call(  # matlint: disable=ML009 legacy routed-SpMV reference kernel, unported to the registry this round (kept as a reference formulation)
        _make_scatter_kernel(g_s, passes),
        name="matrel_spmv_routed_scatter",
        grid=(g_d, g_s),
        in_specs=[
            pl.BlockSpec(cell, lambda gd, gs: (gs, gd, 0, 0)),
            pl.BlockSpec(cell, lambda gd, gs: (gs, gd, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, LANE, LANE), lambda gd, gs: (gd, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g_d, LANE, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((LANE, LANE), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    return gather, scatter


def routed_apply(plan_static, arrays, x: jax.Array, passes: int = 2,
                 interpret: bool = False) -> jax.Array:
    """Traceable body: y = A·x. ``plan_static`` is (n_rows, n_cols, g_s,
    g_d, cap); ``arrays`` is plan.arrays(). Safe inside jit/fori_loop.

    ``passes`` sets the bf16 residual-split depth on both value sides:
    2 → ~2^-16 relative error (default), 3 → f32-faithful (~2^-24).
    """
    n_rows, n_cols, g_s, g_d, cap = plan_static
    loc_src, loc_dst, val = arrays[:3]
    gather, scatter = _routed_runner(g_s, g_d, cap, passes, interpret)

    xf = x.astype(jnp.float32)
    xp = jnp.pad(xf, (0, g_s * SPAN - n_cols))
    x2 = jnp.concatenate(
        [p.reshape(g_s, LANE, LANE) for p in _bf16_split(xp, passes)],
        axis=-1).astype(jnp.bfloat16)          # lossless: bf16-grid values

    w = gather(loc_src, val, x2)
    y = scatter(loc_dst, w).reshape(-1)[:n_rows]
    if len(arrays) > 3:
        ov_r, ov_c, ov_v = arrays[3:]
        from matrel_tpu.ops.spmv import gather_1d
        w_ov = gather_1d(xf, ov_c) * ov_v
        y = y + jax.ops.segment_sum(w_ov, ov_r, num_segments=n_rows,
                                    indices_are_sorted=True)
    return y


_routed_jitted = jax.jit(routed_apply, static_argnums=(0, 3, 4))  # matlint: disable=ML010 pre-seam ops runner cache — the porting worklist (the ML009 legacy-kernel idiom)


def routed_spmv(plan: RoutedSpMVPlan, x: jax.Array, passes: int = 2,
                interpret: bool = False) -> jax.Array:
    """y = A·x (convenience wrapper; jit-cached per plan shape)."""
    static = (plan.n_rows, plan.n_cols, plan.g_src, plan.g_dst, plan.cap)
    return _routed_jitted(static, plan.arrays(), x, passes, interpret)
