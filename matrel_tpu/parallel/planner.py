"""Cost-based physical planning — the MatfastPlanner analogue
(SURVEY.md §2 "Physical planner", §3.2 "strategy choice per multiply").

The reference chooses BMM vs CPMM vs RMM per multiply from dimensions,
sparsity, and partitioning. Here the choice is made per matmul node before
tracing, from the same statistics, using a communication-cost model over the
mesh (comm bytes moved across ICI per strategy — the shuffle-bytes analogue).
The chosen strategy is recorded on the node (``attrs["strategy"]``) so plan
tests can assert it, mirroring the reference's Catalyst plan assertions.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.sharding import Mesh

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.ir.expr import MatExpr, elemwise, matmul, transpose
from matrel_tpu.parallel.strategies import (LONG_CONTRACTION, acc_itemsize,
                                            gram_reduce_bytes,
                                            gram_rider_room, gram_tiles,
                                            rmm_moves_under_dot, rmm_panels,
                                            rmm_transient_bytes)


def _bytes(shape: Tuple[int, int], density: float, itemsize: int = 4) -> float:
    return shape[0] * shape[1] * itemsize * max(density, 0.0)


def _to_2d_reshard(bytes_: float, layout: str, gx: int, gy: int) -> float:
    """Per-device ICI bytes to re-lay an operand into the canonical
    P(x, y) tiling that cpmm/summa kernels consume. Replicated operands
    already hold every tile (free); 1D-sharded ones gather along the
    perpendicular axis (the same closed form as the bmm reshard
    terms); canonical/"other" inputs are assumed in place. The gather
    rides ONE mesh axis — ``_to_2d_axis`` names it for the weighted
    model."""
    p = max(gx * gy, 1)
    if layout == "rep":
        return 0.0
    if layout == "row":
        return (bytes_ / p) * (1 - 1 / gy)
    if layout == "col":
        return (bytes_ / p) * (1 - 1 / gx)
    return 0.0


def _to_2d_axis(layout: str) -> str:
    """Mesh axis a ``_to_2d_reshard`` gather moves data over: a
    row-sharded operand gathers its missing columns along y, a
    col-sharded one along x. (For the free layouts the axis is
    irrelevant — the term is 0.)"""
    return "y" if layout == "row" else "x"


def _split_full_mesh(src_bytes: float, gx: int, gy: int,
                     wx: float, wy: float
                     ) -> Tuple[float, float, float]:
    """(weighted cost, x_bytes, y_bytes) of a FULL-MESH collective that
    replicates ``src_bytes`` from an even p-way shard — the bmm
    broadcast, the join all-gathers, and (scaled) the row↔col
    all-to-all. Flat bill: src·(p−1)/p per device.

    On a hierarchical mesh the collective decomposes into one stage per
    axis, and the stage ORDER decides which axis carries the big late
    stage: gathering along axis A first moves src·(gA−1)/p (shards
    still small), the second stage along B moves src·(gB−1)/gB (near
    the full array). The expensive axis therefore rides the FIRST
    stage — exactly what a topology-aware collective (XLA's
    hierarchical DCN all-gathers) does — so the weighted cost is the
    cheaper of the two orders. Both orders sum to the flat bill, so
    uniform weights reproduce it bit-identically (the fast path keeps
    the flat closed form's float arithmetic)."""
    p = gx * gy
    bx_yfirst = src_bytes * (gx - 1) / gx
    by_yfirst = src_bytes * (gy - 1) / p
    if wx == wy:
        # homogeneous mesh: the flat closed form, scaled (scale 1.0 is
        # the pre-topology model, bit for bit). Axis attribution uses
        # the y-first order — arbitrary but deterministic.
        return src_bytes * (p - 1) / p * wx, bx_yfirst, by_yfirst
    bx_xfirst = src_bytes * (gx - 1) / p
    by_xfirst = src_bytes * (gy - 1) / gy
    cost_yf = wx * bx_yfirst + wy * by_yfirst
    cost_xf = wx * bx_xfirst + wy * by_xfirst
    if cost_yf <= cost_xf:
        return cost_yf, bx_yfirst, by_yfirst
    return cost_xf, bx_xfirst, by_xfirst


def _comm_detail(strategy: str, n: int, k: int, m: int,
                 da: float, db: float, gx: int, gy: int,
                 itemsize: int = 4,
                 a_layout: str = "2d", b_layout: str = "2d",
                 alpha_bytes: float = 0.0,
                 weights: Tuple[float, float] = (1.0, 1.0)
                 ) -> Tuple[float, float, float]:
    """(weighted cost, x_bytes, y_bytes) — the one implementation
    behind :func:`comm_cost` (the scalar the planner ranks by) and
    :func:`comm_cost_axes` (the per-axis bytes obs records). Every
    collective leg is attributed to the mesh axis it moves data over
    and billed bytes × weights[axis]; α steps are weighted the same way
    (a ppermute hop over DCN costs its latency ratio too, and a
    full-mesh collective's latency rides its slowest stage). With
    uniform weights every branch reproduces the flat model's floats
    exactly — the per-term arithmetic and summation order are the
    pre-topology code's."""
    a_bytes = _bytes((n, k), da, itemsize)
    b_bytes = _bytes((k, m), db, itemsize)
    c_bytes = _bytes((n, m), 1.0, itemsize)
    p = gx * gy
    wx, wy = weights
    ax = {"x": 0.0, "y": 0.0}

    def leg(bytes_: float, axis: str) -> Tuple[float, float]:
        """(weighted cost, α-step weight) of a single-axis leg."""
        w = wx if axis == "x" else wy
        ax[axis] += bytes_
        return bytes_ * w, w

    def bcast(src_bytes: float) -> Tuple[float, float]:
        """Full-mesh replication of ``src_bytes``; its latency rides
        the slower of its two stages."""
        cost, bx, by = _split_full_mesh(src_bytes, gx, gy, wx, wy)
        ax["x"] += bx
        ax["y"] += by
        return cost, max(wx, wy)

    FREE = (0.0, 0.0)

    def total(*terms, extra_steps_w: float = 0.0):
        steps_w = sum(w for t, w in terms if t > 0.0) + extra_steps_w
        return sum(t for t, _w in terms) + alpha_bytes * steps_w

    def to2d(bytes_: float, layout: str) -> Tuple[float, float]:
        amt = _to_2d_reshard(bytes_, layout, gx, gy)
        return leg(amt, _to_2d_axis(layout)) if amt > 0.0 else FREE

    if strategy == "bmm_right":
        # replicate B everywhere (all-gather to every device) + reshard A
        # to row-sharding over all devices (free when already row-sharded
        # — and when replicated: slicing holds-everything down to a row
        # shard moves nothing, review r5). The A-reshard gathers along y.
        t_bcast = FREE if b_layout == "rep" else bcast(b_bytes)
        t_resh = (FREE if a_layout in ("row", "rep")
                  else leg((a_bytes / p) * (1 - 1 / gy), "y"))
        return total(t_bcast, t_resh), ax["x"], ax["y"]
    if strategy == "bmm_left":
        t_bcast = FREE if a_layout == "rep" else bcast(a_bytes)
        t_resh = (FREE if b_layout in ("col", "rep")
                  else leg((b_bytes / p) * (1 - 1 / gx), "x"))
        return total(t_bcast, t_resh), ax["x"], ax["y"]
    if strategy == "cpmm":
        # A consumed P(x, y) in place (re-laid if 1D-sharded); B resharded
        # to P(y, None): each device gathers b_bytes/gy of B rows
        # replicated along x (an x-axis gather, free when B is already
        # replicated), then a reduce-scatter of partial C over y —
        # the collective that rides the slow axis of a (slices, chips)
        # mesh. rs_c > 0 exactly when the reduce-scatter exists (gy > 1
        # — c_bytes is never 0), so the nonzero-term count in total()
        # already charges its α step.
        t_a = to2d(a_bytes, a_layout)
        t_b = (FREE if b_layout == "rep"
               else leg((b_bytes / gy) * (gx - 1) / gx, "x"))
        t_c = leg((c_bytes / gx) * (gy - 1) / gy, "y")
        return total(t_a, t_b, t_c), ax["x"], ax["y"]
    if strategy in ("rmm", "xla"):
        # all-gather A along y (each device ends with n/gx × k) and B
        # along x; replicated operands already hold their gather target.
        # xla is unknown until the SPMD partitioner runs; modelled as RMM
        # (its usual pick).
        t_a = (FREE if a_layout == "rep"
               else leg((a_bytes / gx) * (gy - 1) / gy, "y"))
        t_b = (FREE if b_layout == "rep"
               else leg((b_bytes / gy) * (gx - 1) / gx, "x"))
        return total(t_a, t_b), ax["x"], ax["y"]
    if strategy == "summa":
        # inputs re-laid to the P(x, y) tiles the ring consumes, then
        # Cannon: g−1 execution steps, each a ppermute of one A tile AND
        # one B tile per device — the stepped strategy the α term exists
        # for (VERDICT r5 "Missing #4"). A tiles shift along y, B tiles
        # along x, so each operand's ring traffic (and its g−1 hop
        # latencies) is billed on its own axis.
        g = max(gx, gy)
        ring_a = (a_bytes / p) * (g - 1)
        ring_b = (b_bytes / p) * (g - 1)
        ax["y"] += ring_a
        ax["x"] += ring_b
        if wx == wy:
            # flat fast path: the pre-topology float arithmetic
            ring = (a_bytes / p + b_bytes / p) * (g - 1) * wx
        else:
            ring = ring_a * wy + ring_b * wx
        cost = ring + total(to2d(a_bytes, a_layout),
                            to2d(b_bytes, b_layout),
                            extra_steps_w=(g - 1) * wy + (g - 1) * wx)
        return cost, ax["x"], ax["y"]
    if strategy == OWN_ROWS:
        # both operands read where they lie (chosen only where they lie
        # cut over all devices along the contraction: own_rows_operands);
        # one all-reduce of the partial C over both axes, 2 (g - 1) / g
        # of it a device and axis
        t_y = leg(2.0 * c_bytes * (gy - 1) / gy, "y")
        t_x = leg(2.0 * c_bytes * (gx - 1) / gx, "x")
        return total(t_y, t_x), ax["x"], ax["y"]
    if strategy == "spgemm":
        # S×S tile-intersection (ops/spgemm.py): both tile stacks are
        # replicated (the broadcast side of the SpMM plan family), the
        # pair compute is device-local and the canonical-output
        # constraint slices a replicated result — no ICI, no steps.
        # nnz-proportionality lives in the FLOP side of the model
        # (matmul_cost's density credit); this prices the comm bill.
        return 0.0, 0.0, 0.0
    raise ValueError(f"unknown strategy {strategy}")


def comm_cost(strategy: str, n: int, k: int, m: int,
              da: float, db: float, gx: int, gy: int,
              itemsize: int = 4,
              a_layout: str = "2d", b_layout: str = "2d",
              alpha_bytes: float = 0.0,
              weights: Tuple[float, float] = (1.0, 1.0),
              coeff: Optional[dict] = None) -> float:
    """Estimated per-device interconnect cost of each strategy, in
    weighted byte-equivalents — or in calibrated MILLISECONDS when a
    ``coeff`` row is passed (see below).

    ``a_layout``/``b_layout`` describe how the operand already lives on the
    mesh ("2d", "row", "col", "rep", "other"): co-partitioned inputs make
    their reshard terms free — the analogue of the reference's
    partitioner-aware planning that skips shuffles for co-partitioned RDDs
    (SURVEY.md §2 "Partitioners", "co-partitioning"). EVERY strategy
    branch reads the layouts (round 5 — previously only the bmm branches
    did): a replicated operand costs nothing to gather for rmm/cpmm
    either, and a 1D-sharded operand pays its way back to the 2D tiling
    cpmm/summa consume. Costs count resharding all-gathers plus
    execution-time collectives; the closed forms recast the reference's
    shuffle-size formulas for a gx × gy mesh.

    ``alpha_bytes`` is the per-collective-STEP latency charge in
    byte-equivalents (the α of an α-β model, VERDICT r5 "Missing #4"):
    each nonzero reshard/gather term counts one step, cpmm's
    reduce-scatter one, and SUMMA's Cannon ring 2·(g−1) ppermute steps
    — so small latency-bound multiplies stop ranking purely by bytes.
    Default 0.0 keeps the pure-β closed forms the chain DP's native
    mirror is equivalence-fuzzed against; the PLANNER passes
    config.comm_alpha_bytes (choose_strategy_ex).

    ``weights`` are the per-mesh-axis inverse-bandwidth weights
    (core/mesh.MeshTopology): each collective leg is billed on the axis
    it actually moves data over, so on a hierarchical ICI/DCN mesh a
    slow-axis reduce-scatter is priced like the DCN traffic it is. The
    default (1.0, 1.0) reproduces the flat byte model bit-identically
    (same per-term arithmetic, same summation order); α steps are
    weighted the same way.

    ``coeff`` (a drift-calibrated row from parallel/coeffs.py — the
    ML018 seam) converts the weighted bill into measured milliseconds:
    the row's ms/est-MiB ratio was calibrated against exactly this
    quantity (the drift samples' ``est_bytes``), so the scale applies
    to what it was measured on. None (the default) keeps the raw
    byte-equivalents every existing caller ranks by — bit-identical.
    """
    cost = _comm_detail(strategy, n, k, m, da, db, gx, gy, itemsize,
                        a_layout, b_layout, alpha_bytes, weights)[0]
    if coeff is not None:
        from matrel_tpu.parallel import coeffs as coeffs_lib
        cm = coeff.get("ms_per_mib")
        if cm is None:
            cm = coeffs_lib.ANALYTIC_MS_PER_MIB
        return float(cm) * (cost / (1 << 20))
    return cost


def comm_cost_axes(strategy: str, n: int, k: int, m: int,
                   da: float, db: float, gx: int, gy: int,
                   itemsize: int = 4,
                   a_layout: str = "2d", b_layout: str = "2d",
                   weights: Tuple[float, float] = (1.0, 1.0),
                   coeff: Optional[dict] = None
                   ) -> Tuple[float, float]:
    """Raw (unweighted) per-device bytes a strategy moves over each
    mesh axis, as (x_bytes, y_bytes) — the per-axis decomposition of
    :func:`comm_cost`'s bill, recorded by ``matmul_decisions`` so
    slow-axis traffic is auditable per decision. ``weights`` only
    influence which stage order a full-mesh collective's bytes are
    attributed under (the split the weighted cost actually uses).
    ``coeff`` (the parallel/coeffs.py seam row, same contract as
    :func:`comm_cost`) scales both axes into calibrated milliseconds;
    None keeps raw bytes — bit-identical."""
    _, bx, by = _comm_detail(strategy, n, k, m, da, db, gx, gy,
                             itemsize, a_layout, b_layout, 0.0, weights)
    if coeff is not None:
        from matrel_tpu.parallel import coeffs as coeffs_lib
        cm = coeff.get("ms_per_mib")
        if cm is None:
            cm = coeffs_lib.ANALYTIC_MS_PER_MIB
        scale = float(cm) / (1 << 20)
        return bx * scale, by * scale
    return bx, by


#: The stamp of a product that reads both operands where they lie, cut
#: over ALL devices along the contraction, and all-reduces the devices'
#: partial products (strategies.over_own_rows): upstream's cross-product
#: multiply over a RowPartitioner's partitions. No byte-model candidate:
#: chosen from how the operands lie (:func:`long_in_place`), by
#: ``strategy_source`` "layout".
OWN_ROWS = "cpmm_rows"


def in_place_strategy(mesh: Mesh) -> str:
    """The stamp of the product that multiplies what each device holds,
    as it lies: the plain local dot on one device, :data:`OWN_ROWS` on
    a mesh."""
    return "xla" if mesh.size == 1 else OWN_ROWS


def _norm_axes(e):
    """Normalise one PartitionSpec entry: 1-tuples to their element,
    multi-axis tuples kept as tuples."""
    if isinstance(e, tuple):
        if len(e) == 0:
            return None
        if len(e) == 1:
            return e[0]
        return tuple(e)
    return e


def _layout_of(node: MatExpr, mesh: Mesh) -> str:
    """How a LEAF operand already lives on the mesh, from its real
    PartitionSpec. Interior nodes go through :func:`infer_layout`."""
    if node.kind != "leaf":
        return "2d"
    spec = node.attrs["matrix"].spec
    x, y = mesh.axis_names
    row = _norm_axes(spec[0] if len(spec) > 0 else None)
    col = _norm_axes(spec[1] if len(spec) > 1 else None)
    if row is None and col is None:
        return "rep"
    flat = ((x, y), (y, x))
    if col is None and row in flat:
        return "row"
    if row is None and col in flat:
        return "col"
    # "2d" means THE CANONICAL spec for this shape on this mesh — the
    # layout autotune probes are measured at (BlockMatrix.random uses
    # canonical specs). On a (2,4) grid that's P(x, y) for matrices and
    # P(x, None) for column vectors; on a 1×N grid it's P(None, y).
    # Anything else — e.g. P(x, None) on a matrix whose canonical spec
    # is P(x, y) — is a real, non-canonical placement: "other"
    # (review r5: reading partials as "2d" let the measured winner be
    # applied to a layout it was never measured on).
    from matrel_tpu.core import padding
    cspec = padding.canonical_spec(padding.padded_shape(node.shape, mesh),
                                   mesh)
    crow = _norm_axes(cspec[0] if len(cspec) > 0 else None)
    ccol = _norm_axes(cspec[1] if len(cspec) > 1 else None)
    return "2d" if (row, col) == (crow, ccol) else "other"


#: Vocabulary of the planner's layout model. "2d" = the canonical spec
#: for the shape on this mesh (what autotune probes measure);
#: "row"/"col" = 1D-sharded over ALL devices on that matrix axis;
#: "rep" = fully replicated; "other" = a real placement matching none
#: of these — costed like "2d" (no credit) but gated OUT of the
#: measured-winner consult.
LAYOUTS = ("2d", "row", "col", "rep", "other")


def infer_layout(node: MatExpr, mesh: Mesh,
                 memo: Optional[dict] = None,
                 config: Optional[MatrelConfig] = None) -> str:
    """Best-effort output layout of ANY expression node's lowering.

    Bottom-up propagation mirroring the executor's actual sharding
    behaviour, exactly the way :func:`infer_dtype` mirrors its dtype
    behaviour (VERDICT r4 "what's missing" #2: the old leaf-only
    ``_layout_of`` hardcoded "2d" for every interior node, so the
    co-partitioning credit — the analogue of the reference's
    partitioner-aware planning that skips shuffles for co-partitioned
    RDDs, SURVEY.md §2 "Partitioners" — never fired for the interior of
    a chain or for a join feeding a matmul):

    - leaves: the real PartitionSpec (``_layout_of``);
    - matmul: by the stamped strategy's out_specs — bmm_right emits
      P((x,y), None) = "row", bmm_left "col"; cpmm/rmm/summa emit
      P(x, y) and the xla fallback constrains to it = "2d"
      (strategies.py out_specs). A matmul dispatching a narrow COO
      SpMV emits replicated results = "rep" — but ONLY where the
      lowering actually pins that: the multi-device compact Pallas
      path's out_specs=P() (executor._coo_compact_sharded) or a
      single-device mesh; the multi-device expanded XLA path leaves
      the sharding to GSPMD and reads "2d" (review r5). An
      UN-annotated matmul reads "2d" — annotate_strategies stamps
      children before parents, so interior nodes are always stamped
      by the time a parent asks;
    - transpose swaps row/col; entrywise ops (scalar, selects,
      join_index) preserve their operand's layout; elemwise preserves
      a layout its operands agree on (XLA aligns the other operand);
    - row/col joins: by the stamped scheme — "align" emits the join
      axis's 1D sharding (executor._join_axis constraint); replicate-
      left/right emit the KEPT side's layout;
    - agg: "all"/"diag" produce a replicated 1x1; row-agg of a
      row-sharded operand stays row-sharded (resp. col);
    - solve: "rep" where both operands are replicated on a mesh (every
      device then runs the local solve on its own copy, executor._solve);
    - everything else (vec's reshape, other solve/inverse local solves,
      materialised value-joins, sparse/coo leaves used densified):
      "2d" — the conservative status quo; free-ness is only ever
      claimed where the lowering pins it.

    Memoised per uid and threaded through annotate_strategies like the
    dtype memo, so planning stays O(nodes).
    """
    if memo is None:
        memo = {}
    cfg = config or default_config()

    def walk(n: MatExpr) -> str:
        if n.uid in memo:
            return memo[n.uid]
        memo[n.uid] = l = _infer(n)
        return l

    def _infer(n: MatExpr) -> str:
        k = n.kind
        if k == "leaf":
            return _layout_of(n, mesh)
        if k == "matmul":
            # the lowering IGNORES the stamped strategy for sparse_leaf
            # matmuls (the SpMM path) and for wide/refused COO matmuls
            # (densify path runs hard-coded "xla") — consulting
            # STRATEGY_OUT_LAYOUT there claimed a "row"/"col" the
            # executor never produces, an unearned free-consume credit
            # (advisor r5 medium). Free-ness is only claimed where the
            # lowering pins it: both off-strategy dispatches read "2d".
            # Branch ORDER mirrors Lowerer._matmul exactly (review r6):
            # spgemm, then coo_leaf on EITHER side, then sparse_leaf —
            # a mixed coo×sparse matmul takes the COO SpMV path (the
            # sparse operand densifies as its dense input), so reading
            # the sparse-first rule there claimed "2d" where the
            # compact path pins a replicated output.
            if _spgemm_matmul(n, cfg):
                return "2d"              # SpGEMM scatters canonically
            if any(c.kind == "coo_leaf" for c in n.children):
                if not _coo_narrow_matmul(n):
                    return "2d"          # densify path: hard-coded xla
                from matrel_tpu.config import pallas_enabled
                # "rep" only where the lowering PINS it: single device,
                # or the compact sharded path (out_specs=P()) is
                # guaranteed. With autotune on, a measured "expanded"
                # winner can reroute the dispatch onto the XLA path at
                # compile time (executor._coo_spmv_stack), whose output
                # sharding is GSPMD-decided — no claim then (review r5).
                if mesh.size == 1 or (pallas_enabled(cfg)
                                      and not cfg.autotune):
                    return "rep"
                return "2d"
            if any(c.kind == "sparse_leaf" for c in n.children):
                return "2d"
            return STRATEGY_OUT_LAYOUT.get(n.attrs.get("strategy"),
                                           "2d")
        if k == "transpose":
            c = walk(n.children[0])
            return {"row": "col", "col": "row"}.get(c, c)
        if k in ("scalar", "select_value", "select_index",
                 "select_block"):
            return walk(n.children[0])
        if k == "rank1":
            return walk(n.children[0])
        if k in ("elemwise", "join_index"):
            la, lb = walk(n.children[0]), walk(n.children[1])
            # broadcast: the full-shaped operand's layout carries
            if k == "elemwise" and n.children[0].shape != n.shape:
                return lb
            if k == "elemwise" and n.children[1].shape != n.shape:
                return la
            if la == lb:
                return la
            # one replicated operand: XLA computes on the other's layout
            if la == "rep":
                return lb
            if lb == "rep":
                return la
            return "2d"
        if k == "agg":
            axis = n.attrs["axis"]
            lc = walk(n.children[0])
            if axis in ("all", "diag"):
                return "rep"
            if axis == "row" and lc == "row":
                return "row"
            if axis == "col" and lc == "col":
                return "col"
            return "2d"
        if k in ("join_rows", "join_cols"):
            rep = n.attrs.get("replicate")
            if rep in ("align", "left", "right"):
                # ONE source of truth for scheme -> output layout,
                # shared with the tiebreak (review r5)
                return _scheme_out_layout(rep, n, walk(n.children[0]),
                                          walk(n.children[1]))
            return "2d"
        if k == "solve" and mesh.size > 1 and all(
                walk(c) == "rep" for c in n.children):
            # every device's own factorisation of what every device
            # holds (executor._solve pins it)
            return "rep"
        return "2d"

    return walk(node)


def _spgemm_matmul(n: MatExpr, config=None) -> bool:
    """Will this matmul dispatch the S×S tile-intersection SpGEMM?
    Consults executor._spgemm_dispatch — the single source of truth
    shared with the lowering (the _coo_dispatch_plan idiom), so the
    estimator, the threshold compare and any future refusal logic can
    never drift from what actually executes. Lazily imported to keep
    the executor→planner import direction."""
    l, r = n.children
    if (l.kind in ("sparse_leaf", "coo_leaf")
            and r.kind in ("sparse_leaf", "coo_leaf")):
        from matrel_tpu import executor as _exec
        return _exec._spgemm_dispatch(n, config)
    return False


def _coo_narrow_matmul(n: MatExpr) -> bool:
    """Will this matmul dispatch the narrow COO SpMV path (whose sharded
    compact executor emits REPLICATED results, out_specs=P())? Consults
    executor._coo_dispatch_plan itself — the single source of truth —
    so the plan-REFUSAL fallback (build_spmv_plan returning None on
    pathological padding, which densifies onto the 2d XLA path) is
    honoured too, not just the width threshold (review r5). The plan it
    builds is memoised on the matrix and needed at lowering anyway.
    Lazily imported to keep the executor→planner import direction."""
    l, r = n.children
    if l.kind == "coo_leaf" or r.kind == "coo_leaf":
        from matrel_tpu import executor as _exec
        return _exec._coo_dispatch_plan(n) is not None
    return False


def fused_sampled(node: MatExpr, parent: Optional[MatExpr], mesh: Mesh,
                  config: Optional[MatrelConfig] = None) -> bool:
    """A ``sampled`` node that is no array: an operand of a product
    that answers it fused (executor._sampled_dispatch_plan, the single
    source of truth), its values made and multiplied an entry and a
    panel at a time. Anywhere else it lowers as the dense array it
    stands for and is counted as one."""
    if (node.kind != "sampled" or parent is None
            or parent.kind != "matmul"):
        return False
    from matrel_tpu import executor as _exec
    return _exec._sampled_dispatch_plan(parent, mesh, config) is not None


def sampled_dense_bytes(node: MatExpr, mesh: Mesh) -> float:
    """What a ``sampled`` node that lowers as an array keeps on one
    device beside its own value: the leaf's dense float32 copy and the
    dense product it is sampled from, both of its own padded shape."""
    from matrel_tpu.core import padding
    pn, pm = padding.padded_shape(node.shape, mesh)
    return 2 * 4.0 * pn * pm / max(mesh.size, 1)


def semiring_product(node: MatExpr, mesh: Mesh,
                     config: Optional[MatrelConfig] = None) -> dict:
    """How a ``semiring`` node will run, and what that keeps on one
    device beside the column it reads and the column it makes
    (executor._semiring_dispatch, the single source of truth):
    ``chosen`` "coo_reduce" (the matrix's forward SpMV plan through the
    chunk grid's reduction kernel, with the plan's ``layout`` and
    ``panels``) or "coo_reduce_xla" (XLA's segment reduction over the
    sorted entries), and ``bytes`` as core.coo.semiring_facts reckons
    them. Either way from the leaf's entries alone: the (n × m) join
    the node was written as is priced nowhere."""
    from matrel_tpu import executor as _exec
    from matrel_tpu.core import coo as coo_lib
    m, plan = _exec._semiring_dispatch(node, mesh, config)
    facts = coo_lib.semiring_facts(m, plan, node.attrs["reduce"])
    out = {"chosen": "coo_reduce" if plan is not None else "coo_reduce_xla",
           "bytes": float(facts["hbm_plan_bytes"])}
    if plan is not None:
        out.update(layout=facts["layout"], panels=(facts["panels"], 1))
    return out


def coo_product(node: MatExpr, mesh: Mesh,
                config: Optional[MatrelConfig] = None) -> Optional[dict]:
    """How a matmul with a coo_leaf operand will run, and what that
    keeps on one device beside the dense operand and the output — None
    for any other node. A product that answers a ``sampled`` operand
    fused (:func:`fused_sampled`): ``chosen`` "sampled_spmm", the plan's
    ``layout`` and ``panels`` and ``bytes`` as core.coo.sampled_facts
    reckons them (the tables, a panel's gathered rows, the slab and one
    panel of its quotient: neither the dense product nor the sampled
    values whole). Through the matrix's SpMV plan
    (executor._coo_dispatch_plan, the single source of truth):
    ``chosen`` "coo_spmm", the plan's ``layout``, its ``panels`` (of
    table rows, of sources) and ``bytes``, the compact tables and one
    panel's temporaries of the k-wide product (core.coo.plan_facts). Or,
    where the dense side is wider than the tables multiply or the plan
    was refused, by DENSIFYING the leaf: ``chosen`` "densify", ``bytes``
    the dense float32 copy on the mesh's padded shape, ``why``."""
    l, r = node.children
    from matrel_tpu import executor as _exec
    from matrel_tpu.core import coo as coo_lib, padding
    if l.kind == "sampled" or r.kind == "sampled":
        plan = _exec._sampled_dispatch_plan(node, mesh, config)
        if plan is None:
            return None
        smp = l if l.kind == "sampled" else r
        # the shared gather is the lowering's to find; without it a
        # panel holds one gathered row a slot more
        facts = coo_lib.sampled_facts(
            plan, smp.children[0].attrs["matrix"].nnz, shared=False)
        return {"chosen": "sampled_spmm", "layout": facts["layout"],
                "panels": (facts["panels"], facts["source_panels"]),
                "bytes": float(facts["hbm_plan_bytes"])}
    if l.kind != "coo_leaf" and r.kind != "coo_leaf":
        return None
    flipped = l.kind != "coo_leaf"
    leaf = r if flipped else l
    plan = _exec._coo_dispatch_plan(node)
    per_device = max(mesh.size, 1)
    if plan is not None:
        facts = coo_lib.plan_facts(plan, leaf.attrs["matrix"].nnz)
        return {"chosen": "coo_spmm", "layout": facts["layout"],
                "panels": (facts["panels"], facts["source_panels"]),
                "bytes": facts["plan_bytes"] / per_device}
    k = l.shape[0] if flipped else r.shape[1]
    why = (f"its dense side has {k} columns, more than the "
           f"{_exec.COO_NARROW_MAX} the SpMV tables multiply"
           if k > _exec.COO_NARROW_MAX else
           "build_spmv_plan refused its layout (padding)")
    pn, pm = padding.padded_shape(leaf.shape, mesh)
    return {"chosen": "densify", "why": why,
            "bytes": 4.0 * pn * pm / per_device}


def infer_dtype(node: MatExpr, config: Optional[MatrelConfig] = None,
                memo: Optional[dict] = None):
    """Statically-known output dtype of ANY expression node, or None.

    Bottom-up propagation mirroring the Lowerer's actual dtype
    behaviour (VERDICT r3 #3: the old leaf-only walk meant autotune's
    measured table was consulted only for leaf×leaf multiplies — the
    interior products of a reordered chain, the recurring shapes the
    closed loop exists for, always fell back to the byte model):

    - leaves: the matrix payload dtype;
    - transpose/scalar/agg/vec/select_*: dtype-preserving (the executor
      casts aggregates and scalar ops back to the operand dtype);
    - matmul: accumulates in f32 when bf16 is involved, then casts back
      to the common input dtype under ``config.keep_input_dtype``
      (executor.py matmul cast) — so bf16·bf16 is bf16 with the default
      config, f32 otherwise;
    - elemwise/rank1/join_value: jnp promotion of the operands (the
      value-join lowering casts its streamed result to exactly this);
    - solve/inverse: computed in f32, cast back to the input dtype
      under keep_input_dtype (solve: only when both operands agree);
    - join_rows/join_cols with a CALLABLE merge, and anything else
      unknown: None (conservative — the autotune consult is skipped).

    Results are memoised per uid: expressions are DAGs and chains
    re-walk shared operands. Pass a shared ``memo`` dict to amortise the
    walk across calls (annotate_strategies threads one through the whole
    pass, making planning O(nodes) instead of O(nodes^2) for deep
    chains — review r4).
    """
    cfg = config or default_config()
    import jax.numpy as jnp
    import numpy as np
    if memo is None:
        memo = {}

    def walk(n: MatExpr):
        if n.uid in memo:
            return memo[n.uid]
        memo[n.uid] = d = _infer(n)
        return d

    def _promote(*ds):
        if any(d is None for d in ds):
            return None
        out = ds[0]
        for d in ds[1:]:
            out = jnp.promote_types(out, d)
        return out

    def _infer(n: MatExpr):
        k = n.kind
        if k in ("leaf", "sparse_leaf", "coo_leaf"):
            m = n.attrs["matrix"]
            if k == "coo_leaf":
                # COOMatrix carries no dtype attribute; its payloads
                # are f32 by construction (core/coo.py from_edges) and
                # its SpMV paths accumulate f32. CHECKED here with an
                # explicit raise (VERDICT r4 "what's weak" #4; not an
                # assert — must survive python -O, review r5) so a
                # future dtype-bearing COOMatrix fails loudly instead
                # of silently keying the wrong table row.
                vals = getattr(m, "vals", None)
                if vals is not None and np.dtype(vals.dtype) != np.dtype(
                        "float32"):
                    raise TypeError(
                        f"COOMatrix payload dtype {vals.dtype} != "
                        "float32: infer_dtype's COO rule (and the SpMV "
                        "f32 accumulation it mirrors) no longer holds "
                        "— teach both paths the new dtype together")
            return getattr(m, "dtype", np.dtype("float32"))
        if k in ("transpose", "scalar", "agg", "vec", "select_value",
                 "select_index", "select_block"):
            return walk(n.children[0])
        if k == "matmul":
            # a stamped integer tier keeps its int32 accumulator as the
            # RESULT dtype (the exact integer algebra flows to
            # consumers — aggregates, further int-tier products —
            # without a lossy f32 round-trip); bf16 tiers accumulate
            # f32 and store the f32 input dtype, same as the default
            # lowering, so only the int tiers change the answer here
            if n.attrs.get("precision_tier") in ("int32", "int8"):
                return np.dtype("int32")
            da, db = walk(n.children[0]), walk(n.children[1])
            if da is None or db is None:
                return None
            if cfg.keep_input_dtype and da == db:
                return da
            if "bfloat16" in (np.dtype(da).name, np.dtype(db).name):
                return np.dtype("float32")
            return _promote(da, db)
        if k in ("elemwise", "rank1", "join_value", "sampled", "semiring",
                 "mmchain"):
            return _promote(*(walk(c) for c in n.children))
        if k == "inverse":
            da = walk(n.children[0])
            if da is None:
                return None
            return da if cfg.keep_input_dtype else np.dtype("float32")
        if k == "solve":
            da, db = walk(n.children[0]), walk(n.children[1])
            if da is None or db is None:
                return None
            if cfg.keep_input_dtype and da == db:
                return da
            return np.dtype("float32")
        if k in ("join_rows", "join_cols", "join_index"):
            # structured merges promote; user callables may not
            if n.attrs.get("merge_kind") is not None:
                return _promote(*(walk(c) for c in n.children))
            return None
        return None

    return walk(node)


# -- precision tiers (round 8: per-query accuracy SLAs) --------------------
#
# Precision is a first-class planner dimension (ROADMAP open item 3;
# "Large Scale Distributed Linear Algebra With TPUs", arXiv:2112.09017):
# the MXU's native numeric format is bf16, and f32-class accuracy is
# RECOVERABLE from bf16 passes by splitting each f32 operand into bf16
# slices (hi = bf16(x), lo = bf16(x − hi)) and accumulating the
# significant cross-products in f32 — keeping hi·hi + hi·lo + lo·hi
# (3 MXU passes) drops only the ~2^-16-relative lo·lo term. Integer-
# shaped workloads (triangle counts, PageRank iteration counts, boolean
# semiring joins) are EXACT on the int paths. The chooser below picks
# the cheapest tier that satisfies the query's SLA; the lowering
# (executor._matmul → ops/precision.py) emits the multi-pass
# decomposition; the vocabulary/cost tables here are the one source of
# truth for the cost model, matmul_decisions, and MV108.

#: Tier vocabulary. "f32" = today's single full-precision product
#: (config.matmul_precision, i.e. XLA's 6-pass bf16 emulation on TPU);
#: "bf16x1" = one native bf16 MXU pass; "bf16x3" = the 3-pass
#: split-summation correction (~f32 accuracy); "int32"/"int8" =
#: integer-exact MXU paths (int32 accumulate).
PRECISION_TIERS = ("f32", "bf16x1", "bf16x3", "int32", "int8")

#: MXU passes a tier's lowering emits per matmul — the est pass count
#: matmul_decisions records. f32 counts XLA's HIGHEST-precision 6-pass
#: bf16 emulation of an f32 dot on the MXU (the TPU cost model the
#: planner targets; on CPU backends f32 is one native pass and the
#: numbers are a modelling convention, not a measurement).
TIER_PASSES = {"f32": 6, "bf16x1": 1, "bf16x3": 3, "int32": 1,
               "int8": 1}

#: Relative MXU time per MAC (f32-single-pass-rate units): bf16 passes
#: run at 2× the f32-class rate, so time = passes / 2 for the bf16
#: tiers; int8 runs at 4× (the int8 MXU path); int32 is conservatively
#: f32-class. This is the "3× the MACs at 2× the MXU rate" billing —
#: the model prices real pass counts, never a free speedup.
TIER_COMPUTE_UNITS = {"f32": 3.0, "bf16x1": 0.5, "bf16x3": 1.5,
                      "int32": 1.0, "int8": 0.25}

#: HBM bytes per operand element a tier's lowering reads: bf16x1
#: streams half-width operands; bf16x3 keeps BOTH bf16 slices resident
#: (hi + lo = 4 B — the split halves the per-pass bytes, not the
#: total); int8 quarters them.
TIER_ITEMSIZE = {"f32": 4, "bf16x1": 2, "bf16x3": 4, "int32": 4,
                 "int8": 1}

#: Documented per-MAC relative error bound of each tier (docs/
#: PRECISION.md): max-abs error of an (n,k)x(k,m) product is bounded by
#: TIER_EPS[tier] · k · max|A| · max|B|. The int tiers are EXACT for
#: integer-valued operands whose products/sums fit int32 (and, for the
#: f32-stored result, 2^24).
TIER_EPS = {"f32": 2.0 ** -20, "bf16x1": 2.0 ** -8,
            "bf16x3": 2.0 ** -15, "int32": 0.0, "int8": 0.0}

#: Explicit-dtype SLA spellings → the tier they pin.
_DTYPE_SLA_TIER = {"float32": "f32", "bfloat16": "bf16x1",
                   "bf16x3": "bf16x3", "int32": "int32", "int8": "int8"}


def tier_matmul_cost(tier: str, n: int, k: int, m: int,
                     da: float = 1.0, db: float = 1.0) -> float:
    """Estimated execution cost of one (n×k)·(k×m) multiply at a
    precision tier, in f32-FLOP-equivalents: the REAL per-pass MAC work
    (sparsity-credited, scaled by the tier's relative MXU time) plus
    the per-tier HBM operand/output traffic in FLOP-equivalents. This
    is the quantity the SLA chooser ranks tiers by — a 3-pass bf16
    multiply is billed 1.5× the single-pass f32-rate MACs at half the
    per-pass operand bytes, not assumed free."""
    from matrel_tpu.ir import stats
    compute = (stats.matmul_cost(n, k, m, da, db)
               * TIER_COMPUTE_UNITS[tier])
    isz = TIER_ITEMSIZE[tier]
    hbm = (n * k * max(da, 0.0) + k * m * max(db, 0.0)) * isz \
        + n * m * 4.0                     # result stored full-width
    return compute + stats.HBM_FLOPS_PER_BYTE * hbm


def tier_error_bound(tier: str, k: int, amax: float = 1.0,
                     bmax: float = 1.0) -> float:
    """Documented max-abs error bound of a k-deep product at a tier
    (TIER_EPS closed form) — shared by tests/test_precision.py and the
    soak battery so the asserted bound IS the documented one."""
    return TIER_EPS[tier] * float(k) * float(amax) * float(bmax)


def sla_allowed_tiers(sla: str, integral: bool,
                      config: Optional[MatrelConfig] = None) -> tuple:
    """Tiers admissible under an SLA for a dense float-f32 matmul whose
    operands are (``integral``=True) provably integer-valued. The SLA
    is an accuracy FLOOR — every allowed tier meets or beats it:

      exact  f32 always; int tiers when integral (integer-exact).
      high   + bf16x3 (~f32 accuracy at bf16 MXU rate).
      fast   + bf16x1 (documented bf16 bound).
      <dtype> exactly the pinned tier (bypasses the enable gates:
              an explicit ask is an ask).

    Tier enable flags (config.precision_enable_bf16/_int) drop their
    families from the NAMED levels; "default" returns () — nothing is
    ever stamped, the pre-tier lowering runs bit-identically.
    """
    cfg = config or default_config()
    if sla == "default":
        return ()
    pinned = _DTYPE_SLA_TIER.get(sla)
    if pinned is not None:
        return (pinned,)
    tiers = ["f32"]
    if cfg.precision_enable_int and integral:
        tiers.append("int32")
    if cfg.precision_enable_bf16:
        if sla in ("high", "fast"):
            tiers.append("bf16x3")
        if sla == "fast":
            tiers.append("bf16x1")
    return tuple(tiers)


def sla_compute_factor(config: Optional[MatrelConfig] = None) -> float:
    """Relative MXU time per MAC of the tier a dense float matmul would
    run at under the session SLA, vs the default lowering — the
    ``flop_scale`` the chain DP's step cost uses so parenthesisation
    ranks honestly when the query's FLOPs retire at bf16 rate
    (ir/chain.optimal_order; 1.0 under "default", bit-identical)."""
    cfg = config or default_config()
    tiers = sla_allowed_tiers(cfg.precision_sla, False, cfg)
    if not tiers:
        return 1.0
    best = min(tiers, key=lambda t: TIER_COMPUTE_UNITS[t])
    return TIER_COMPUTE_UNITS[best] / TIER_COMPUTE_UNITS["f32"]


#: Largest accumulated |value| the int32 tiers may provably reach: the
#: int32 accumulator's range. The chooser only auto-picks an int tier
#: when k*bound(A)*bound(B) (stats.integral_abs_bound) fits -- "exact"
#: must never silently wrap (review r8).
INT32_ACC_MAX = float(2 ** 31 - 1)


def int_tier_fits(node: MatExpr, tier: str,
                  integral_memo: Optional[dict] = None) -> bool:
    """Is an int tier PROVABLY overflow-free for this matmul? The
    accumulated product is bounded by k*bound(A)*bound(B)
    (stats.integral_abs_bound); int8 additionally needs each operand's
    entries to fit the int8 cast. Unknown bounds -> False (the chooser
    conservatively keeps f32; an unprovable explicit int pin is MV108's
    business). Shared by the chooser and the MV108 pass so gate and
    verifier cannot disagree."""
    from matrel_tpu.ir import stats
    a, b = node.children
    ba = stats.integral_abs_bound(a, integral_memo)
    bb = stats.integral_abs_bound(b, integral_memo)
    if ba is None or bb is None:
        return False
    if tier == "int8" and (ba > 127.0 or bb > 127.0):
        return False

    def exact_operand(child, bound) -> bool:
        # a FLOAT-computed integral operand is only exactly integer
        # while it fits f32's contiguous-integer range (2^24); an
        # int-tiered product carries int32 exactness instead
        if child.attrs.get("precision_tier") in ("int32", "int8"):
            return bound <= INT32_ACC_MAX
        return bound <= 2.0 ** 24

    if not (exact_operand(a, ba) and exact_operand(b, bb)):
        return False
    return a.shape[1] * ba * bb <= INT32_ACC_MAX


def choose_precision_tier(node: MatExpr,
                          config: Optional[MatrelConfig] = None,
                          dtype_memo: Optional[dict] = None,
                          integral_memo: Optional[dict] = None
                          ) -> Optional[str]:
    """The tier one matmul node will execute at under the session SLA,
    or None for the default (untier) lowering. None whenever the node
    is not a dense product the tier lowering owns:

    - "default" SLA: nothing is ever stamped (bit-identity contract);
    - sparse/COO dispatches (SpGEMM, SpMV, SpMM): their kernels own
      their numerics (bf16-split passes, f32 accumulate) already;
    - statically-unknown operand dtypes: no claim without proof;
    - non-f32 floats (bf16 leaves): already at MXU-native width.

    Integer algebra stays closed: when BOTH operands are provably
    integer-valued (integer dtype from an inner int-tier product, OR an
    integral f32 leaf -- any mix), the exact int32 tier continues,
    gated by the int32-accumulator overflow proof (int_tier_fits) --
    an unprovable magnitude keeps f32, never a silent wrap. Explicit
    int dtype SLAs pin their tier on integer data (the caller's
    claim); a float pin on integer data stamps nothing (the untier
    promotion runs).

    Among the SLA's admissible tiers (sla_allowed_tiers) the cheapest
    by tier_matmul_cost wins, deterministic ties by vocabulary order.
    ``integral_memo`` amortises the integrality/magnitude walks across
    a planning pass (the dtype-memo precedent -- review r8).
    """
    import numpy as np
    cfg = config or default_config()
    sla = cfg.precision_sla
    if sla == "default" or node.kind != "matmul":
        return None
    a, b = node.children
    if _spgemm_matmul(node, cfg) or any(
            c.kind in ("sparse_leaf", "coo_leaf") for c in node.children):
        return None
    da = infer_dtype(a, cfg, dtype_memo)
    db = infer_dtype(b, cfg, dtype_memo)
    if da is None or db is None:
        return None
    da, db = np.dtype(da), np.dtype(db)
    f32 = np.dtype("float32")

    def _ok(d):
        return d == f32 or np.issubdtype(d, np.integer)

    if not (_ok(da) and _ok(db)):
        return None
    from matrel_tpu.ir import stats
    pinned = _DTYPE_SLA_TIER.get(sla)
    any_int_dtype = (np.issubdtype(da, np.integer)
                     or np.issubdtype(db, np.integer))
    if any_int_dtype:
        # integer-dtype operands ARE integral (inner int-tier
        # products); a mixed f32 side must prove its own integrality
        # for the exact algebra to continue
        integral = all(
            np.issubdtype(d, np.integer)
            or stats.infer_integral(c, integral_memo)
            for d, c in ((da, a), (db, b)))
        if pinned in ("int32", "int8"):
            return pinned            # explicit ask: the caller's claim
        if pinned is not None:
            return None              # float pin on int data: untier
        if integral and cfg.precision_enable_int \
                and int_tier_fits(node, "int32", integral_memo):
            return "int32"
        return None
    integral = stats.infer_integral(node, integral_memo)
    tiers = sla_allowed_tiers(sla, integral, cfg)
    # the overflow proof gates the AUTO int pick; an explicit int pin
    # stays (MV108 warns/errors on unprovable or overflowing stamps)
    if pinned is None:
        tiers = tuple(t for t in tiers
                      if t not in ("int32", "int8")
                      or int_tier_fits(node, t, integral_memo))
    if not tiers:
        return None
    n, k = a.shape
    m = b.shape[1]
    dens_a = a.density if a.density is not None else 1.0
    dens_b = b.density if b.density is not None else 1.0
    best, best_cost = None, None
    for t in tiers:
        c = tier_matmul_cost(t, n, k, m, dens_a, dens_b)
        if best_cost is None or c < best_cost:
            best, best_cost = t, c
    return best


def strategy_hbm_bytes(strategy: str, pn: int, pk: int, pm: int,
                       gx: int, gy: int, itemsize: int = 4,
                       panels: Tuple[int, int] = (1, 1)) -> float:
    """Per-device HBM working set of ONE product's shard_map program,
    taken alone, in bytes: operand shards × their replication factor +
    the output, at the padded dims the specs actually carve
    (strategies.py in_specs/out_specs). Dense bytes on purpose — every
    strategy here consumes materialised dense operands, so a density
    credit would under-count exactly the plans the feasibility gate
    exists to drop (per-chip memory is THE binding constraint for
    distributed linear algebra on TPUs, arXiv:2112.09017).

    ``panels`` = (row panels, column panels) of the panelled rmm
    (strategies.matmul_rmm): one panel of each gathered operand is
    alive, so the replication shrinks by the panel counts. xla is
    estimated like the one-panel rmm — GSPMD gathers both operands'
    panels for a 2D-sharded product, and at the sizes where that
    matters it is what the chip's compiler was seen to do (PERF.md §6,
    PR 27) — and is gated like the others; spgemm is 0: its working
    set is the sparse pair list, priced by spgemm_estimates, not a
    dense replication factor. What ELSE is alive beside the product
    (catalog tables, a chain's intermediates) is the plan-level
    reckoning's, :func:`plan_hbm_bytes`."""
    p = max(gx * gy, 1)
    a = float(pn) * pk * itemsize
    b = float(pk) * pm * itemsize
    c = float(pn) * pm * itemsize
    if strategy == "bmm_right":
        return b + a / p + c / p          # B replicated everywhere
    if strategy == "bmm_left":
        return a + b / p + c / p
    if strategy == "cpmm":
        # A P(x,y); B P(y,None) — replicated along x; partial C
        # (pn/gx × pm) lives until the reduce-scatter
        return a / p + b / gy + c / gx
    if strategy in ("rmm", "xla"):
        # the replication strategy: A holds every y-slice, B every
        # x-slice (VERDICT r5 Weak #3 — the case that OOMs first);
        # panelled, one row panel of A and one column panel of B
        r, cp = panels if strategy == "rmm" else (1, 1)
        return a / gx / r + b / gy / cp + c / p
    if strategy == "summa":
        # P(x,y) tiles double-buffered through the ppermute ring
        return 2.0 * (a / p + b / p) + c / p
    return 0.0                            # spgemm / unknown


def strategy_transient_bytes(strategy: str, pn: int, pk: int, pm: int,
                             gx: int, gy: int, itemsize: int = 4,
                             panels: Tuple[int, int] = (1, 1)) -> float:
    """Per-device bytes a strategy allocates BESIDE its operands as they
    lie (2D shards) and its output at the storage dtype — the term the
    plan-level reckoning adds to the residents and intermediates alive
    at a product (:func:`plan_hbm_bytes`). ``itemsize`` is the
    operands' (and the stored output's, keep_input_dtype); narrow
    operands accumulate in four bytes, and a strategy whose
    accumulator is the whole tile (summa's carry, cpmm's partial,
    whatever leaves a shard_map before the storage cast) pays for it
    here."""
    p = max(gx * gy, 1)
    a = float(pn) * pk * itemsize
    b = float(pk) * pm * itemsize
    acc = acc_itemsize(itemsize)
    c_acc = float(pn) * pm * acc
    wide = c_acc / p if acc > itemsize else 0.0   # tile before the cast
    if strategy == "rmm":
        return rmm_transient_bytes(pn, pk, pm, gx, gy, itemsize, panels)
    if strategy == "xla":
        # both gathered panels (the partitioner's all-gathers)
        return ((a / gx if gy > 1 else 0.0)
                + (b / gy if gx > 1 else 0.0))
    if strategy == "cpmm":
        # B re-laid P(y, None); the partial C in the accumulator's
        # width until the reduce-scatter
        return (b / gy if gx > 1 else 0.0) + c_acc / gx
    if strategy == "summa":
        # skewed copies of both tiles, the shifted ones received
        # beside them, and the tile-sized accumulator of the carry
        return 2.0 * (a / p + b / p) + c_acc / p
    if strategy == "bmm_right":
        return b + a / p + wide
    if strategy == "bmm_left":
        return a + b / p + wide
    if strategy == OWN_ROWS:
        # operands read in place (none); the device's partial product
        # in the loop's accumulators, and the all-reduce's result
        # beside it (a Gram's block triangle is 5/8 of this)
        return 2.0 * c_acc
    return 0.0                            # spgemm / unknown


def divides(strategy: str, pn: int, pk: int, pm: int,
            gx: int, gy: int) -> bool:
    """Can this strategy's shard_map specs divide the padded dims evenly?

    Size-1 (vector/scalar) dims stay unpadded (padding.py), so matvec-shaped
    multiplies are only eligible for strategies that keep those dims
    replicated — everything else falls through to the XLA SPMD path."""
    p = gx * gy
    if strategy == "bmm_right":
        return pn % p == 0
    if strategy == "bmm_left":
        return pm % p == 0
    if strategy == "cpmm":
        return pn % gx == 0 and pk % gy == 0 and pm % gy == 0
    if strategy == "rmm":
        return pn % gx == 0 and pm % gy == 0
    if strategy == "summa":
        return (gx == gy and pn % gx == 0 and pm % gy == 0
                and pk % gx == 0 and pk % gy == 0)
    if strategy == OWN_ROWS:
        return pk % p == 0
    return True  # xla


def admissible(strategy: str, pn: int, pk: int, pm: int,
               gx: int, gy: int, itemsize: int = 4,
               hbm_budget_bytes: int = 0) -> bool:
    """:func:`divides` — and, when ``hbm_budget_bytes`` > 0, does the
    product's own per-device working set (strategy_hbm_bytes, one
    panel) fit the budget?

    The HBM gate (VERDICT r5 Weak #3 / Next #6) drops over-replicating
    plans BEFORE costing: a byte model that ranks RMM cheapest on ICI
    traffic must never hand the executor a plan whose replicated
    operands cannot exist on the chip. xla is gated like the others
    (PR 27: exempt and estimated at 0, it was handed the plans that
    could not be allocated). This is the gate of ONE product taken
    alone, for callers that hold no plan (autotune, the verifier's
    hints); the planner's own choice is held to the plan-level
    reckoning (:func:`plan_hbm_bytes`), where rmm may also take more
    panels."""
    if (hbm_budget_bytes > 0
            and strategy_hbm_bytes(strategy, pn, pk, pm, gx, gy,
                                   itemsize) > hbm_budget_bytes):
        return False
    return divides(strategy, pn, pk, pm, gx, gy)


def device_bytes(node: MatExpr, mesh: Mesh,
                 config: Optional[MatrelConfig] = None,
                 dtype_memo: Optional[dict] = None,
                 layout_memo: Optional[dict] = None) -> float:
    """Bytes of a node's value on ONE device: the shard the leaf's array
    really has there, or the padded shape at the inferred dtype over
    the devices its inferred layout spreads it on (all of them unless
    replicated). A block-sparse leaf counts its tile stack whole (it
    is replicated); a COO leaf's device tables are its plan's, built
    on first use, and are not reckoned."""
    if node.kind == "leaf":
        data = getattr(node.attrs.get("matrix"), "data", None)
        sharding = getattr(data, "sharding", None)
        if sharding is not None:
            # what the buffers really take, where the array can say: it
            # depends on how a table lies in the chip's (8, 128) tiles
            # (1000 columns on the lanes are 1024; an N x 1 column laid
            # row-major is 128 lanes a row, 1.3 GB for 10 MB of data)
            try:
                return (float(data.on_device_size_in_bytes())
                        / max(len(sharding.device_set), 1))
            except (AttributeError, RuntimeError):
                # a described shape (no buffer), a deleted array
                shard = sharding.shard_shape(data.shape)
                return float(np.prod(shard)) * data.dtype.itemsize
    elif node.kind == "sparse_leaf":
        return float(node.attrs["matrix"].blocks.nbytes)
    elif node.kind == "coo_leaf":
        return 0.0
    from matrel_tpu.core import padding
    dt = infer_dtype(node, config, dtype_memo)
    isz = np.dtype(dt).itemsize if dt is not None else 4
    total = float(np.prod(padding.padded_shape(node.shape, mesh))) * isz
    if infer_layout(node, mesh, layout_memo, config) == "rep":
        return total
    return total / max(mesh.size, 1)


def plan_resident_bytes(root: MatExpr, mesh: Mesh,
                        config: Optional[MatrelConfig] = None) -> float:
    """Per-device bytes of every leaf the plan reads (each once): the
    catalog tables that are resident on the chip whatever the plan
    does."""
    seen, total = set(), 0.0

    def walk(n: MatExpr):
        nonlocal total
        if n.uid in seen:
            return
        seen.add(n.uid)
        if not n.children:
            total += device_bytes(n, mesh, config)
        for c in n.children:
            walk(c)

    walk(root)
    return total


class PlanMemoryError(ValueError):
    """A one-device plan whose reckoned peak is over the device's limit
    (:func:`refuse_over_limit`): raised before anything is traced."""


def solve_transient_bytes(k: int, m: int) -> float:
    """What a ``solve`` allocates beside its operands and its output:
    the float32 copy of the k x k left side that the factorisation
    overwrites, and the float32 right-hand side the substitutions
    run in (executor._solve computes in float32 on logical shapes)."""
    return 4.0 * k * k + 4.0 * k * m


def plan_hbm_bytes(strategy: str, pn: int, pk: int, pm: int,
                   gx: int, gy: int, itemsize: int, alive: float,
                   panels: Tuple[int, int] = (1, 1)) -> float:
    """A PLAN's reckoned peak on one device while one of its products
    runs under ``strategy``: ``alive`` — the leaves the plan reads
    (catalog residents) and every intermediate alive at that product,
    its own operands among them — plus the product's output at its
    storage dtype, plus the strategy's transient
    (:func:`strategy_transient_bytes`). This, not one product's own
    working set, is what a chip has to hold: three 2 GiB tables and a
    chain's intermediate leave a v5e's 15.75 GiB 5.75 for the second
    product's transient (PERF.md §6, PR 27)."""
    out = float(pn) * pm * itemsize
    if STRATEGY_OUT_LAYOUT.get(strategy) != "rep":  # else whole on each
        out /= max(gx * gy, 1)
    return alive + out + strategy_transient_bytes(
        strategy, pn, pk, pm, gx, gy, itemsize, panels)


def _hbm_gate(cands, pn, pk, pm, gx, gy, itemsize, alive, limit):
    """The plan-level feasibility gate over candidate strategies:
    {strategy: (plan bytes, panels, fits)}. rmm takes the fewest panels
    whose transient fits what ``alive`` and the output leave of
    ``limit``; with the gate off (limit 0) every candidate fits at one
    panel."""
    out_b = float(pn) * pm * itemsize / max(gx * gy, 1)
    room = limit - alive - out_b if limit > 0 else None
    out = {}
    for s in cands:
        panels = (rmm_panels(pn, pk, pm, gx, gy, itemsize, room)
                  if s == "rmm" else (1, 1))
        need = plan_hbm_bytes(s, pn, pk, pm, gx, gy, itemsize, alive,
                              panels)
        out[s] = (need, panels, not (limit > 0 and need > limit))
    return out


def choose_strategy(node: MatExpr, mesh: Mesh,
                    config: Optional[MatrelConfig] = None,
                    dtype_memo: Optional[dict] = None,
                    layout_memo: Optional[dict] = None) -> str:
    """Pick the cheapest admissible strategy for one matmul node."""
    return choose_strategy_ex(node, mesh, config, dtype_memo,
                              layout_memo)[0]


def _root_reshard_cost(strategy: str, n: int, m: int,
                       gx: int, gy: int,
                       transposed: bool = False,
                       weights: Tuple[float, float] = (1.0, 1.0)
                       ) -> float:
    """Per-device ICI bytes to re-lay a strategy's OUTPUT to the
    canonical sharding. The executor constrains every ROOT output to
    canonical_sharding (lower_multi), so a root-level bmm really pays
    this row/col→2d move after computing; interior consumers instead
    see the producer's layout through their own per-layout credit and
    must NOT be charged here (round 5). ``transposed`` marks an ODD
    number of transposes between this matmul and the root: the
    transpose swaps row↔col, so the re-lay gathers along the OTHER
    perpendicular axis (review r5 — matters on non-square grids).
    Same closed forms as comm_cost's reshard terms; the gather is a
    single-axis collective, billed at that axis's topology weight."""
    p = gx * gy
    c_bytes = _bytes((n, m), 1.0)
    out_row = (strategy == "bmm_right") != transposed
    if strategy == "bmm_right" or strategy == "bmm_left":
        g_perp = gy if out_row else gx
        w = weights[1] if out_row else weights[0]
        return (c_bytes / p) * (1 - 1 / g_perp) * w
    return 0.0                         # cpmm/rmm/summa/xla emit 2d


#: Output layout each matmul strategy emits (strategies.py out_specs) —
#: the ONE mapping shared by infer_layout's matmul rule and the
#: consumer-aware tiebreak (review r5).
STRATEGY_OUT_LAYOUT = {"bmm_right": "row", "bmm_left": "col",
                       "cpmm": "2d", "rmm": "2d", "summa": "2d",
                       "xla": "2d", "spgemm": "2d", OWN_ROWS: "rep"}

#: Near-tie band for the consumer-aware STRATEGY tiebreak (the matmul
#: analogue of JOIN_TIE_REL): candidates within this margin of the
#: cheapest may be flipped toward the layout the consumer reads free.
STRATEGY_TIE_REL = 0.10


def _hint_tiebreak(costs: dict, best, out_layout_of,
                   hint: Optional[str], tie_rel: float):
    """Shared near-tie flip for the consumer-aware tiebreaks (join
    schemes and matmul strategies — review r5: one band/epsilon rule,
    not two drifting copies): among candidates within ``tie_rel`` of
    the cheapest, return the cheapest one whose output layout (per
    ``out_layout_of``) matches ``hint``; otherwise ``best``."""
    if hint is None:
        return best
    near = sorted(
        (s for s in costs
         if costs[s] <= costs[best] * (1.0 + tie_rel) + 1e-9),
        key=costs.get)
    for s in near:
        if out_layout_of(s) == hint:
            return s
    return best


def choose_strategy_ex(node: MatExpr, mesh: Mesh,
                       config: Optional[MatrelConfig] = None,
                       dtype_memo: Optional[dict] = None,
                       layout_memo: Optional[dict] = None,
                       root_output: bool = False,
                       root_transposed: bool = False,
                       consumer_hint: Optional[str] = None,
                       root_scale: float = 1.0,
                       cost_detail: Optional[dict] = None,
                       alive_bytes: Optional[float] = None,
                       hbm_detail: Optional[dict] = None
                       ) -> Tuple[str, str]:
    """(strategy, source) for one matmul node. ``source`` records WHY —
    the observability side of the closed loop (physical EXPLAIN prints
    it): "override" (config.strategy_override), "dispatch" (an S×S
    SpGEMM the lowering takes regardless of the byte model), "measured"
    (autotune table hit), "model" (byte-model argmin), "layout" (a
    long float32 product whose operands lie cut over all devices along
    the contraction is multiplied where it lies: :func:`long_in_place`),
    "default" (single device / no admissible candidates).

    ``cost_detail`` (an out-param dict, the return tuple stays a
    2-tuple for the existing callers — analysis passes unpack it
    positionally) reports WHICH cost model priced a "model" decision
    when ``config.coeff_planner_enable``: ``{"cost": "measured"}``
    when the learned-coefficient ranking ran (every admissible
    candidate had a warm parallel/coeffs.py row), ``{"cost":
    "analytic"}`` when any candidate was cold and the closed forms
    decided (docs/COST_MODEL.md).

    ``alive_bytes`` is what the PLAN keeps on one device while this
    product runs — the leaves it reads and the intermediates alive,
    this product's operands among them (annotate_strategies threads
    it); None reckons the product alone (its two operands as they
    lie). Every candidate's plan-level peak (:func:`plan_hbm_bytes`)
    is held to ``core.mesh.hbm_limit_bytes``. ``hbm_detail`` (an out-param
    dict, like ``cost_detail``) receives ``chosen``, its ``panels``,
    ``moves_under_dot``, ``hbm_plan_bytes`` and ``refused_hbm``."""
    cfg = config or default_config()
    if _spgemm_matmul(node, cfg):
        # S×S below the density crossover: the LOWERING dispatches the
        # tile-intersection SpGEMM unconditionally (_spgemm_dispatch is
        # the shared truth, the _coo_dispatch_plan pattern), so the
        # stamp must say so — obs/explain then report what executes.
        # Checked BEFORE strategy_override: an override cannot reroute
        # this dispatch (same as the COO SpMV path), so stamping the
        # override string would misreport what runs and price a comm
        # bill that never executes. Forcing the densify path is the
        # documented kill switch config.spgemm_density_threshold = 0.
        # Its comm bill is comm_cost("spgemm") = 0 (replicated tile
        # stacks, device-local pairs); the nnz-proportional FLOP side
        # lives in spgemm_estimates.
        return "spgemm", "dispatch"
    a, b = node.children
    n, k = a.shape
    _, m = b.shape
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    from matrel_tpu.core import padding
    pn, pk = padding.padded_shape((n, k), mesh)
    _, pm = padding.padded_shape((k, m), mesh)
    if (gx * gy == 1 and cfg.strategy_override == "auto"
            and hbm_detail is None):
        return "xla", "default"  # single device: plain local dot
    # the feasibility gate reckons the PLAN's peak on a device, not
    # one product's own working set: what is alive beside the product
    # comes from the caller (annotate_strategies), or is the product's
    # own operands as they lie
    if alive_bytes is None:
        alive_bytes = (device_bytes(a, mesh, cfg, dtype_memo, layout_memo)
                       + device_bytes(b, mesh, cfg, dtype_memo,
                                      layout_memo))
    limit = mesh_lib.hbm_limit_bytes(mesh, cfg)
    # the gate reads the real accumulation itemsize where it is
    # statically known (bf16 operands still accumulate/store f32-sized
    # working sets only when promotion says so — infer_dtype is the
    # one mirror of that); unknown dtypes assume f32
    dt_out = infer_dtype(node, cfg, dtype_memo)
    isz = np.dtype(dt_out).itemsize if dt_out is not None else 4
    # a stamped precision tier changes the operand WIDTH the strategy's
    # working set is built from (bf16x1 replicates half the bytes, so
    # plans the f32 budget refuses become feasible; int8 a quarter) —
    # the gate must see the tier's real itemsize, not the f32 one
    tier = node.attrs.get("precision_tier")
    if tier in TIER_ITEMSIZE:
        isz = TIER_ITEMSIZE[tier]
    if cfg.strategy_override != "auto" or gx * gy == 1:
        # one device: the plain local dot, whatever is forced on it
        # (every strategy's shard_map is that dot on a 1x1 grid): no
        # choice to make, but the plan's peak at this product is
        # reckoned all the same
        forced = (cfg.strategy_override
                  if cfg.strategy_override != "auto" else "xla")
        if hbm_detail is not None:
            # a forced strategy is reckoned like a chosen one (a forced
            # rmm still derives its panels from the budget); the gate
            # can only say that it does not fit
            gated = forced if gx * gy > 1 else "xla"
            need, panels, ok = _hbm_gate([gated], pn, pk, pm, gx, gy, isz,
                                         alive_bytes, limit)[gated]
            hbm_detail.update(chosen=forced, panels=panels,
                              moves_under_dot=rmm_moves_under_dot(
                                  pk, gy, panels),
                              hbm_plan_bytes=int(need),
                              refused_hbm=[] if ok else [forced])
        return ((forced, "override") if cfg.strategy_override != "auto"
                else ("xla", "default"))
    if long_in_place(node, mesh, cfg, dtype_memo):
        # nothing to rank: cpmm, rmm and summa would each re-lay (and a
        # transposed operand first copy) a table that the devices can
        # multiply where it lies; the gate can only say that what the
        # plan keeps beside it does not fit
        need, panels, ok = _hbm_gate([OWN_ROWS], pn, pk, pm, gx, gy, isz,
                                     alive_bytes, limit)[OWN_ROWS]
        if hbm_detail is not None:
            hbm_detail.update(chosen=OWN_ROWS, panels=panels,
                              moves_under_dot=0, hbm_plan_bytes=int(need),
                              refused_hbm=[] if ok else [OWN_ROWS])
        return OWN_ROWS, "layout"
    la = infer_layout(a, mesh, layout_memo, cfg)
    lb = infer_layout(b, mesh, layout_memo, cfg)
    if cfg.autotune:
        # MEASURED winner beats the byte model (closed autotune loop);
        # admissibility is re-checked against THESE dims — the table
        # keys by shape class, the divisibility constraint is exact.
        # Only consulted when BOTH operand dtypes are statically known
        # (leaves, possibly through transposes) and equal: keying a
        # bf16 multiply into the f32 table row — or measuring f32
        # operands for a bf16 chain step — would violate the
        # measured-beats-model premise. Density-credited operands skip
        # the table too (advisor r3): it measures DENSE probes, and the
        # byte model's density credit would be bypassed on a hit.
        # Layout gates the consult the same way (VERDICT r4 "what's
        # missing" #3): the table measures canonically-2D-sharded
        # operands, so a winner is only applied when BOTH operands
        # actually lie 2D — a row-sharded bmm output or a replicated
        # leaf gets the byte model, whose per-layout credit sees the
        # real placement. No measured winner is ever applied to a
        # layout it wasn't measured on.
        dta = infer_dtype(a, cfg, dtype_memo)
        dtb = infer_dtype(b, cfg, dtype_memo)
        dense = ((a.density is None or a.density >= 1.0)
                 and (b.density is None or b.density >= 1.0))
        if (dense and dta is not None and dta == dtb
                and la == "2d" and lb == "2d"):
            from matrel_tpu.parallel import autotune
            best = autotune.lookup_or_measure(n, k, m, mesh, str(dta),
                                              cfg)
            if (best is not None
                    and admissible(best, pn, pk, pm, gx, gy,
                                   itemsize=np.dtype(dta).itemsize,
                                   hbm_budget_bytes=cfg.hbm_budget_bytes)
                    and not (root_output
                             and STRATEGY_OUT_LAYOUT.get(best) != "2d")):
                # a measured 1D-emitting winner is NOT applied at a
                # plan ROOT: the probes never pay the canonical-output
                # re-lay the executor performs there, so the premise
                # doesn't cover this context (review r5) — the model,
                # which charges _root_reshard_cost, decides instead
                return best, "measured"
    da, db = a.density, b.density
    cands = {}
    a_bytes = _bytes((n, k), da)
    b_bytes = _bytes((k, m), db)
    # per-step latency charge (α-β model, VERDICT r5 "Missing #4") —
    # the planner is the one caller that prices REAL choices, so it
    # passes the configured α; the chain DP's comm proxy stays β-only
    # (its native mirror is fuzzed against the alpha-free closed forms)
    al = cfg.comm_alpha_bytes
    # per-axis topology weights (core/mesh.MeshTopology): on a
    # hierarchical ICI/DCN mesh every candidate's collective legs are
    # billed on the axis they actually ride — the piece that keeps the
    # ranking honest the moment the fabric stops being homogeneous
    wts = mesh_lib.axis_weights(mesh, cfg)
    # BMM is only admissible when the broadcast side fits the threshold —
    # the reference's broadcast-variable size gate.
    if b_bytes <= cfg.broadcast_threshold_bytes:
        cands["bmm_right"] = comm_cost("bmm_right", n, k, m, da, db, gx, gy,
                                       a_layout=la, b_layout=lb,
                                       alpha_bytes=al, weights=wts)
    if a_bytes <= cfg.broadcast_threshold_bytes:
        cands["bmm_left"] = comm_cost("bmm_left", n, k, m, da, db, gx, gy,
                                      a_layout=la, b_layout=lb,
                                      alpha_bytes=al, weights=wts)
    cands["cpmm"] = comm_cost("cpmm", n, k, m, da, db, gx, gy,
                              a_layout=la, b_layout=lb, alpha_bytes=al,
                              weights=wts)
    cands["rmm"] = comm_cost("rmm", n, k, m, da, db, gx, gy,
                             a_layout=la, b_layout=lb, alpha_bytes=al,
                             weights=wts)
    # SUMMA needs a square grid and pays latency per step; prefer it when
    # replication would not fit HBM (big square operands).
    if gx == gy and gx > 1:
        cands["summa"] = comm_cost("summa", n, k, m, da, db, gx, gy,
                                   a_layout=la, b_layout=lb,
                                   alpha_bytes=al, weights=wts)
    cands = {s: c for s, c in cands.items()
             if divides(s, pn, pk, pm, gx, gy)}
    gate = _hbm_gate(cands, pn, pk, pm, gx, gy, isz, alive_bytes, limit)
    if not any(ok for _, _, ok in gate.values()):
        # nothing the byte model ranks fits (or divides): the XLA SPMD
        # path, estimated and gated like the others; where that is
        # refused too, the candidate that needs least — a plan that may
        # not fit is still better handed over than none, and
        # ``refused_hbm`` says so
        gate.update(_hbm_gate(["xla"], pn, pk, pm, gx, gy, isz,
                              alive_bytes, limit))
        cands = {}
    refused = sorted(s for s, g in gate.items() if not g[2])
    fits = {s: g for s, g in gate.items() if g[2]}
    if not fits:
        least = min(gate, key=lambda s: gate[s][0])
        fits = {least: gate[least]}

    def _report(strategy):
        if hbm_detail is not None:
            hbm_detail.update(chosen=strategy, panels=fits[strategy][1],
                              moves_under_dot=rmm_moves_under_dot(
                                  pk, gy, fits[strategy][1]),
                              hbm_plan_bytes=int(fits[strategy][0]),
                              refused_hbm=refused)

    cands = {s: c for s, c in cands.items() if s in fits}
    if "rmm" in cands and fits["rmm"][1][0] > 1 and lb != "rep":
        # every further row panel gathers B's column panels once more
        cands["rmm"] += ((fits["rmm"][1][0] - 1) * (b_bytes / gy)
                         * (gx - 1) / gx * wts[0])
    if root_output:
        # the executor re-lays ROOT outputs to the canonical sharding;
        # a bmm's 1D-sharded result pays that move, 2d emitters do
        # not. ``root_scale`` (annotate's _child_root_scale) weights
        # the charge by how much of the root's output bytes this
        # node's layout actually reaches — half under a root elemwise
        # (at most one operand's re-lay occurs), the element-count
        # ratio under shape-changing wrappers (ADVICE r5).
        cands = {s: c + _root_reshard_cost(s, n, m, gx, gy,
                                           root_transposed,
                                           weights=wts) * root_scale
                 for s, c in cands.items()}
    if not cands:
        only = next(iter(fits))
        _report(only)
        return only, "default"
    if cfg.coeff_planner_enable:
        # learned-coefficient ranking (parallel/coeffs.py — the ML018
        # seam; docs/COST_MODEL.md): when EVERY admissible candidate
        # has a warm calibration row for this (strategy[@tier],
        # shape-class, backend) population, rank by predicted
        # milliseconds — ms/GFLOP × FLOPs + ms/est-MiB × the weighted
        # bill each candidate was just priced at (the exact quantity
        # the drift auditor calibrated the ratio against, root-reshard
        # charge included). Partial coverage stays analytic: comparing
        # one candidate's measured milliseconds against another's raw
        # byte-equivalents would be a units error, not a ranking —
        # the cold-class fallback the placement model set.
        from matrel_tpu.parallel import coeffs as coeffs_lib
        from matrel_tpu.obs import drift as drift_lib
        import jax
        cost_src = "analytic"
        path = drift_lib.table_path(cfg)
        cls = drift_lib.shape_class((n, k, m))
        backend = jax.default_backend()
        gf = 2.0 * n * k * m / 1e9
        measured: Optional[dict] = {}
        for s, c in cands.items():
            row = coeffs_lib.strategy_row(s, cls, backend, path,
                                          tier=tier or "")
            if row is None or row["count"] < cfg.coeff_min_samples:
                measured = None
                break
            measured[s] = coeffs_lib.predict_ms(row, gf, c)
        if measured:
            cands = measured
            cost_src = "measured"
        if cost_detail is not None:
            cost_detail["cost"] = cost_src
    best = min(cands, key=cands.get)
    if not root_output:
        # consumer-aware tiebreak (the matmul analogue of the join
        # scheme's, round 5): among near-tied candidates prefer the one
        # whose output layout the PARENT consumes in place — e.g. a
        # left-child multiply flips an ε-worse bmm_right over rmm
        # because the parent reads its row-sharded result for free.
        best = _hint_tiebreak(cands, best, STRATEGY_OUT_LAYOUT.get,
                              consumer_hint, STRATEGY_TIE_REL)
    _report(best)
    return best, "model"


def _reshard_to_axis(bytes_: float, layout: str, axis: str,
                     gx: int, gy: int,
                     weights: Tuple[float, float] = (1.0, 1.0),
                     config: Optional[MatrelConfig] = None) -> float:
    """Per-device ICI bytes to re-lay an operand as 1D-sharded over all
    devices along ``axis`` ("row"/"col") from its current ``layout`` —
    the join-side analogue of comm_cost's per-layout reshard terms,
    billed at the topology weight of the mesh axis each move rides.

    With ``config.reshard_peak_budget_bytes`` > 0 the price comes from
    the REAL ReshardPlan the lowering will run (parallel/reshard.py)
    instead of these closed forms: for single-axis moves the two are
    bit-identical by construction (the plan compiler reuses this
    module's float expressions verbatim — equality-tested), and for
    the one move where they can differ — the opposite-1D flip whose
    bounded decomposition routes through 2d when the direct move's
    transient would blow the budget — the plan's honestly higher
    staged bill is what the join scheme must rank by. The default
    config never constructs a plan (closed forms stay the fast path).
    """
    p = max(gx * gy, 1)
    wx, wy = weights
    if layout == axis or layout == "rep":
        return 0.0
    if config is not None and config.reshard_peak_budget_bytes > 0:
        from matrel_tpu.parallel import reshard as reshard_lib
        return reshard_lib.compile_reshard(
            layout, axis, bytes_, gx, gy, weights,
            peak_budget=float(config.reshard_peak_budget_bytes)
        ).weighted_cost
    if layout in ("2d", "other"):
        # gather along the perpendicular mesh axis (same closed form as
        # comm_cost's bmm reshard terms). "other" (a real non-canonical
        # placement) is costed exactly like "2d" per the LAYOUTS
        # contract — no credit, no penalty (review r5: this branch and
        # the doc must agree)
        g_perp = gy if axis == "row" else gx
        w_perp = wy if axis == "row" else wx
        return (bytes_ / p) * (1 - 1 / g_perp) * w_perp
    # opposite 1D sharding: all-to-all redistribution of the local
    # shard — a full-mesh collective with source bytes_/p, split per
    # axis like the broadcasts (_split_full_mesh; flat form preserved
    # at uniform weights)
    return _split_full_mesh(bytes_ / p, gx, gy, wx, wy)[0]


#: Near-tie band for the consumer-aware join-scheme tiebreak: schemes
#: within this relative margin of the cheapest are considered equal-cost
#: and the one whose OUTPUT layout the consumer reads in place wins.
JOIN_TIE_REL = 0.10


def _scheme_out_layout(scheme: str, node: MatExpr,
                       la: str, lb: str) -> str:
    """Output layout each join scheme produces (mirrors infer_layout's
    join case, phrased over candidate schemes instead of the stamped
    one)."""
    if scheme == "align":
        return "row" if node.kind == "join_rows" else "col"
    return lb if scheme == "left" else la


def choose_join_scheme(node: MatExpr, mesh: Mesh,
                       config: Optional[MatrelConfig] = None,
                       layout_memo: Optional[dict] = None,
                       consumer_hint: Optional[str] = None) -> str:
    """Scheme selection for row/col index joins — the reference's
    cost-based choice of which operand to replicate (SURVEY.md §2
    "Physical: relational execs": "join-scheme selection to minimize
    replication"), v3 with PER-LAYOUT cost terms (VERDICT r3 #5; v2
    credited only fully-replicated operands).

    Three schemes, costed like comm_cost does for matmuls:
      "left"/"right" — all-gather that side everywhere (free when it is
        already replicated). The KEPT side pays nothing: with the other
        operand fully replicated, the broadcast-merge computes on the
        kept side's existing layout and the output inherits it;
      "align" — replicate NOTHING: both operands re-laid 1D-sharded
        along the join axis, the join computes shard-locally. This is
        the scheme that wins when a large operand's existing row/col
        sharding can be consumed in place (its reshard term is zero)
        and also for similar-sized 2D operands, where two cheap
        redistributions beat one full broadcast.
    Bytes are density-credited. Returns "left" | "right" | "align".

    ``consumer_hint`` (VERDICT r4 #7) is the layout the PARENT node
    would consume in place ("row" for a matmul's left operand, "col"
    for its right — the bmm credits); among schemes within JOIN_TIE_REL
    of the cheapest, the one whose output layout matches the hint wins,
    so an align output feeding a matmul is not thrown away for a
    same-cost replicate whose output the parent must reshard."""
    a, b = node.children
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    p = max(gx * gy, 1)
    axis = "row" if node.kind == "join_rows" else "col"
    la = infer_layout(a, mesh, layout_memo, config)
    lb = infer_layout(b, mesh, layout_memo, config)
    a_bytes = _bytes(a.shape, a.density if a.density is not None else 1.0)
    b_bytes = _bytes(b.shape, b.density if b.density is not None else 1.0)
    # same topology weighting as the matmul model: a replicate scheme's
    # full-mesh all-gather and align's per-axis reshards are billed on
    # the axes they ride, so joins stop broadcasting over the DCN axis
    # when an in-slice redistribution is cheaper
    wts = mesh_lib.axis_weights(mesh, config)

    def ag(bytes_: float, layout: str) -> float:
        if layout == "rep":
            return 0.0
        return _split_full_mesh(bytes_, gx, gy, wts[0], wts[1])[0]

    cost = {
        "left": ag(a_bytes, la),
        "right": ag(b_bytes, lb),
    }
    # align needs the join axis to actually shard p ways: with fewer
    # rows/cols than devices the 1D constraint degenerates to XLA
    # involuntary full rematerialization (replicate both operands, then
    # repartition) — strictly worse than the broadcast it was meant to
    # avoid (review r4, reproduced on the 8-device CPU mesh)
    # the join constructors enforce equal extents on the join axis
    # (relational/ops.py), so reading operand a alone is sound; assert
    # it here so a future join kind with unequal extents cannot
    # silently break the gate (VERDICT r4 "what's weak" #5)
    a_extent = a.shape[0] if axis == "row" else a.shape[1]
    b_extent = b.shape[0] if axis == "row" else b.shape[1]
    if a_extent != b_extent:      # explicit raise, not assert: must
        raise ValueError(         # survive python -O (review r5)
            f"{node.kind} operands disagree on the join axis extent "
            f"({a_extent} vs {b_extent}) — the align gate assumes the "
            f"constructor-enforced equality (relational/ops.py)")
    if a_extent >= p:
        cost["align"] = (
            _reshard_to_axis(a_bytes, la, axis, gx, gy, weights=wts,
                             config=config)
            + _reshard_to_axis(b_bytes, lb, axis, gx, gy, weights=wts,
                               config=config))
    best = min(cost, key=cost.get)
    return _hint_tiebreak(
        cost, best, lambda s: _scheme_out_layout(s, node, la, lb),
        consumer_hint, JOIN_TIE_REL)


def _child_root_scale(e: MatExpr, i: int, scale: float) -> float:
    """Fraction of the plan-ROOT canonical-resharding charge child
    ``i``'s output layout is exposed to (0.0 = none — the v1 bool,
    review r5, is now a weight, ADVICE r5). The executor re-lays only
    the ROOT output (lower_multi), so exposure flows through
    entrywise/layout-preserving wrappers — a scalar op over a bmm
    output still pays the row→canonical move at the root — and stops
    under a matmul/join/agg, whose own cost model sees the child's
    layout instead. Two corrections over the bool version:

    * elemwise/join_index exposed BOTH children to the FULL charge,
      though at most one root re-lay ever occurs; which operand's
      layout carries is unknowable here (children are not yet
      annotated), so each side now carries half — except under
      broadcast, where only the full-shaped operand's layout can flow
      to the root at all (infer_layout's elemwise rule) and it carries
      the whole charge;
    * the charge was priced on the child's own (n, m) bytes even when
      a shape-changing wrapper sits between it and the root — the real
      re-lay acts on the WRAPPER's output. The element-count ratio
      rescales it (identity for today's shape-preserving masked
      selects; exact for transpose and any future shrinking select)."""
    if scale <= 0.0:
        return 0.0

    def _elems(shape) -> float:
        return float(max(shape[0] * shape[1], 1))

    k = e.kind
    child = e.children[i]
    if k in ("scalar", "select_value", "select_index",
             "select_block", "transpose"):
        return scale * _elems(e.shape) / _elems(child.shape)
    if k == "rank1":
        return scale if i == 0 else 0.0
    if k in ("elemwise", "join_index"):
        if k == "elemwise" and e.children[0].shape != e.children[1].shape:
            return scale if child.shape == e.shape else 0.0
        return scale * 0.5
    return 0.0


def _child_layout_hints(e: MatExpr, mesh: Optional[Mesh] = None,
                        config: Optional[MatrelConfig] = None,
                        dtype_memo: Optional[dict] = None
                        ) -> Tuple[Optional[str], ...]:
    """Layout each child's output would be consumed in-place at by this
    node, for the consumer-aware tiebreaks: a matmul reads its left
    operand row-sharded for free (bmm_right's reshard credit) and its
    right operand col-sharded (bmm_left). A hint is only emitted when
    the parent could actually RUN that bmm — its broadcast side under
    the threshold, not a sparse/COO dispatch (whose SpMV/SpMM
    lowerings ignore the hinted layout entirely) — review r5 — AND
    admissible on the mesh's grid for the parent's PADDED dims
    (ADVICE r5: a bmm whose sharded dim does not divide by the device
    count never runs, so its hint steered the child toward a layout
    the parent could not consume). An unusable hint flips the child to
    a worse pick AND leaves the parent paying a 1D→2d re-lay, a
    double loss. ``mesh=None`` skips only the divisibility gate (for
    callers without one in hand). Other parents express no
    preference."""
    if e.kind == "matmul":
        if any(c.kind in ("sparse_leaf", "coo_leaf") for c in e.children):
            return (None, None)
        cfg = config or default_config()
        a, b = e.children
        b_fits = _bytes(b.shape, b.density) <= cfg.broadcast_threshold_bytes
        a_fits = _bytes(a.shape, a.density) <= cfg.broadcast_threshold_bytes
        right_ok, left_ok = b_fits, a_fits
        if mesh is not None:
            from matrel_tpu.core import padding
            gx, gy = mesh_lib.mesh_grid_shape(mesh)
            n, k = a.shape
            m = b.shape[1]
            pn, pk = padding.padded_shape((n, k), mesh)
            _, pm = padding.padded_shape((k, m), mesh)
            # the SAME itemsize choose_strategy_ex will gate the parent
            # with (review r6): an itemsize-4 hint on f64 operands
            # would steer the child toward a layout the parent's own
            # budget gate then refuses — the double loss again
            dt_out = infer_dtype(e, cfg, dtype_memo)
            isz = np.dtype(dt_out).itemsize if dt_out is not None else 4
            budget = cfg.hbm_budget_bytes
            right_ok = right_ok and admissible(
                "bmm_right", pn, pk, pm, gx, gy, itemsize=isz,
                hbm_budget_bytes=budget)
            left_ok = left_ok and admissible(
                "bmm_left", pn, pk, pm, gx, gy, itemsize=isz,
                hbm_budget_bytes=budget)
        return ("row" if right_ok else None,    # parent bmm_right viable
                "col" if left_ok else None)     # parent bmm_left viable
    return (None,) * len(e.children)


def _same_operand(u: MatExpr, v: MatExpr) -> bool:
    """Do two expression nodes denote the SAME evaluated operand?
    True for a shared DAG node, or for distinct leaf wrappers of
    one matrix object (the DSL creates a fresh leaf per .expr())."""
    if u is v or u.uid == v.uid:
        return True
    return (u.kind == "leaf" and v.kind == "leaf"
            and u.attrs["matrix"] is v.attrs["matrix"])


def gram_operand(node: MatExpr) -> Optional[Tuple[str, MatExpr]]:
    """("AtA", X) for the product ``t(X) * X`` and ("AAt", X) for ``X *
    t(X)`` of one evaluated operand, else None. A stamped precision tier
    owns the product's numerics: such a product is no Gram to anyone."""
    if node.attrs.get("precision_tier") is not None:
        return None
    return _gram_sides(node)


def _gram_sides(node: MatExpr) -> Optional[Tuple[str, MatExpr]]:
    """:func:`gram_operand` by the operands alone, whatever tier is
    stamped on the product."""
    l, r = node.children
    if l.kind == "transpose" and _same_operand(l.children[0], r):
        return "AtA", r
    if r.kind == "transpose" and _same_operand(r.children[0], l):
        return "AAt", l
    return None


def own_rows_operands(node: MatExpr, mesh: Mesh
                      ) -> Optional[Tuple[Tuple[MatExpr, int],
                                          Tuple[MatExpr, int]]]:
    """((a, ca), (b, cb)) of a product: its operands AS THEY LIE (a
    transposed one is the table under the transpose) and the dimension
    each is contracted over — or None on a mesh where not both are
    leaves cut over ALL devices along that dimension (``_layout_of``
    "row" for 0, "col" for 1): then a device's share of one is not the
    other's, and the product is no sum of the devices' own. A leaf's
    layout is honoured as it lies: a canonical ``P(x, y)`` table is not
    re-laid by rows behind its owner's back, and plans as it did."""
    l, r = node.children
    a, ca = (l.children[0], 0) if l.kind == "transpose" else (l, 1)
    b, cb = (r.children[0], 1) if r.kind == "transpose" else (r, 0)
    if mesh.size > 1 and any(_layout_of(t, mesh) != ("row", "col")[c]
                             for t, c in ((a, ca), (b, cb))):
        return None
    return (a, ca), (b, cb)


def long_in_place(node: MatExpr, mesh: Mesh,
                  config: Optional[MatrelConfig] = None,
                  dtype_memo: Optional[dict] = None) -> bool:
    """Is this product of a MESH plan multiplied where its operands lie
    (:data:`OWN_ROWS`: strategies.over_own_rows around the panelled
    contraction of one device)? A float32 product over a contraction of
    LONG_CONTRACTION or more whose operands both lie cut over all
    devices along it (:func:`own_rows_operands`), no strategy forced
    and no precision tier on it; a Gram under ``matmul_precision``
    "high" keeps ops/gram.py's two-pass split over a ranked strategy.
    Asked before the product is stamped (choose_strategy_ex, and
    annotate_strategies for the transpose beneath it), from what can be
    observed: shapes, dtypes, the leaves' own layouts, the config."""
    cfg = config or default_config()
    if (mesh.size == 1 or cfg.strategy_override != "auto"
            or node.children[0].shape[1] < LONG_CONTRACTION):
        return False
    found = own_rows_operands(node, mesh)
    if found is None or (cfg.matmul_precision == "high"
                         and gram_operand(node) is not None):
        return False
    tier = (node.attrs["precision_tier"] if "precision_tier" in node.attrs
            else choose_precision_tier(node, cfg, dtype_memo=dtype_memo))
    return tier in (None, "f32") and all(
        infer_dtype(t, cfg, dtype_memo) == np.float32 for t, _ in found)


def long_gram(node: MatExpr, mesh: Mesh,
              config: Optional[MatrelConfig] = None,
              dtype_memo: Optional[dict] = None
              ) -> Optional[Tuple[str, MatExpr]]:
    """:func:`gram_operand` of a product that is lowered as the upper
    block triangle of its panels (strategies.gram_in_panels): a float32
    Gram whose contraction is LONG_CONTRACTION or longer, multiplied
    where its table lies (:func:`in_place_strategy`: under the plain
    local dot on one device; on a mesh, a device at a time over a table
    that lies cut over all devices along the contraction, the stamp
    :data:`OWN_ROWS` that :func:`long_in_place` alone hands out).
    ``matmul_precision`` "high" lowers every float32 Gram as
    ops/gram.py's two-pass split instead: none is one there. The ONE
    test the stamp (``gram_tiles``, annotate_strategies) and the
    lowering (executor._long_contraction) both ask, for one device and
    for the mesh, so they cannot disagree."""
    gram = gram_operand(node)
    if (gram is None
            or (config or default_config()).matmul_precision == "high"
            or node.attrs.get("strategy", "xla") != in_place_strategy(mesh)
            or node.children[0].shape[1] < LONG_CONTRACTION
            or infer_dtype(gram[1], config, dtype_memo) != np.float32):
        return None
    return gram


def _lies_by_columns(leaf: MatExpr) -> bool:
    """Does a dense leaf's array lie with its ROWS on the lanes (the
    chip's default for a tall float32 table, ``major_to_minor=(1, 0)``:
    PR 31), so that ``x.T`` under ``jit`` is a bitcast and no copy? Off
    the chip (interpreted kernels) the question has no cost and the
    answer is yes."""
    from matrel_tpu.config import on_tpu
    if not on_tpu():
        return True
    try:
        layout = leaf.attrs["matrix"].data.format.layout
    except (AttributeError, KeyError, RuntimeError):
        return False        # a described shape; a computed operand
    return tuple(getattr(layout, "major_to_minor", ())) == (1, 0)


def gram_kernel_plan(node: MatExpr, mesh: Mesh,
                     config: Optional[MatrelConfig] = None,
                     dtype_memo: Optional[dict] = None) -> Optional[dict]:
    """How a LONG Gram (``t(X) * X`` or ``X * t(X)`` over a contraction
    of LONG_CONTRACTION or more) runs, from what can be observed
    (shapes, dtypes, how the table lies, the mesh, the config), or None
    for any other product: the facts ``last_plan()["products"]`` carries
    as ``gram_kernel`` and the ``matrel.gram.plan`` record of the
    lowering. ``one_read`` true: ONE ``pallas_call`` over X where it
    lies (ops/gram_kernel.py: row tiles of ``tile_rows``, the upper
    triangle in blocks of 128, ``tiles`` of them of those the square
    holds, ``rider`` columns of a second product inside it:
    :func:`_stamp_gram_riders` counts them). False: the loop of
    :func:`long_gram` (strategies.gram_in_panels, four XLA dots a panel)
    or whatever else the product is lowered as, and ``why_not`` names
    what declined — a mesh (a device at a time the loop multiplies
    where the table lies, whatever its layout), a forced strategy, ``X
    * t(X)``, a table that is not float32, ``matmul_precision`` "high"
    (ops/gram.py owns that Gram), a precision tier or SLA, no Pallas
    executor, columns in ragged sublanes, under two blocks of 128 (one
    tile and nothing to skip) or wider than VMEM holds, fewer rows than
    one lane chunk, a table that lies by rows (its transpose would be a
    second table; one device pads nothing). Of the dense long-contraction
    family (:func:`long_gram`, :func:`mmchain_plan`): the ONE test the
    stamp (annotate_strategies), the riders' room (:func:`gram_riders`)
    and the lowering (executor._long_contraction) all go by, so they
    cannot disagree."""
    from matrel_tpu.config import pallas_enabled
    cfg = config or default_config()
    found = _gram_sides(node)
    if found is None or node.children[0].shape[1] < LONG_CONTRACTION:
        return None
    side, x = found
    facts = {"one_read": False, "rider": 0, "tile_rows": 0, "tiles": []}
    why = None
    if mesh.size > 1:
        why = "mesh"
    elif cfg.strategy_override != "auto":
        why = "strategy_override"
    elif side != "AtA":
        why = "contraction"
    elif infer_dtype(x, cfg, dtype_memo) != np.float32:
        why = "dtype"
    elif cfg.matmul_precision == "high":
        why = "matmul_precision"
    elif (cfg.precision_sla != "default"
          or node.attrs.get("precision_tier") is not None):
        why = "precision_sla"
    elif not pallas_enabled(cfg):
        why = "pallas_off"
    else:
        # only a process that runs Pallas kernels pays their import
        # (1.3 s: a mesh's plan and a CPU session never get here)
        from matrel_tpu.ops import gram_kernel
        n, k = x.shape
        tile = gram_kernel.tile_rows(n)
        if (k % 8 or gram_kernel.blocks(k) < 2
                or gram_kernel.vmem_bytes(k, tile) > gram_kernel.VMEM_LIMIT):
            why = "columns"
        elif not tile:
            why = "rows"
        elif not _lies_by_columns(x):
            why = "layout"
        else:
            facts.update(one_read=True, tile_rows=tile,
                         tiles=list(gram_kernel.tiles(k)))
    if why is not None:
        facts["why_not"] = why
    return facts


def mmchain_plan(node: MatExpr, mesh: Mesh,
                 config: Optional[MatrelConfig] = None,
                 dtype_memo: Optional[dict] = None) -> dict:
    """How an ``mmchain`` node ``t(X) * (w .* (X * v))`` runs, from what
    can be observed (shapes, dtypes, how the table lies, the mesh, the
    config): the facts ``last_plan()["mmchain"]`` and the
    ``matrel.mmchain.plan`` spans carry. ``one_read`` true: ONE pass
    over X in row tiles of ``tile_rows`` (ops/mmchain.py,
    ``bytes_read`` the table once). False: the two products it was
    written as, X read twice, and ``why_not`` names what declined — a
    mesh (the chain on a mesh is the two ``cpmm_rows`` products of PR
    39), a ``v`` wider than the kernel's one column, a table or vector
    that is not float32, a ``matmul_precision`` other than "highest"
    or a precision SLA (both own the products' numerics), a forced
    strategy, no Pallas executor, a table too wide for two tiles in
    VMEM or with ragged sublanes, fewer rows than one lane chunk, a
    table that lies by rows (its transpose would be a second table).
    Of the dense long-contraction family (:func:`long_in_place`,
    :func:`long_gram`): the ONE test the stamp (annotate_strategies)
    and the lowering (executor._mmchain) both go by, so they cannot
    disagree."""
    from matrel_tpu.config import pallas_enabled
    from matrel_tpu.core import padding
    from matrel_tpu.ops import mmchain as mmchain_lib
    cfg = config or default_config()
    x, v = node.children[:2]
    n, k = x.shape
    tile = mmchain_lib.tile_rows(n)
    why = None
    if mesh.size > 1:
        why = "mesh"
    elif cfg.strategy_override != "auto":
        why = "strategy_override"
    elif v.shape[1] != 1:
        why = "v_columns"
    elif any(infer_dtype(c, cfg, dtype_memo) != np.float32
             for c in node.children):
        why = "dtype"
    elif cfg.matmul_precision != "highest":
        why = "matmul_precision"
    elif cfg.precision_sla != "default":
        why = "precision_sla"
    elif not pallas_enabled(cfg):
        why = "pallas_off"
    elif k % 8 or k > mmchain_lib.COLS_MAX:
        why = "table_columns"
    elif not tile:
        why = "rows"
    elif (padding.padded_shape(x.shape, mesh) != tuple(x.shape)
          or not _lies_by_columns(x)):
        why = "layout"
    reads = 1 if why is None else 2
    facts = {"rows": n, "cols": k, "weighted": bool(node.attrs["weighted"]),
             "tile_rows": tile if why is None else 0,
             "bytes_read": reads * 4 * n * k, "one_read": why is None}
    if why is not None:
        facts["why_not"] = why
    return facts


def unfused_mmchain(node: MatExpr, facts: dict) -> MatExpr:
    """The two products an ``mmchain`` node was written as, for a plan
    that :func:`mmchain_plan` declined: ``t(X) * (w .* (X * v))`` as the
    rule found it, stamped by annotate_strategies and lowered like any
    other products (the parent's program), with the declined ``facts``
    on the outer one for ``last_plan()`` and the spans."""
    x, v, *w = node.children
    q = matmul(x, v)
    if w:
        q = elemwise("mul", w[0], q)
    return matmul(transpose(x), q).with_attrs(mmchain=facts)


def _nodes(root: MatExpr) -> List[MatExpr]:
    """Every node under ``root`` once, in evaluation order."""
    out, seen = [], set()

    def walk(n: MatExpr):
        if n.uid in seen:
            return
        seen.add(n.uid)
        for c in n.children:
            walk(c)
        out.append(n)

    walk(root)
    return out


def gram_riders(root: MatExpr, mesh: Mesh,
                config: Optional[MatrelConfig] = None,
                dtype_memo: Optional[dict] = None
                ) -> Dict[int, Tuple[MatExpr, MatExpr]]:
    """{uid: (gram, rider)}, under both nodes' uids, of the products of
    one plan that are lowered as ONE pass over their table
    (strategies.gram_in_panels / gram_in_tiles ``rhs``): a
    :func:`long_gram` ``t(X) * X`` and a ``t(X) * B`` over the very
    same ``X``, float32 and
    multiplied where they lie like it (:func:`in_place_strategy`),
    whose ``B`` fits the lanes the Gram's last block
    column leaves spare in its MXU tile (strategies.gram_rider_room: 24
    columns at k = 1000, none where k is a multiple of 128; under the
    kernel the spare rows of its ragged last block,
    ops/gram_kernel.rider_room: the same 24) and is not
    computed from the Gram. A Gram carries one product, the first in
    evaluation order. Like :func:`long_gram`, the ONE test the stamps
    (``gram_rides`` / ``rides_gram``, annotate_strategies) and the
    lowering (executor._long_contraction) both ask."""
    nodes = [n for n in _nodes(root) if n.kind == "matmul"]
    pairs: Dict[int, Tuple[MatExpr, MatExpr]] = {}
    from matrel_tpu.core import padding
    own = in_place_strategy(mesh)
    for gram in nodes:
        found = long_gram(gram, mesh, config, dtype_memo)
        if found is None or found[0] != "AtA":
            continue
        # the arrays a mesh's lowering sees are the padded ones; the
        # kernel's room is its ragged last block's spare rows
        pk = padding.padded_shape(gram.shape, mesh)[0]
        if gram_kernel_plan(gram, mesh, config, dtype_memo)["one_read"]:
            from matrel_tpu.ops import gram_kernel
            room = gram_kernel.rider_room(pk)
        else:
            room = gram_rider_room(pk)
        if not room:
            continue
        for rider in nodes:
            l, r = rider.children
            if (rider.uid not in pairs and l.kind == "transpose"
                    and _same_operand(l.children[0], found[1])
                    and padding.padded_shape(r.shape, mesh)[1] <= room
                    and rider.attrs.get("strategy", "xla") == own
                    and rider.attrs.get("precision_tier") is None
                    and infer_dtype(r, config, dtype_memo) == np.float32
                    and gram not in _nodes(r)):
                pairs[gram.uid] = pairs[rider.uid] = (gram, rider)
                break
    return pairs


def own_rows_stamps(node: MatExpr, mesh: Mesh) -> dict:
    """What the observability says of a product multiplied where its
    operands lie on a mesh (:data:`OWN_ROWS`): ``operand_layout`` (how
    the left table lies: "row" under a transpose), ``devices`` (whose
    partial products the all-reduce adds), ``rows_a_device`` (of the
    contraction, as padded) and ``reduce_bytes`` (what the all-reduce
    moves a device: the partial product; :func:`_stamp_gram_riders`
    corrects it where the product is a Gram's block triangle with its
    riders, or rides one and moves nothing of its own)."""
    from matrel_tpu.core import padding
    (a, ca), _ = own_rows_operands(node, mesh)
    pn, pk = padding.padded_shape(node.children[0].shape, mesh)
    pm = padding.padded_shape(node.shape, mesh)[1]
    return {"operand_layout": ("row", "col")[ca], "devices": mesh.size,
            "rows_a_device": pk // mesh.size,
            "reduce_bytes": (gram_reduce_bytes(pn)
                             if gram_operand(node) is not None
                             else 4 * pn * pm)}


def _stamp_gram_riders(root: MatExpr, mesh: Mesh,
                       config: Optional[MatrelConfig],
                       dtype_memo: dict) -> MatExpr:
    """The plan with :func:`gram_riders`' pairs stamped, the engagement
    counter of the one-pass lowering: ``gram_rides`` (the columns
    carried) on the Gram, ``rides_gram`` on the product that has no
    loop of its own. A plan with no pair comes back as it is."""
    from matrel_tpu.core import padding
    pairs = gram_riders(root, mesh, config, dtype_memo)
    if not pairs:
        return root
    done: Dict[int, MatExpr] = {}

    def rebuild(n: MatExpr) -> MatExpr:
        if n.uid not in done:
            kids = tuple(rebuild(c) for c in n.children)
            out = n if all(a is b for a, b in zip(kids, n.children)) \
                else n.with_children(kids)
            if n.uid in pairs:
                gram, rider = pairs[n.uid]
                out = out.with_attrs(**(
                    {"rides_gram": True} if n is rider
                    else {"gram_rides": rider.shape[1]}))
                how = n.attrs["gram_kernel"] if n is gram else {}
                if how.get("one_read"):     # the rider is the kernel's
                    out = out.with_attrs(gram_kernel={
                        **how, "rider": rider.shape[1]})
                if "reduce_bytes" in out.attrs:
                    # the pair's ONE all-reduce is the Gram's
                    out = out.with_attrs(reduce_bytes=0 if n is rider
                                         else gram_reduce_bytes(
                                             *padding.padded_shape(
                                                 rider.shape, mesh)))
            done[n.uid] = out
        return done[n.uid]

    return rebuild(root)


def _folded_transpose(node: MatExpr, parent: Optional[MatExpr],
                      mesh: Mesh,
                      config: Optional[MatrelConfig] = None,
                      dtype_memo: Optional[dict] = None) -> bool:
    """A transpose that is no array: on one device the product that
    reads it contracts over the other dimension (``x.T`` under a
    ``dot`` is the dot's dimension numbers; compiled for a v5e, t(X)·X
    of a 10 GB table has 5 MB of temporaries), and so does a mesh's
    product that multiplies its operands where they lie
    (:func:`long_in_place`). Under any other product of a mesh it is a
    re-lay of the shards and stays counted."""
    return (node.kind == "transpose" and parent is not None
            and parent.kind == "matmul"
            and (mesh.size == 1
                 or long_in_place(parent, mesh, config, dtype_memo)))


def annotate_strategies(e: MatExpr, mesh: Mesh,
                        config: Optional[MatrelConfig] = None,
                        _dtype_memo: Optional[dict] = None,
                        _layout_memo: Optional[dict] = None,
                        _consumer_hint: Optional[str] = None,
                        _root_scale: float = 1.0,
                        _root_swap: bool = False,
                        _integral_memo: Optional[dict] = None,
                        _held: Optional[float] = None,
                        _parent: Optional[MatExpr] = None) -> MatExpr:
    """Bottom-up pass stamping attrs['strategy'] on every matmul node
    and attrs['replicate'] on every row/col index join. One dtype memo
    and one layout memo are threaded through the whole pass and seeded
    as each rewritten node is produced, so every choose_strategy
    dtype/layout lookup is O(1). ``_consumer_hint`` carries the parent's
    in-place-consumable layout down to BOTH join-scheme and matmul
    strategy near-ties (_hint_tiebreak); a matmul whose output layout
    flows to the plan ROOT is additionally charged the fraction
    ``_root_scale`` (_child_root_scale) of the canonical-output reshard
    its lowering really pays there (_root_reshard_cost).

    ``_held`` is the plan-level memory reckoning (PR 27): the bytes one
    device keeps while this subtree is evaluated, beside what the
    subtree makes itself — the leaves the whole plan reads (reckoned
    once, at the root) and the values of elder siblings waiting for
    their parent. A matmul's candidates are gated on it plus its own
    computed operands (choose_strategy_ex ``alive_bytes``); a ``solve``
    and a ``transpose`` that is materialised are stamped with the peak
    at them too (``hbm_plan_bytes``, and ``refused_hbm`` where it is
    over the limit). On one device no strategy is chosen, but the plan
    is reckoned all the same: there a transpose that a product reads is
    the dot's own dimension numbers and no array, and is not
    counted."""
    memo = {} if _dtype_memo is None else _dtype_memo
    lmemo = {} if _layout_memo is None else _layout_memo
    imemo = {} if _integral_memo is None else _integral_memo
    if e.kind == "mmchain" and "mmchain" not in e.attrs:
        how = mmchain_plan(e, mesh, config, memo)
        # declined: the two products it was written as, planned below
        e = (e.with_attrs(mmchain=how) if how["one_read"]
             else unfused_mmchain(e, how))
    is_root = _held is None
    if is_root:
        # the root's value is the program's output: its buffer is
        # handed over before anything runs, so it is alive beside
        # every product below the root (the chip counted it so: 6 GiB
        # of tables, 2 GiB of output and 8 GiB of temporaries were 16)
        _held = plan_resident_bytes(e, mesh, config)
        if e.children:
            _held += device_bytes(e, mesh, config, memo, lmemo)
    hints = _child_layout_hints(e, mesh, config, dtype_memo=memo)
    swap = _root_swap != (e.kind == "transpose")   # odd transposes flip
    new_children = []
    alive = _held
    for i, (c, h) in enumerate(zip(e.children, hints)):
        nc = annotate_strategies(c, mesh, config, memo, lmemo, h,
                                 _child_root_scale(e, i, _root_scale),
                                 swap, imemo, alive, e)
        if (nc.children
                and not _folded_transpose(nc, e, mesh, config, memo)
                and not fused_sampled(c, e, mesh, config)):
            # a computed value, kept for e
            alive += device_bytes(nc, mesh, config, memo, lmemo)
        new_children.append(nc)
    new_children = tuple(new_children)
    if any(nc is not oc for nc, oc in zip(new_children, e.children)):
        e = e.with_children(new_children)
    if e.kind == "matmul" and "precision_tier" not in e.attrs:
        # tier BEFORE strategy: the strategy choice's HBM-feasibility
        # gate reads the stamped tier's operand itemsize. Under the
        # "default" SLA choose_precision_tier returns None and nothing
        # is stamped — the bit-identity contract (plan snapshots
        # unchanged, zero new attrs). The shared integral memo keeps
        # the integrality/magnitude walks O(nodes) over deep chains.
        tier = choose_precision_tier(e, config, dtype_memo=memo,
                                     integral_memo=imemo)
        if tier is not None:
            e = e.with_attrs(precision_tier=tier)
    if e.kind == "matmul" and "strategy" not in e.attrs:
        # cost-model provenance (docs/COST_MODEL.md): only requested —
        # and only stamped — under coeff_planner_enable, so default
        # plans carry zero new attrs (the bit-identity snapshot
        # contract)
        detail = ({} if config is not None
                  and config.coeff_planner_enable else None)
        hbm = {}
        # the root's own value was counted into ``_held`` for the
        # products below it; at the root it is the product's output
        own_alive = (alive - device_bytes(e, mesh, config, memo, lmemo)
                     if is_root else alive)
        strat, source = choose_strategy_ex(e, mesh, config,
                                           dtype_memo=memo,
                                           layout_memo=lmemo,
                                           root_output=_root_scale > 0.0,
                                           root_transposed=_root_swap,
                                           consumer_hint=_consumer_hint,
                                           root_scale=_root_scale,
                                           cost_detail=detail,
                                           alive_bytes=own_alive,
                                           hbm_detail=hbm)
        stamp = {"strategy": strat, "strategy_source": source}
        if detail is not None and detail.get("cost"):
            stamp["cost_model"] = detail["cost"]
        if hbm:
            # what the lowering needs (the panelled rmm's panel counts,
            # stamped only where there is more than one: plans that fit
            # whole carry no new attr) and what the observability reads
            # (plan.meta / the plan.strategy spans: hbm_report)
            if strat == "rmm" and tuple(hbm["panels"]) != (1, 1):
                stamp["panels"] = tuple(hbm["panels"])
            if hbm["moves_under_dot"]:
                stamp["moves_under_dot"] = hbm["moves_under_dot"]
            if hbm["refused_hbm"]:
                stamp["refused_hbm"] = tuple(hbm["refused_hbm"])
            stamp["hbm_plan_bytes"] = hbm["hbm_plan_bytes"]
            coo = coo_product(e, mesh, config)
            if coo is not None:
                # a sparse operand's tables are its plan's and were not
                # among the leaves reckoned: what the product keeps
                # beside its dense operand and its output is the SpMV
                # plan's tables and panel, or the leaf's dense copy
                need = int(hbm["hbm_plan_bytes"] + coo["bytes"])
                limit = mesh_lib.hbm_limit_bytes(mesh, config)
                stamp.update(coo_product=coo, hbm_plan_bytes=need)
                stamp.pop("refused_hbm", None)
                if 0 < limit < need:
                    stamp["refused_hbm"] = (coo["chosen"],)
        if strat == OWN_ROWS:
            stamp.update(own_rows_stamps(e, mesh))
        e = e.with_attrs(**stamp)
        how = gram_kernel_plan(e, mesh, config, memo)
        if how is not None:
            # a long Gram: which lowering multiplies it, and why
            e = e.with_attrs(gram_kernel=how)
        if long_gram(e, mesh, config, memo) is not None:
            # engagement counter of the triangle lowering: block
            # products a panel (the loop) or a row tile (the kernel)
            # multiplies (computed, of), for plan.meta and the
            # plan.strategy spans (hbm_report)
            e = e.with_attrs(gram_tiles=tuple(how["tiles"])
                             if how["one_read"]
                             else gram_tiles(e.shape[0]))
        if strat == "spgemm":
            # registry dispatch (ops/kernel_registry.py): stamp WHICH
            # kernel the S×S lowering will run — chosen from the
            # registry's cost estimates over the operand pair's
            # structure class, overridden by a measured autotune
            # winner (the MV106 "measured"-stamp precedent) or the
            # config forcing knob. The lowering honors the stamp and
            # MV110 verifies it; the shared chooser
            # (executor.spgemm_kernel_choice) is the single source of
            # truth so the three can never drift.
            from matrel_tpu import executor as _exec
            kid, struct, ksrc = _exec.spgemm_kernel_choice(e, config,
                                                           mesh)
            e = e.with_attrs(spgemm_kernel=kid,
                             spgemm_structure=struct,
                             spgemm_kernel_source=ksrc)
    if (e.kind in ("solve", "transpose") and "hbm_plan_bytes" not in e.attrs
            and not _folded_transpose(e, _parent, mesh, config, memo)):
        # no strategy to choose, but an array the device has to hold:
        # what is alive at it (its computed operands among them), its
        # own value (the root's was counted into ``_held``), and what
        # a solve's factorisation allocates beside them
        need = alive + (0.0 if is_root
                        else device_bytes(e, mesh, config, memo, lmemo))
        if e.kind == "solve":
            need += solve_transient_bytes(*e.shape)
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        e = e.with_attrs(hbm_plan_bytes=int(need),
                         **({"refused_hbm": (e.kind,)}
                            if 0 < limit < need else {}))
    if (e.kind == "sampled" and "hbm_plan_bytes" not in e.attrs
            and not fused_sampled(e, _parent, mesh, config)):
        # lowered as the element-wise node it was written as: its own
        # value, the leaf's dense copy and the dense product whole
        need = (alive + sampled_dense_bytes(e, mesh)
                + (0.0 if is_root
                   else device_bytes(e, mesh, config, memo, lmemo)))
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        e = e.with_attrs(hbm_plan_bytes=int(need),
                         **({"refused_hbm": ("sampled",)}
                            if 0 < limit < need else {}))
    if e.kind == "semiring" and "hbm_plan_bytes" not in e.attrs:
        # the leaf's tables are its plan's and were not among the
        # leaves reckoned: what is alive (the column it reads among
        # it), its own column, and what answers it keeps
        how = semiring_product(e, mesh, config)
        need = (alive + how["bytes"]
                + (0.0 if is_root
                   else device_bytes(e, mesh, config, memo, lmemo)))
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        e = e.with_attrs(hbm_plan_bytes=int(need), coo_product=how,
                         **({"refused_hbm": (how["chosen"],)}
                            if 0 < limit < need else {}))
    if e.kind == "mmchain" and "hbm_plan_bytes" not in e.attrs:
        # one pass over the resident table: what is alive (the table
        # and the vectors among it), its own column, the kernel's
        # lanes of partial sums and, of a ragged table, the tail's copy
        rows, cols = e.children[0].shape
        tile = e.attrs["mmchain"]["tile_rows"]
        need = (alive + 4.0 * cols * (2 * 128 + rows % tile)
                + (0.0 if is_root
                   else device_bytes(e, mesh, config, memo, lmemo)))
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        e = e.with_attrs(hbm_plan_bytes=int(need),
                         **({"refused_hbm": ("mmchain",)}
                            if 0 < limit < need else {}))
    if e.kind in ("join_rows", "join_cols") and "replicate" not in e.attrs:
        e = e.with_attrs(replicate=choose_join_scheme(
            e, mesh, config, layout_memo=lmemo,
            consumer_hint=_consumer_hint))
    if is_root:
        e = _stamp_gram_riders(e, mesh, config, memo)
    infer_dtype(e, config, memo)     # seed this (possibly new-uid) node
    infer_layout(e, mesh, lmemo, config)
    return e


def hbm_report(root: MatExpr) -> list:
    """What the plan-level memory reckoning decided, read back from an
    ANNOTATED plan: one record a dense matmul, a solve and a
    materialised transpose, in evaluation order — ``node`` (its kind),
    ``shape``, ``chosen`` (the strategy; the kind where there is none
    to choose), ``refused_hbm``, ``panels`` (rows, columns),
    ``moves_under_dot`` (strategies.rmm_moves_under_dot: the moves a
    row panel of the panelled rmm hides under a dot),
    ``hbm_plan_bytes`` (the plan's peak on one device at that node),
    and on a long Gram lowered as its upper block triangle alone
    (:func:`long_gram`) ``gram_tiles``: the block products a panel
    multiplies, of those the square holds; where that Gram's loop
    carries a second product over its table (:func:`gram_riders`),
    ``gram_rides`` (the columns carried) on the Gram and ``rides_gram``
    on the product carried; on a mesh's product multiplied where its
    operands lie (``chosen`` :data:`OWN_ROWS`), :func:`own_rows_stamps`'
    ``operand_layout``, ``devices``, ``rows_a_device`` and
    ``reduce_bytes``; on a fused chain (``node`` "mmchain") and on the
    outer product of one that was un-fused, ``mmchain``:
    :func:`mmchain_plan`'s facts; on every long Gram, ``gram_kernel``:
    :func:`gram_kernel_plan`'s facts (``one_read`` where ONE kernel
    multiplies it, ``gram_tiles`` then the kernel's tiles; else
    ``why_not``)."""
    out = []
    for n in _nodes(root):
        if "hbm_plan_bytes" in n.attrs:
            out.append({"node": n.kind, "shape": list(n.shape),
                        "chosen": n.attrs.get("strategy", n.kind),
                        "refused_hbm": list(n.attrs.get("refused_hbm", ())),
                        "panels": list(n.attrs.get("panels", (1, 1))),
                        "moves_under_dot": n.attrs.get("moves_under_dot", 0),
                        "hbm_plan_bytes": n.attrs["hbm_plan_bytes"]})
            coo = n.attrs.get("coo_product")
            if coo is not None:
                # a coo_leaf product, or a semiring product over one:
                # what runs is the SpMV plan, the segment reduction or
                # the densified leaf, not a stamped dense strategy
                out[-1]["chosen"] = coo["chosen"]
                if "layout" in coo:
                    out[-1].update(layout=coo["layout"],
                                   panels=list(coo["panels"]))
                elif coo["chosen"] == "densify":
                    out[-1]["densified_bytes"] = int(coo["bytes"])
            if "gram_tiles" in n.attrs:
                out[-1]["gram_tiles"] = list(n.attrs["gram_tiles"])
            for stamp in ("gram_rides", "rides_gram", "operand_layout",
                          "devices", "rows_a_device", "reduce_bytes",
                          "mmchain", "gram_kernel"):
                if stamp in n.attrs:
                    out[-1][stamp] = n.attrs[stamp]
    return out


def refuse_over_limit(roots, mesh: Mesh,
                      config: Optional[MatrelConfig] = None) -> None:
    """Raise :class:`PlanMemoryError` for a plan that holds a node
    stamped over the limit with no way left to run it, naming the first
    such node in evaluation order. On one device there is one way to
    run a node, and what the reckoning adds up are arrays that have to
    exist — the leaves, the intermediates alive, the node's own value —
    so a plan over the limit cannot run, and says so here instead of in
    the compiler or the allocator. On a mesh a refused strategy leaves
    others to choose from, and where none fits the one that needs least
    is handed over (``refused_hbm`` says so): its verdict rests on an
    estimate of the strategy's transient. A materialised transpose or a
    solve over the limit is an array that has to exist, there as on one
    device, and refuses the plan (:func:`_refuse_on_mesh`)."""
    if mesh.size != 1:
        _refuse_on_mesh(roots, mesh, config)
        return
    for root in roots:
        n = next((n for n in _nodes(root) if n.attrs.get("refused_hbm")),
                 None)
        if n is None:
            continue
        own = int(device_bytes(n, mesh, config))
        limit = mesh_lib.hbm_limit_bytes(mesh, config)
        coo = n.attrs.get("coo_product")
        if n.kind == "sampled":
            leaf, a, b = n.children
            raise PlanMemoryError(
                f"plan refused before tracing: the sampled node "
                f"{leaf.shape[0]}x{leaf.shape[1]} "
                f"{'./' if n.attrs['op'] == 'div' else '.*'} "
                f"({a.shape[0]}x{a.shape[1]} * {b.shape[0]}x{b.shape[1]}) "
                f"is no operand of a product that answers it at its "
                f"entries alone (one device, the compact-table executor, "
                f"a dense side of at most 128 columns), so it would "
                f"DENSIFY its element-sparse operand "
                f"({leaf.attrs['matrix'].nnz:,} entries) and multiply "
                f"the dense product whole: "
                f"{int(sampled_dense_bytes(n, mesh)):,} bytes as float32 "
                f"beside its own value; with them the plan holds "
                f"{n.attrs['hbm_plan_bytes']:,} bytes on the one device, "
                f"over the limit of {limit:,} bytes. Multiply it by a "
                f"narrow dense side (t(X) * (S ./ (A * B)), "
                f"(S ./ (A * B)) * Y), which needs neither.")
        if coo is not None and coo["chosen"] == "densify":
            leaf = next(c for c in n.children if c.kind == "coo_leaf")
            raise PlanMemoryError(
                f"plan refused before tracing: the product "
                f"{n.children[0].shape[0]}x{n.children[0].shape[1]} * "
                f"{n.children[1].shape[0]}x{n.children[1].shape[1]} would "
                f"DENSIFY its element-sparse operand "
                f"({leaf.shape[0]}x{leaf.shape[1]}, "
                f"{leaf.attrs['matrix'].nnz:,} entries: "
                f"{int(coo['bytes']):,} bytes as float32) because "
                f"{coo['why']}; with it the plan holds "
                f"{n.attrs['hbm_plan_bytes']:,} bytes on the one device, "
                f"over the limit of {limit:,} bytes. Bracket the query so "
                f"that the sparse matrix meets a dense side of at most "
                f"128 columns (A * (B * C), not (A * B) * C), or multiply "
                f"column panels of the dense side.")
        raise PlanMemoryError(
            f"plan refused before tracing: at {n.kind} "
            f"{n.shape[0]}x{n.shape[1]} ({own:,} bytes of its own) the "
            f"plan holds {n.attrs['hbm_plan_bytes']:,} bytes on the one "
            f"device (the tables it reads, the intermediates alive, this "
            f"node's value), over the limit of {limit:,} bytes "
            f"(min of hbm_budget_bytes and the device's bytes_limit). "
            f"Write the query so that no intermediate is that wide, or "
            f"raise hbm_budget_bytes if the device really has the room "
            f"(0 turns the reckoning off).")


def rows_delta_plan(table, batch_rows: int, views, partners,
                    mesh: Mesh,
                    config: Optional[MatrelConfig] = None) -> dict:
    """The one-device reckoning of a rows delta applied in place
    (serve/ivm.py: executor.rows_update, then executor.rows_patch a
    view): ``table`` (the dense matrix whose rows are replaced, ONE
    copy: the update donates it), the batch and the rows that leave
    (two c x m arrays), every view that follows the delta at its two
    words and the correction it is patched with (a third array of its
    shape, while its patch runs), and the ``partners`` whose rows the
    patches read (resident tables, each once). Raises
    :class:`PlanMemoryError` where that is over the device's limit, by
    name and before anything is uploaded; returns the record the
    ``matrel.delta.update`` span and the delta's summary carry."""
    from matrel_tpu.ir.expr import leaf

    def one(m) -> int:
        return int(device_bytes(leaf(m), mesh, config))

    itemsize = np.dtype(table.data.dtype).itemsize
    seen, resident = set(), 0
    for m in [table, *partners]:
        if id(m) not in seen:
            seen.add(id(m))
            resident += one(m)
    batch = 2 * int(batch_rows) * int(table.shape[1]) * itemsize
    kept = sum(3 * one(v) for v in views)
    need = resident + batch + kept
    limit = mesh_lib.hbm_limit_bytes(mesh, config)
    if limit > 0 and need > limit:
        raise PlanMemoryError(
            f"rows delta refused before anything was uploaded: replacing "
            f"{batch_rows:,} rows of the table {table.shape[0]}x"
            f"{table.shape[1]} in place holds {need:,} bytes on the one "
            f"device (the table and the tables its views' patches read "
            f"{resident:,}; the batch and the rows that leave {batch:,}; "
            f"{len(views)} view(s) at two words and a correction "
            f"{kept:,}), over the limit of {limit:,} bytes (min of "
            f"hbm_budget_bytes and the device's bytes_limit).")
    return {"hbm_plan_bytes": need, "table_bytes": one(table)}


def _refuse_on_mesh(roots, mesh: Mesh,
                    config: Optional[MatrelConfig] = None) -> None:
    """:func:`refuse_over_limit` for a mesh: raise where a materialised
    transpose or a solve is over the limit. The message names every
    node whose verdict left nothing to choose — those, and the products
    that are over the limit under the strategy they were handed — the
    first in evaluation order at its head."""
    over = [n for root in roots for n in _nodes(root)
            if n.attrs.get("strategy", n.kind)
            in n.attrs.get("refused_hbm", ())]
    if all(n.kind == "matmul" for n in over):
        return
    limit = mesh_lib.hbm_limit_bytes(mesh, config)

    def says(n):
        under = (f" under {n.attrs['strategy']} (refused: "
                 f"{', '.join(n.attrs['refused_hbm'])})"
                 if n.kind == "matmul" else "")
        return (f"{n.kind} {n.shape[0]}x{n.shape[1]}{under}: "
                f"{n.attrs['hbm_plan_bytes']:,} bytes")

    raise PlanMemoryError(
        f"plan refused before tracing: on the "
        f"{'x'.join(str(g) for g in mesh_lib.mesh_grid_shape(mesh))} mesh "
        f"the plan would hold on ONE device (its shards of the tables it "
        f"reads, the intermediates alive, the node's value and the "
        f"strategy's transient), at {'; at '.join(says(n) for n in over)}"
        f" — over the limit of {limit:,} bytes (min of hbm_budget_bytes "
        f"and the device's bytes_limit), and no strategy that fits was "
        f"left to choose. A transposed table is a second table, and "
        f"cpmm, rmm and summa re-lay their operands: a tall table whose "
        f"Gram or t(X) * y is wanted is multiplied where it lies when it "
        f"is registered by rows over all devices "
        f"(PartitionSpec(('x', 'y'), None)). Raise hbm_budget_bytes if "
        f"the devices really have the room (0 turns the reckoning off).")


def matmul_decisions(root: MatExpr, mesh: Mesh,
                     config: Optional[MatrelConfig] = None) -> list:
    """Per-matmul planner-decision records for an ANNOTATED plan — the
    observability feed (obs/ event log, explain(analyze=True)): for
    every matmul node, the chosen strategy, WHY (strategy_source), the
    operand layouts the choice saw, the model's estimated per-device
    ICI bytes for that strategy under those layouts, and the multiply's
    FLOPs. Pure read — never re-chooses; shared DAG nodes appear once.
    Dispatches the byte model ignores (sparse/COO fast paths) are
    tagged ``dispatch`` so readers don't attribute ICI estimates to
    lowerings that bypass the strategy."""
    cfg = config or default_config()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    topo = mesh_lib.mesh_topology(mesh, cfg)
    wts = topo.axis_weights
    lmemo: dict = {}
    dmemo: dict = {}
    out: list = []
    seen: set = set()
    # fused-region prepass (ir/fusion.py stamps live on region ROOTS,
    # which may be elementwise/agg nodes): map each anchor matmul's uid
    # to its region record so the matmul's decision carries the chosen
    # boundary — fused_region, member census, est saved dispatches/HBM
    # — into the obs event stream. Empty with fusion off (no stamps):
    # zero extra fields, the bit-identity obs contract.
    fused_of: dict = {}
    fseen: set = set()

    def fwalk(node: MatExpr):
        if node.uid in fseen:
            return
        fseen.add(node.uid)
        for c in node.children:
            fwalk(c)
        a_uid = node.attrs.get("fused_anchor")
        if "fused_region" in node.attrs and a_uid is not None:
            fused_of[a_uid] = node.attrs

    fwalk(root)

    def walk(n: MatExpr):
        if n.uid in seen:
            return
        seen.add(n.uid)
        for c in n.children:
            walk(c)
        if n.kind != "matmul":
            return
        a, b = n.children
        nn, kk = a.shape
        mm = b.shape[1]
        rec = {"uid": n.uid, "dims": [nn, kk, mm],
               "strategy": n.attrs.get("strategy", "xla"),
               "source": n.attrs.get("strategy_source", "unknown"),
               "flops": 2.0 * nn * kk * mm}
        cm = n.attrs.get("cost_model")
        if cm:
            # WHICH cost model priced the ranking: "measured" (learned
            # parallel/coeffs.py coefficients) or "analytic" (closed
            # forms) — absent with coeff_planner_enable off, the
            # bit-identity obs contract (docs/COST_MODEL.md)
            rec["cost"] = cm
        tier = n.attrs.get("precision_tier")
        if tier is not None:
            # the chosen precision tier + what it really costs/promises
            # (obs events, explain(analyze=True), history --summary,
            # the drift auditor's tier-keyed calibration rows)
            rec["precision_tier"] = tier
            rec["est_passes"] = TIER_PASSES.get(tier)
            rec["est_tier_cost"] = tier_matmul_cost(
                tier, nn, kk, mm,
                a.density if a.density is not None else 1.0,
                b.density if b.density is not None else 1.0) \
                if tier in TIER_COMPUTE_UNITS else None
            rec["est_rel_err"] = TIER_EPS.get(tier)
        # result-cache reuse (serve/): an operand that entered planning
        # as a materialized-result leaf never re-pays its subplan — the
        # decision record says which side(s) got that credit, so the
        # obs roll-up can attribute layout credits to cache reuse
        rc_ops = [bool(c.kind == "leaf" and c.attrs.get("result_cache"))
                  for c in n.children]
        if any(rc_ops):
            rec["rc_operands"] = rc_ops
        # cross-query CSE reuse (serve/mqo.py): an operand fed by a
        # batch-shared hoisted interior gets the same layout credit as
        # a result-cache leaf — the decision record says which side(s)
        cse_ops = [bool(c.kind == "leaf" and c.attrs.get("cse"))
                   for c in n.children]
        if any(cse_ops):
            rec["cse_operands"] = cse_ops
        if _spgemm_matmul(n, cfg):
            # the S×S tile-intersection dispatch: record the estimated
            # FLOPs/HBM bytes it avoids vs the densify fallback — the
            # obs/ surface (query events, explain(analyze=True),
            # history roll-up) where the SpGEMM win is visible
            from matrel_tpu import executor as _exec
            rec["dispatch"] = "spgemm"
            rec.update(_exec.spgemm_estimates(n, cfg))
            # registry dispatch record: WHICH kernel runs, over WHAT
            # structure class, and whether a measurement or the cost
            # estimate picked it — the obs surface (query events,
            # explain(analyze=True), history's kernel census, the
            # drift auditor's spgemm:<kernel_id> calibration rows)
            kid = n.attrs.get("spgemm_kernel")
            struct = n.attrs.get("spgemm_structure")
            ksrc = n.attrs.get("spgemm_kernel_source")
            if kid is None:
                kid, struct, ksrc = _exec.spgemm_kernel_choice(
                    n, cfg, mesh)
            rec["kernel_id"] = kid
            rec["structure_class"] = struct
            rec["kernel_source"] = ksrc
            rec["est_vs_measured"] = ("measured" if ksrc == "measured"
                                      else "estimate")
        elif any(c.kind == "coo_leaf" for c in n.children):
            # checked BEFORE sparse_leaf — Lowerer._matmul's order: a
            # mixed coo×sparse matmul runs the COO SpMV path (review r6)
            rec["dispatch"] = ("coo_spmv" if _coo_narrow_matmul(n)
                               else "densify")
        elif any(c.kind == "sparse_leaf" for c in n.children):
            rec["dispatch"] = "spmm"
        else:
            la = infer_layout(a, mesh, lmemo, cfg)
            lb = infer_layout(b, mesh, lmemo, cfg)
            rec["layouts"] = [la, lb]
            try:
                # est_ici_bytes stays in RAW byte-equivalents (flat
                # weights) whatever the mesh: its consumers (history's
                # MiB column, cross-session comparisons) sum it as
                # bytes moved, and a weighted value would inflate by
                # the weight ratio (review r7)
                rec["est_ici_bytes"] = comm_cost(
                    rec["strategy"], nn, kk, mm, a.density, b.density,
                    gx, gy, a_layout=la, b_layout=lb,
                    alpha_bytes=cfg.comm_alpha_bytes)
                # per-axis decomposition of the same bill (raw bytes,
                # pre-weight): the auditable record of how much of the
                # decision's traffic rides each mesh axis — history's
                # roll-up turns this into the slow-axis regression
                # signal (docs/TOPOLOGY.md)
                rec["est_axis_bytes"] = list(comm_cost_axes(
                    rec["strategy"], nn, kk, mm, a.density, b.density,
                    gx, gy, a_layout=la, b_layout=lb, weights=wts))
                if not topo.uniform:
                    # the quantity the weighted ranking actually
                    # minimised — a separate field, separate unit
                    rec["est_weighted_cost"] = comm_cost(
                        rec["strategy"], nn, kk, mm, a.density,
                        b.density, gx, gy, a_layout=la, b_layout=lb,
                        alpha_bytes=cfg.comm_alpha_bytes, weights=wts)
                    rec["axis_weights"] = list(wts)
                    rec["topology_source"] = topo.source
                if cfg.reshard_peak_budget_bytes > 0:
                    # the staged reshard moves this decision's lowering
                    # will actually run (parallel/reshard.py — the ONE
                    # derivation the executor and MV109 share): step
                    # kinds, raw per-axis bytes, worst per-device peak
                    from matrel_tpu.parallel import reshard as _resh
                    rr = _resh.moves_record(_resh.staged_matmul_moves(
                        n, mesh, cfg, lmemo, dmemo))
                    if rr is not None:
                        rec["reshard"] = rr
            except ValueError:       # an override string the model
                rec["est_ici_bytes"] = None   # doesn't know
        ivm = root.attrs.get("ivm_patch")
        if isinstance(ivm, dict):
            # this plan IS a delta patch (serve/ivm.py stamps the root;
            # docs/IVM.md): every decision record carries the pricing
            # that chose patching over recompute, so the obs surfaces
            # (query events, explain(analyze=True), the history IVM
            # roll-up) can audit the patch-vs-recompute call the way
            # they audit strategy choices
            rec["delta_rule"] = ivm.get("rule")
            rec["delta_est_saved_flops"] = ivm.get("est_saved_flops")
        fr = fused_of.get(n.uid)
        if fr is not None:
            # this matmul anchors a fused region: the decision record
            # carries the chosen boundary so obs/history/drift see it
            # (the drift auditor keys these rows ``fused:<sig>`` — a
            # miscalibrated fused estimate must not poison the
            # per-strategy calibration rows). setdefault on the HBM
            # field: a SpGEMM anchor's est_saved_hbm_bytes already
            # means "saved vs densify" and keeps that meaning.
            rec["fused_region"] = fr.get("fused_region")
            rec["fused_census"] = dict(fr.get("fused_census") or {})
            rec["est_saved_dispatches"] = fr.get(
                "fused_saved_dispatches")
            rec.setdefault("est_saved_hbm_bytes",
                           fr.get("fused_saved_hbm_bytes"))
        out.append(rec)

    walk(root)
    return out
