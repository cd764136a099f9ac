"""Dimension + sparsity statistics propagation (SURVEY.md §2
"Statistics / sparsity estimation").

The reference propagates (nRows, nCols, nnz) bottom-up through the Catalyst
plan and feeds the estimates to the matrix-chain DP and physical strategy
choice. Same role here: pure-Python estimates over the MatExpr tree, no
devices involved.

Estimation model (standard independence assumptions, as in MatFast/MatRel):
  density(A·B)   ≈ 1 - (1 - dA*dB)^k   (k = contraction dim)
  density(A+B)   ≈ min(1, dA + dB)
  density(A⊙B)  ≈ dA * dB
  transpose/scalar-mul preserve density; scalar-add densifies; a
  sampled node (S op (A·B), ir/expr.py) has its leaf S's structure; a
  semiring node ((max | min, ×) of a leaf and a column) is one dense
  column, its (n × m) join never a node; an mmchain node (t(X)·(w ∘
  (X·v))) is dense (k × m), its (n × m) intermediate never a node.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple


def density_of(nnz: Optional[int], shape: Tuple[int, int]) -> float:
    if nnz is None:
        return 1.0
    n = shape[0] * shape[1]
    return min(1.0, nnz / n) if n else 0.0


def nnz_from_density(d: float, shape: Tuple[int, int]) -> int:
    return int(round(min(1.0, max(0.0, d)) * shape[0] * shape[1]))


def matmul_density(da: float, db: float, k: int) -> float:
    """Probability an output entry is nonzero given k independent trials."""
    p = da * db
    if p <= 0.0:
        return 0.0
    if p >= 1.0:
        return 1.0
    # 1-(1-p)^k, computed stably.
    return -math.expm1(k * math.log1p(-p))


def add_density(da: float, db: float) -> float:
    return min(1.0, da + db)


def elemmul_density(da: float, db: float) -> float:
    return da * db


def matmul_cost(
    n: int, k: int, m: int, da: float = 1.0, db: float = 1.0
) -> float:
    """Estimated FLOP cost of an (n×k)·(k×m) multiply.

    Sparsity-aware as in the reference's chain DP: work scales with the
    expected number of nonzero multiply-accumulate pairs.
    """
    return 2.0 * n * k * m * da * db


def solve_cost(k: int, m: int) -> float:
    """Estimated FLOP cost of solving a (k×k) system against m
    right-hand sides: one LU factorisation, 2k³/3, and the forward and
    backward substitutions, 2k²·m. The chain DP's step for an inverse
    factor (ir/chain.py): the width m the solve is taken against is
    what the association decides."""
    return 2.0 * k ** 3 / 3.0 + 2.0 * float(k) * k * m


HBM_FLOPS_PER_BYTE = 120.0
"""Blend factor converting HBM bytes into f32-FLOP-equivalents for the
precision-tier cost model (planner.tier_matmul_cost): a v5e chip
retires ~98e12 f32-class FLOP/s against ~819 GB/s of HBM, so ~120 f32
FLOPs buy the time of one HBM byte. Order-of-magnitude, like
COMM_FLOPS_PER_BYTE below — the term makes bandwidth-bound shapes rank
half-width bf16 operand traffic honestly against pass counts."""


def integral_abs_bound(node, memo: dict = None):
    """Conservative upper bound on max|entry| of a provably-integral
    expression, or None when no bound can be proven. The magnitude
    half of the integer-exactness story: :func:`infer_integral` proves
    entries are integers, this proves HOW BIG — the int-tier chooser
    only auto-picks int32 when the accumulated product
    k·bound(A)·bound(B) provably fits the int32 accumulator, so
    "exact" can never silently wrap (the review-round overflow hole).
    Leaf bounds come from ``BlockMatrix.int_abs_max`` (recorded by
    from_numpy for integral sources); anything unproven is None and
    the chooser conservatively keeps f32. Duck-typed like
    infer_integral; pass a shared ``memo`` to amortise across a
    planning pass."""
    if memo is None:
        memo = {}

    def walk(n):
        key = ("bound", n.uid)
        if key in memo:
            return memo[key]
        memo[key] = got = _bound(n)
        return got

    def _mix(vals, fn):
        if any(v is None for v in vals):
            return None
        return float(fn(vals))

    def _bound(n):
        k = n.kind
        if k in ("leaf", "sparse_leaf", "coo_leaf"):
            v = getattr(n.attrs.get("matrix"), "int_abs_max", None)
            return float(v) if v is not None else None
        if k in ("transpose", "select_index", "select_block", "vec"):
            return walk(n.children[0])
        if k == "select_value":
            return _mix([walk(n.children[0]),
                         abs(float(n.attrs.get("fill", 0.0)))], max)
        if k == "matmul":
            ba, bb = walk(n.children[0]), walk(n.children[1])
            if ba is None or bb is None:
                return None
            return float(n.children[0].shape[1]) * ba * bb
        if k == "elemwise":
            op = n.attrs.get("op")
            vals = [walk(c) for c in n.children]
            if op in ("add", "sub"):
                return _mix(vals, sum)
            if op == "mul":
                return _mix(vals, lambda v: v[0] * v[1])
            if op in ("min", "max"):
                return _mix(vals, max)
            return None
        if k == "scalar":
            op, v = n.attrs["op"], abs(float(n.attrs["value"]))
            b = walk(n.children[0])
            if b is None:
                return None
            if op == "add":
                return b + v
            if op == "mul":
                return b * v
            if op == "pow" and v >= 1:
                return b ** v
            return None
        if k == "agg":
            kind, axis = n.attrs["agg"], n.attrs["axis"]
            c = n.children[0]
            b = walk(c)
            if kind == "count":
                return float(max(c.shape[0] * c.shape[1], 1))
            if b is None:
                return None
            if kind in ("max", "min"):
                return b
            if kind == "sum":
                terms = {"row": c.shape[1], "col": c.shape[0],
                         "all": c.shape[0] * c.shape[1],
                         "diag": min(c.shape)}[axis]
                return float(terms) * b
            return None
        if k == "rank1":
            ba, bu, bv = (walk(c) for c in n.children)
            if None in (ba, bu, bv):
                return None
            return ba + bu * bv
        if k == "sampled":
            # S .* (A·B) at S's entries; a quotient has no bound
            if n.attrs.get("op") != "mul":
                return None
            bs, ba, bb = (walk(c) for c in n.children)
            if None in (bs, ba, bb):
                return None
            return bs * float(n.children[1].shape[1]) * ba * bb
        if k == "semiring":
            # an extremum of S[i, j] · x[j] (or the 0 of a missing cell)
            bs, bx = (walk(c) for c in n.children)
            if None in (bs, bx):
                return None
            return bs * bx
        if k == "mmchain":
            # sum over n rows of x · (w ·) (sum over k columns of x · v)
            vals = [walk(c) for c in n.children]
            if None in vals:
                return None
            rows, cols = n.children[0].shape
            bound = float(rows) * float(cols) * vals[0] * vals[0] * vals[1]
            return bound * vals[2] if len(vals) == 3 else bound
        if k == "join_index":
            mk = n.attrs.get("merge_kind")
            vals = [walk(c) for c in n.children]
            if mk == "add":
                return _mix(vals, sum)
            if mk == "mul":
                return _mix(vals, lambda v: v[0] * v[1])
            if mk in ("left", "right"):
                return _mix(vals, max)
            return None
        return None

    return walk(node)


def infer_integral(node, memo: dict = None) -> bool:
    """Is this expression provably INTEGER-VALUED (every entry an exact
    integer representable in f32)? The static inference that lets an
    "exact" precision SLA route integer-shaped workloads (triangle
    counting, PageRank iteration counts, boolean semiring joins) onto
    the exact int32/int8 MXU tiers instead of conservatively pinning
    f32 (docs/PRECISION.md). Duck-typed over MatExpr (kind/children/
    attrs) — expr.py imports this module, not vice versa.

    Conservative by construction: False whenever exactness cannot be
    proven, so a float workload can never be silently truncated. Leaf
    integrality comes from ``BlockMatrix.integral`` (auto-detected for
    integer/bool numpy sources, or declared by the caller). Pass a
    shared ``memo`` dict to amortise the walk across a planning pass
    (the infer_dtype precedent — per-node fresh memos made deep-chain
    annotation O(nodes²), review r8). The memo is shared with
    :func:`integral_abs_bound` (distinct key spaces)."""
    if memo is None:
        memo = {}

    def walk(n) -> bool:
        key = ("int", n.uid)
        got = memo.get(key)
        if got is None:
            memo[key] = got = _integral(n)
        return got

    def _integral(n) -> bool:
        k = n.kind
        if k in ("leaf", "sparse_leaf", "coo_leaf"):
            return bool(getattr(n.attrs.get("matrix"), "integral",
                                False))
        if k in ("transpose", "select_index", "select_block", "vec"):
            return walk(n.children[0])
        if k == "select_value":
            # non-matching entries become the fill value
            fill = float(n.attrs.get("fill", 0.0))
            return fill.is_integer() and walk(n.children[0])
        if k == "matmul":
            # a bf16-tiered product of integers is NOT integer-valued:
            # the bf16 passes round (the tier is stamped bottom-up
            # before any consumer asks, so the claim is read here)
            if n.attrs.get("precision_tier") in ("bf16x1", "bf16x3"):
                return False
            return all(walk(c) for c in n.children)
        if k == "elemwise":
            if n.attrs.get("op") == "div":
                return False
            return all(walk(c) for c in n.children)
        if k == "scalar":
            op, v = n.attrs["op"], float(n.attrs["value"])
            if op in ("add", "mul"):
                return v.is_integer() and walk(n.children[0])
            if op == "pow":
                return v.is_integer() and v >= 1 and walk(n.children[0])
            return False
        if k == "agg":
            kind = n.attrs["agg"]
            if kind == "count":
                return True          # nonzero counts are integers
            if kind in ("sum", "max", "min"):
                return walk(n.children[0])
            return False             # avg divides
        if k == "rank1":
            return all(walk(c) for c in n.children)
        if k == "sampled":
            return (n.attrs.get("op") == "mul"
                    and all(walk(c) for c in n.children))
        if k in ("semiring", "mmchain"):
            return all(walk(c) for c in n.children)
        if k in ("join_index", "join_rows", "join_cols", "join_value"):
            # structured merges are closed over integers; callables are
            # black boxes
            if n.attrs.get("merge_kind") in ("left", "right", "add",
                                             "mul"):
                return all(walk(c) for c in n.children)
            return False
        return False

    return walk(node)


COMM_FLOPS_PER_BYTE = 1000.0
"""Blend factor converting ICI bytes into FLOP-equivalents for the
chain DP's step cost: a v5e chip retires ~200e12 bf16 FLOP/s against
~200 GB/s of per-link ICI, so ~1000 MXU FLOPs buy the time of one
ICI byte. Order-of-magnitude is what matters — the term breaks
FLOP-ties toward the cheaper collective bill."""


#: Layout codes shared with native/chain_dp.cc's layout-aware DP — the
#: C side receives operand layouts as int8 with exactly this mapping.
LAYOUT_CODES = {"2d": 0, "row": 1, "col": 2, "rep": 3, "other": 4}


def comm_proxy_layout(n: int, k: int, m: int, da: float, db: float,
                      gx: int, gy: int, itemsize: int = 4,
                      la: str = "2d", lb: str = "2d",
                      weights: tuple = (1.0, 1.0)
                      ) -> tuple:
    """(cheapest per-device ICI cost, output layout of the argmin
    strategy) for an (n×k)·(k×m) multiply on a gx×gy mesh — the chain
    DP's comm term, PER-LAYOUT (round 5) and now TOPOLOGY-WEIGHTED
    (round 7: ``weights`` are the per-axis inverse-bandwidth weights of
    core/mesh.MeshTopology, so the DP ranks parenthesisations by what
    their collectives cost on a hierarchical ICI/DCN mesh, not by flat
    bytes).

    Delegates to planner.comm_cost per strategy (ONE Python source of
    truth for the per-layout closed forms — review r5; the only copy is
    the C mirror in native/chain_dp.cc, equivalence-fuzzed by
    test_native) but still applies NO admissibility or broadcast-
    threshold gates (the planner picks the real strategy per multiply
    afterwards). Tie-break order (bmm_right, bmm_left, cpmm, rmm) MUST
    stay in sync with native/chain_dp.cc's comm_proxy_layout."""
    p = gx * gy
    if p <= 1:
        return 0.0, "2d"
    from matrel_tpu.parallel import planner   # lazy: no import cycle
    best, lay = None, "2d"
    for strat, out_lay in (("bmm_right", "row"), ("bmm_left", "col"),
                           ("cpmm", "2d"), ("rmm", "2d")):
        c = planner.comm_cost(strat, n, k, m, da, db, gx, gy,
                              itemsize, la, lb, weights=weights)
        if best is None or c < best:
            best, lay = c, out_lay
    return best, lay


def comm_proxy(n: int, k: int, m: int, da: float, db: float,
               gx: int, gy: int, itemsize: int = 4) -> float:
    """comm_proxy_layout at the canonical "2d" layouts — the
    layout-blind view kept for callers that predate the layout-aware
    DP (and for the native matrel_chain_dp_comm symbol's semantics)."""
    return comm_proxy_layout(n, k, m, da, db, gx, gy, itemsize)[0]


def chain_step_cost(n: int, k: int, m: int, da: float, db: float,
                    gx: int = 1, gy: int = 1) -> float:
    """DP step cost: sparsity-aware FLOPs + the collective bill in
    FLOP-equivalents. With gx·gy == 1 this is exactly matmul_cost, so
    single-device plans are unchanged."""
    return (matmul_cost(n, k, m, da, db)
            + COMM_FLOPS_PER_BYTE * comm_proxy(n, k, m, da, db, gx, gy))


def chain_step_cost_layout(n: int, k: int, m: int, da: float, db: float,
                           gx: int, gy: int, la: str, lb: str,
                           weights: tuple = (1.0, 1.0),
                           flop_scale: float = 1.0,
                           comm_weight=None) -> tuple:
    """(step cost, output layout): chain_step_cost with per-layout,
    topology-weighted comm terms — the layout-aware DP's step (round 5;
    weights round 7). ``flop_scale`` (round 8) is the precision tier's
    relative MXU time per MAC (planner.sla_compute_factor): a "fast"
    bf16 query retires its FLOPs faster, so the comm term weighs
    relatively MORE and the DP may legitimately prefer a different
    parenthesisation. 1.0 (the default, and every "default"-SLA query)
    is bit-identical to the pre-tier step cost.

    ``comm_weight`` overrides :data:`COMM_FLOPS_PER_BYTE` with a
    MEASURED flops-per-byte conversion for this step's shape class
    (parallel/coeffs.chain_comm_weights — the drift-calibrated ratio
    of interconnect time to MXU time on the live backend, consulted
    under ``config.coeff_planner_enable``; docs/COST_MODEL.md). None
    (the default, and every cold class) keeps the analytic constant —
    bit-identical."""
    comm, lay = comm_proxy_layout(n, k, m, da, db, gx, gy, la=la, lb=lb,
                                  weights=weights)
    w = COMM_FLOPS_PER_BYTE if comm_weight is None else float(comm_weight)
    return (matmul_cost(n, k, m, da, db) * flop_scale
            + w * comm), lay


def matmul_out_nnz(
    n: int, k: int, m: int, nnz_a: Optional[int], nnz_b: Optional[int]
) -> Optional[int]:
    if nnz_a is None and nnz_b is None:
        return None
    da = density_of(nnz_a, (n, k))
    db = density_of(nnz_b, (k, m))
    return nnz_from_density(matmul_density(da, db, k), (n, m))


# -- sparsity-structure classification (ops/kernel_registry.py) -------------
# The structure-specialized SpGEMM kernels (JITSPMM's thesis,
# arXiv:2312.05639) need to KNOW the shape of the sparsity, not just
# its density. These closed-form classifiers read the block edge lists
# the engine already computes (BlockSparseMatrix.block_rows/cols; COO
# leaves bucketed at the dispatch block size) and bin each operand into
# one of the STRUCTURE_CLASSES. Host-only numpy, no devices — the same
# contract as everything else in this module.


#: Structure-class vocabulary, most-specific first. "generic" is the
#: conservative fallback every boundary case must land in.
STRUCTURE_CLASSES = ("row_band", "clustered_tile", "powerlaw_coo",
                     "generic")

#: row_band: p90 of |tile offset (col - row) - median offset| must sit
#: inside this fraction of the grid (or within BAND_SPREAD_TILES tiles
#: absolutely — a tridiagonal or 5-point stencil band qualifies on any
#: grid size).
BAND_SPREAD_FRAC = 0.08
BAND_SPREAD_TILES = 2.0

#: powerlaw_coo: max per-block-row tile count >= this multiple of the
#: MEDIAN (over OCCUPIED rows — the median is hub-robust where the
#: mean is not: on a small grid two hub rows lift the mean enough to
#: hide themselves), with at least POWERLAW_MIN_ROWS occupied rows so
#: a 2-row matrix can't fake a hub.
POWERLAW_SKEW = 6.0
POWERLAW_MIN_ROWS = 8

#: clustered_tile: mean occupied-4-neighbor count must beat the
#: uniform-random expectation (4 * block density) by this factor AND
#: clear an absolute floor; above CLUSTER_MAX_DENSITY everything is
#: neighborly and the class says nothing.
CLUSTER_NEIGHBOR_LIFT = 3.0
CLUSTER_NEIGHBOR_MIN = 1.0
CLUSTER_MAX_DENSITY = 0.5

#: Below this many tiles no classifier has evidence — generic.
STRUCTURE_MIN_TILES = 4


def classify_block_structure(rows, cols, gr: int, gc: int) -> str:
    """Structure class of one sparse operand from its block edge lists.

    ``rows``/``cols`` are the tile coordinates (int arrays, any order,
    duplicates allowed) on a (gr, gc) tile grid. Checks most-specific
    first — row_band, then powerlaw_coo, then clustered_tile — and
    falls back to "generic" whenever the evidence is thin (fewer than
    STRUCTURE_MIN_TILES tiles, degenerate grids, boundary histograms
    that clear no threshold)."""
    import numpy as np
    rows = np.asarray(rows, np.int64).ravel()
    cols = np.asarray(cols, np.int64).ravel()
    if rows.size < STRUCTURE_MIN_TILES or gr < 2 or gc < 2:
        return "generic"
    if rows.size != cols.size:
        return "generic"
    ntiles = len(np.unique(rows * gc + cols))
    density = ntiles / float(gr * gc)

    # row_band: tiles hug one (possibly shifted) diagonal — the TILE
    # offset col - row concentrates around its median. Measured in
    # tiles: the absolute floor admits stencil-width bands on any
    # grid, the fractional term scales with flagship grids.
    off = (cols - rows).astype(np.float64)
    med = float(np.median(off))
    dev = float(np.quantile(np.abs(off - med), 0.90))
    if dev <= max(BAND_SPREAD_TILES, BAND_SPREAD_FRAC * min(gr, gc)):
        return "row_band"

    # powerlaw_coo: per-block-row tile counts skewed (the PageRank /
    # hub-graph shape) — a few rows own most of the tiles.
    occ = np.bincount(rows, minlength=gr)
    occ = occ[occ > 0]
    if (occ.size >= POWERLAW_MIN_ROWS
            and float(occ.max())
            >= POWERLAW_SKEW * float(np.median(occ))):
        return "powerlaw_coo"

    # clustered_tile: occupied tiles form dense blobs — the mean count
    # of occupied 4-neighbors beats the uniform-random expectation.
    # Vectorized (sorted-key membership): a million-tile coo_leaf is
    # classified in numpy time, not a Python per-tile loop.
    if density <= CLUSTER_MAX_DENSITY:
        keys = np.unique(rows * gc + cols)
        col = keys % gc
        neigh = (
            (np.isin(keys + 1, keys) & (col < gc - 1)).sum()
            + (np.isin(keys - 1, keys) & (col > 0)).sum()
            + np.isin(keys + gc, keys).sum()
            + np.isin(keys - gc, keys).sum())
        mean_neigh = float(neigh) / max(keys.size, 1)
        if (mean_neigh >= CLUSTER_NEIGHBOR_MIN
                and mean_neigh >= CLUSTER_NEIGHBOR_LIFT * 4.0 * density):
            return "clustered_tile"
    return "generic"


def pair_structure_class(class_a: str, class_b: str) -> str:
    """Structure class of an S×S operand PAIR — what the SpGEMM kernel
    actually runs over. Conservative: a specialized kernel is only
    nominated when BOTH operands share its home structure (A·A-shaped
    graph workloads, band×band chains); any mix falls back to
    "generic", where the legacy kernels stand."""
    if class_a == class_b and class_a in STRUCTURE_CLASSES:
        return class_a
    return "generic"


# -- block-granular SpGEMM estimates (ops/spgemm.py dispatch + pricing) -----


def block_density(elem_density: float, block_size: int) -> float:
    """Probability a block_size×block_size tile holds ≥1 nonzero, under
    the same independence assumption as matmul_density — lifts an
    ELEMENT density (COO leaves) to the BLOCK granularity the SpGEMM
    tile-intersection reasons at. Same stable 1-(1-p)^k form."""
    if elem_density <= 0.0:
        return 0.0
    if elem_density >= 1.0:
        return 1.0
    return -math.expm1(block_size * block_size
                       * math.log1p(-elem_density))


def spgemm_pairs_estimate(nnzb_a: float, nnzb_b: float, kb: int) -> float:
    """Expected (A-tile, B-tile) intersection pairs for a blocked
    S×S multiply with kb contraction block-columns, tiles uniformly
    scattered: each A tile in contraction column c meets the
    ~nnzb_b/kb B tiles of block-row c."""
    return nnzb_a * (nnzb_b / max(kb, 1))


def spgemm_saved_estimate(nnzb_a: float, nnzb_b: float,
                          kb: int, k: int, m: int, bs: int,
                          itemsize: int = 4) -> dict:
    """Estimated work the SpGEMM dispatch avoids vs the densify
    fallback (SpMM over a DENSIFIED right operand — executor.py's S×S
    fallthrough): FLOPs of 2·nnzb_a·bs²·m against 2·pairs·bs³, and the
    HBM bytes of the dense (k, m) operand that is never materialised.
    Feeds planner.matmul_decisions → obs/ query events."""
    pairs = spgemm_pairs_estimate(nnzb_a, nnzb_b, kb)
    flops_densify = 2.0 * nnzb_a * bs * bs * m
    flops_spgemm = 2.0 * pairs * bs * bs * bs
    return {
        "est_pairs": pairs,
        "est_saved_flops": max(0.0, flops_densify - flops_spgemm),
        "est_saved_hbm_bytes": max(
            0.0, float(k) * m * itemsize - nnzb_b * bs * bs * itemsize),
    }
