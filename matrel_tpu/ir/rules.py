"""Algebraic rewrite rules — the MatfastOptimizer rule batch
(SURVEY.md §2 "Optimizer: rewrite rules", §3.2).

Rules, mirroring the reference's Catalyst batch:
  R1 double-transpose elimination:      (Aᵀ)ᵀ → A
  R2 transpose push-down:               (A·B)ᵀ → Bᵀ·Aᵀ ;
     (A+B)ᵀ → Aᵀ+Bᵀ ; (sA)ᵀ → s(Aᵀ) ; vec/agg interplay
  R3 aggregation push-down into multiply:
     rowSum(A·B) → A·rowSum(B) ; colSum(A·B) → colSum(A)·B
     sum(A·B)    → colSum(A)·rowSum(B)
     trace(A·B)  → sum(A ⊙ Bᵀ)
     rowSum(Aᵀ)  → colSum(A)ᵀ ; colSum(Aᵀ) → rowSum(A)ᵀ
     sum(sA)     → s·sum(A) ; sum(A+B) → sum(A)+sum(B)
  R4 scalar folding: s1·(s2·A) → (s1·s2)·A ; s1+(s2+A) → (s1+s2)+A ;
     1·A → A ; 0+A → A
  R5 selection push-down: index-σ commutes through elementwise ops and
     transposes (σ_rows through transpose becomes σ_cols).
  R6 matrix-chain DP reorder (chain.py), run after the structure-exposing
     rules above.
  R7 solve fusion: A⁻¹·B → solve(A,B) ; A·B⁻¹ → solve(Bᵀ,Aᵀ)ᵀ ;
     (A⁻¹)⁻¹ → A — the normal-equations pattern (XᵀX)⁻¹·Xᵀy never
     materialises an inverse. Inside a product chain the fusion is
     R6's: the DP prices an inverse factor as the solve it becomes and
     brackets it against the narrowest side, so (XᵀX)⁻¹·Xᵀ·y solves
     against Xᵀy (k×1) however it was typed, never against Xᵀ (k×N).
  R8 rank-1 multiply push-through: (A + u·vᵀ)·B → A·B + u·(vᵀ·B) and
     B·(A + u·vᵀ) → B·A + (B·u)·vᵀ — the outer product is never
     materialised inside a multiply chain (MatFast's rank-1 family).
  R9 sampled product: S ./ (A·B), S ∘ (A·B), (A·B) ∘ S with S an
     element-sparse leaf and A·B a dense product of a narrow inner
     dimension → sampled(op, S, A, B): the product is wanted only at
     S's entries (SystemML's wdivmm pattern; the KL-divergence NMF
     updates).
  R10 semiring product: rowmax / rowmin over a column join with merge
     "mul" of an element-sparse leaf S and one row t(x) →
     semiring(max | min, S, x): γ by row over ⋈ on the column index, the
     (max, ×) / (min, ×) sibling of the matrix product; the (n × m)
     join is never priced or built (a round of label propagation:
     LDBC Graphalytics' WCC).
  R11 fused chain: t(X)·(X·v) and t(X)·(w ∘ (X·v)) over one dense leaf
     X and a narrow v → mmchain(X, v[, w]) (SystemML's mmchain: a
     round of LinearRegCG, GLM, MLogreg, L2SVM). After the chain DP
     only: written flat, t(X)·X·p is a chain the DP brackets first.

Each rule is a bottom-up tree transform; the batch runs to fixpoint with a
bound, Catalyst-style.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.ir import chain as chain_lib
from matrel_tpu.ir.expr import (
    COO_NARROW_MAX, MMCHAIN_NARROW_MAX, SEMIRING_REDUCES, MatExpr, agg,
    elemwise, matmul, mmchain, sampled, scalar_op, select_index, semiring,
    transpose,
)

Rule = Callable[[MatExpr], Optional[MatExpr]]


def _rewrite_bottom_up(e: MatExpr, rule: Rule,
                       counts: Optional[dict] = None) -> MatExpr:
    new_children = tuple(_rewrite_bottom_up(c, rule, counts)
                         for c in e.children)
    if any(nc is not oc for nc, oc in zip(new_children, e.children)):
        e = e.with_children(new_children)
    out = rule(e)
    if out is not None and counts is not None:
        # per-rule hit counter — the observability feed (obs/ event
        # records carry these, the SparkListener rule-metrics analogue)
        name = getattr(rule, "__name__", str(rule))
        counts[name] = counts.get(name, 0) + 1
    return out if out is not None else e


# -- R1/R2: transpose rules -------------------------------------------------


def transpose_rules(e: MatExpr) -> Optional[MatExpr]:
    if e.kind != "transpose":
        return None
    (c,) = e.children
    if c.kind == "transpose":  # (Aᵀ)ᵀ → A
        return c.children[0]
    if c.kind == "matmul":  # (A·B)ᵀ → Bᵀ·Aᵀ
        a, b = c.children
        return matmul(transpose(b), transpose(a))
    if c.kind == "elemwise":  # (A∘B)ᵀ → Aᵀ∘Bᵀ  (shapes must match exactly)
        a, b = c.children
        if a.shape == b.shape:
            return elemwise(c.attrs["op"], transpose(a), transpose(b))
        return None
    if c.kind == "scalar":  # (s∘A)ᵀ → s∘(Aᵀ)
        return scalar_op(c.attrs["op"], transpose(c.children[0]), c.attrs["value"])
    if c.kind == "agg":
        # rowSumᵀ/colSumᵀ still just a vector; transposing agg output is
        # cheap — leave in place.
        return None
    return None


# -- R3: aggregation push-down ---------------------------------------------


def agg_pushdown(e: MatExpr) -> Optional[MatExpr]:
    if e.kind != "agg":
        return None
    kind, axis = e.attrs["agg"], e.attrs["axis"]
    (c,) = e.children
    if kind != "sum":
        return None  # max/min/count/avg don't distribute over matmul
    if c.kind == "matmul":
        a, b = c.children
        if axis == "row":   # rowSum(A·B) = A · rowSum(B)
            return matmul(a, agg(b, "sum", "row"))
        if axis == "col":   # colSum(A·B) = colSum(A) · B
            return matmul(agg(a, "sum", "col"), b)
        if axis == "all":   # sum(A·B) = colSum(A) · rowSum(B)
            return matmul(agg(a, "sum", "col"), agg(b, "sum", "row"))
        if axis == "diag":  # trace(A·B) = sum(A ⊙ Bᵀ)
            if a.shape == (b.shape[1], b.shape[0]):
                return agg(elemwise("mul", a, transpose(b)), "sum", "all")
        return None
    if c.kind == "transpose":
        inner = c.children[0]
        if axis == "row":   # rowSum(Aᵀ) = colSum(A)ᵀ
            return transpose(agg(inner, "sum", "col"))
        if axis == "col":
            return transpose(agg(inner, "sum", "row"))
        if axis in ("all", "diag"):  # invariant under transpose
            return agg(inner, "sum", axis)
        return None
    if c.kind == "scalar" and c.attrs["op"] == "mul":
        # sum(s·A) = s·sum(A) — shrink before scaling
        return scalar_op("mul", agg(c.children[0], "sum", axis), c.attrs["value"])
    if c.kind == "elemwise" and c.attrs["op"] in ("add", "sub") \
            and c.children[0].shape == c.children[1].shape:
        a, b = c.children
        return elemwise(c.attrs["op"], agg(a, "sum", axis), agg(b, "sum", axis))
    if c.kind == "rank1":
        # rowSum(A + u·vᵀ) = rowSum(A) + u·sum(v)   (MatFast's rank-1
        # update rules: never materialise the outer product for aggregates)
        a, u, v = c.children
        if axis == "row":
            return elemwise("add", agg(a, "sum", "row"),
                            matmul(u, agg(v, "sum", "all")))
        if axis == "col":
            return elemwise("add", agg(a, "sum", "col"),
                            matmul(agg(u, "sum", "all"), transpose(v)))
        if axis == "all":
            # sum(u·vᵀ) = sum(u)·sum(v)
            return elemwise("add", agg(a, "sum", "all"),
                            matmul(agg(u, "sum", "all"), agg(v, "sum", "all")))
    return None


# -- R4: scalar folding -----------------------------------------------------


def scalar_folding(e: MatExpr) -> Optional[MatExpr]:
    if e.kind != "scalar":
        return None
    op, v = e.attrs["op"], e.attrs["value"]
    (c,) = e.children
    if op == "mul" and v == 1.0:
        return c
    if op == "add" and v == 0.0:
        return c
    if op == "pow" and v == 1.0:
        return c
    if c.kind == "scalar" and c.attrs["op"] == op and op in ("mul", "add"):
        merged = v * c.attrs["value"] if op == "mul" else v + c.attrs["value"]
        return scalar_op(op, c.children[0], merged)
    return None


# -- R5: selection push-down ------------------------------------------------


def selection_pushdown(e: MatExpr) -> Optional[MatExpr]:
    if e.kind != "select_index":
        return None
    rows, cols = e.attrs["rows"], e.attrs["cols"]
    (c,) = e.children
    if c.kind == "transpose":
        # σ_rows(Aᵀ) = (σ_cols(A))ᵀ
        return transpose(select_index(c.children[0], rows=cols, cols=rows))
    if c.kind == "elemwise" and c.children[0].shape == c.children[1].shape:
        a, b = c.children
        return elemwise(
            c.attrs["op"],
            select_index(a, rows=rows, cols=cols),
            select_index(b, rows=rows, cols=cols),
        )
    if c.kind == "scalar" and c.attrs["op"] == "mul":
        return scalar_op("mul",
                         select_index(c.children[0], rows=rows, cols=cols),
                         c.attrs["value"])
    if c.kind == "matmul":
        # σ over rows touches only A's rows; over cols only B's cols:
        # σ_r,c(A·B) = σ_r(A) · σ_c(B)
        a, b = c.children
        if rows is not None or cols is not None:
            na = select_index(a, rows=rows, cols=None) if rows is not None else a
            nb = select_index(b, rows=None, cols=cols) if cols is not None else b
            if na is not a or nb is not b:
                return matmul(na, nb)
    return None


# -- R8: rank-1 multiply push-through ----------------------------------------


def rank1_pushdown(e: MatExpr) -> Optional[MatExpr]:
    """(A + u·vᵀ)·B → A·B + u·(vᵀ·B) ; B·(A + u·vᵀ) → B·A + (B·u)·vᵀ.

    MatFast's rank-1 family: never materialise the n×m outer product
    inside a multiply chain — the rewritten form costs two thin
    matmuls and an add, and exposes A·B to the chain DP. Always a win
    for genuine rank-1 updates (u: n×1, v: m×1)."""
    if e.kind != "matmul":
        return None
    a, b = e.children
    if a.kind == "rank1":
        base, u, v = a.children
        return elemwise("add", matmul(base, b),
                        matmul(u, matmul(transpose(v), b)))
    if b.kind == "rank1":
        base, u, v = b.children
        return elemwise("add", matmul(a, base),
                        matmul(matmul(a, u), transpose(v)))
    return None


# -- R9: sampled product -----------------------------------------------------


def sampled_product(e: MatExpr) -> Optional[MatExpr]:
    """S ./ (A·B) → sampled(div, S, A, B); S ∘ (A·B) and (A·B) ∘ S →
    sampled(mul, S, A, B), for a ``coo_leaf`` S and a product of two
    dense operands whose inner dimension is at most COO_NARROW_MAX
    (the two rows a sampled entry's dot gathers fill 128 lanes each).

    The result is zero wherever S is, so A·B is wanted at S's entries
    alone: under a product with a narrow dense side the executor never
    stores either whole. (A·B) ./ S is not matched (it is dense: x / 0
    is defined as 0 everywhere S is not). It fires by what it sees; the
    lowering decides where the node is answered fused."""
    if e.kind != "elemwise" or e.attrs["op"] not in ("div", "mul"):
        return None
    l, r = e.children
    if l.shape != r.shape:
        return None

    def narrow(p: MatExpr) -> bool:
        return (p.kind == "matmul"
                and 0 < p.children[0].shape[1] <= COO_NARROW_MAX
                and not any(c.kind in ("sparse_leaf", "coo_leaf")
                            for c in p.children))

    if l.kind == "coo_leaf" and narrow(r):
        return sampled(e.attrs["op"], l, *r.children)
    if e.attrs["op"] == "mul" and r.kind == "coo_leaf" and narrow(l):
        return sampled("mul", r, *l.children)
    return None


# -- R10: semiring product ---------------------------------------------------


def semiring_product(e: MatExpr) -> Optional[MatExpr]:
    """agg(max | min, row)(join_cols(S, b, "mul")) → semiring(max | min,
    S, t(b)) for a ``coo_leaf`` S (n × m) and ONE row b (1 × m), in
    either order of the join's operands (the merge commutes): the join
    pairs every cell S[i, j] with b[j] and the aggregate takes each
    row's extremum, so the (n × m) joined matrix is wanted a row at a
    time only. It fires by what it sees — a dense leaf, a merge that is
    not the structured "mul", a ``b`` of two rows (the join is then
    (2n × m)) or an aggregate along another axis lower as before, the
    join materialised under ``join_pair_cap_entries``. The mirror
    (colmax over a row join) is not matched: a transposed leaf would be
    a new matrix, and a new plan, every time the rule ran."""
    if (e.kind != "agg" or e.attrs["agg"] not in SEMIRING_REDUCES
            or e.attrs["axis"] != "row"):
        return None
    (j,) = e.children
    if j.kind != "join_cols" or j.attrs.get("merge_kind") != "mul":
        return None
    s, b = j.children
    if s.kind != "coo_leaf":
        s, b = b, s
    if s.kind != "coo_leaf" or b.shape[0] != 1 or b.kind == "coo_leaf":
        return None
    x = b.children[0] if b.kind == "transpose" else transpose(b)
    return semiring(e.attrs["agg"], s, x)


# -- R11: fused chain --------------------------------------------------------


def _same_leaf(a: MatExpr, b: MatExpr) -> bool:
    """Two dense leaves of one matrix object (the SQL front end makes a
    fresh leaf a mention; CSE runs after the rules)."""
    return (a.kind == b.kind == "leaf"
            and a.attrs["matrix"] is b.attrs["matrix"])


def mmchain_product(e: MatExpr) -> Optional[MatExpr]:
    """t(X)·(X·v) → mmchain(X, v); t(X)·(w ∘ (X·v)) and t(X)·((X·v) ∘ w)
    → mmchain(X, v, w), for ONE dense leaf X on both sides, ``v`` of at
    most MMCHAIN_NARROW_MAX columns and ``w`` a column of X's rows. It
    fires by what it sees; whether one pass over X answers the node is
    the planner's to say (planner.mmchain_plan: a mesh, a bfloat16
    table, another ``matmul_precision`` un-fuse it, by name). A Gram
    (t(X)·X), ``t(X)·y`` over a leaf ``y``, an element-sparse X and the
    normal equations' ``solve`` are not the pattern."""
    if e.kind != "matmul":
        return None
    xt, right = e.children
    if xt.kind != "transpose" or xt.children[0].kind != "leaf":
        return None
    x = xt.children[0]
    w = None
    if right.kind == "elemwise" and right.attrs["op"] == "mul":
        a, b = right.children
        if b.kind == "matmul":
            a, b = b, a
        if b.shape != (x.shape[0], 1):
            return None
        right, w = a, b
    if (right.kind != "matmul" or not _same_leaf(right.children[0], x)
            or not 0 < right.children[1].shape[1] <= MMCHAIN_NARROW_MAX
            or (w is not None and right.shape[1] != 1)):
        return None
    return mmchain(x, right.children[1], w)


# -- R7: solve fusion --------------------------------------------------------


def inverse_cancel(e: MatExpr) -> Optional[MatExpr]:
    """(A⁻¹)⁻¹ → A."""
    if e.kind == "inverse" and e.children[0].kind == "inverse":
        return e.children[0].children[0]
    return None


def solve_fusion(e: MatExpr) -> Optional[MatExpr]:
    """A⁻¹·B → solve(A, B); A·B⁻¹ → solve(Bᵀ, Aᵀ)ᵀ.

    The reference's normal-equations workload writes (XᵀX)⁻¹·(Xᵀy); an
    explicit inverse materialises n² solve results to use n·m of them
    and is less numerically stable than LU-solving against B directly.
    The forms are chain.join's: with the chain DP on, it is the DP that
    decides WHICH product an inverse factor is fused with, and this
    rule runs after it, on what no chain held.
    """
    if e.kind == "matmul" and any(c.kind == "inverse" for c in e.children):
        return chain_lib.join(*e.children)
    return None


_RULES: List[Rule] = [
    transpose_rules,
    agg_pushdown,
    scalar_folding,
    selection_pushdown,
    inverse_cancel,
    solve_fusion,
    rank1_pushdown,
    sampled_product,
    semiring_product,
    mmchain_product,
]
# ahead of the chain DP an inverse stays a factor of its chain: fused
# with its left-associated neighbour first, (XᵀX)⁻¹·Xᵀ·y would reach
# the DP as the two-factor product solve(XᵀX, Xᵀ)·y; and a fused chain
# is a bracketing, which is the DP's to choose before the rule reads it
_RULES_AHEAD_OF_CHAIN_DP: List[Rule] = [
    r for r in _RULES if r not in (solve_fusion, mmchain_product)]

_MAX_ITERS = 10


def apply_rewrites(e: MatExpr,
                   counts: Optional[dict] = None,
                   rule_batch: Optional[List[Rule]] = None) -> MatExpr:
    """Run the rule batch to fixpoint (bounded, Catalyst-style).
    ``counts`` (optional) accumulates per-rule hit counts."""
    for _ in range(_MAX_ITERS):
        before = e
        for rule in rule_batch or _RULES:
            e = _rewrite_bottom_up(e, rule, counts)
        if _same_structure(e, before):
            break
    return e


def _same_structure(a: MatExpr, b: MatExpr) -> bool:
    if a is b:
        return True
    if a.kind != b.kind or a.shape != b.shape or len(a.children) != len(b.children):
        return False
    # compare ALL attrs (not a fixed whitelist — a rule rewriting an
    # attr outside a whitelist would fool fixpoint detection into an
    # early exit); callables and other unhashables compare by identity
    keys = set(a.attrs) | set(b.attrs)
    for k in keys:
        va, vb = a.attrs.get(k), b.attrs.get(k)
        if isinstance(va, (int, float, str, bool, type(None))) \
                and isinstance(vb, (int, float, str, bool, type(None))):
            if va != vb:
                return False
        elif va is not vb:
            return False
    return all(_same_structure(x, y) for x, y in zip(a.children, b.children))


def common_subexpressions(e: MatExpr) -> MatExpr:
    """Hash-consing: structurally identical subtrees collapse to ONE node,
    so the executor's identity-keyed memo computes them once (the analogue
    of Catalyst's plan normalization + Spark's reused-exchange). Callable
    attrs (predicates/merges) key by identity."""
    table: dict = {}

    def key_of(n: MatExpr, child_keys) -> tuple:
        attr_items = []
        for k, v in sorted(n.attrs.items()):
            if callable(v) or not isinstance(v, (int, float, str, bool,
                                                 type(None))):
                attr_items.append((k, id(v)))
            else:
                attr_items.append((k, v))
        return (n.kind, n.shape, tuple(attr_items), tuple(child_keys))

    def walk(n: MatExpr) -> tuple:
        child_pairs = [walk(c) for c in n.children]
        child_keys = [k for k, _ in child_pairs]
        new_children = tuple(c for _, c in child_pairs)
        k = key_of(n, child_keys)
        if k in table:
            return k, table[k]
        if any(nc is not oc for nc, oc in zip(new_children, n.children)):
            n = n.with_children(new_children)
        table[k] = n
        return k, n

    return walk(e)[1]


def optimize(e: MatExpr, config: Optional[MatrelConfig] = None,
             grid: tuple = (1, 1), mesh=None,
             counts: Optional[dict] = None) -> MatExpr:
    """Full logical optimization: rewrites, chain-DP reorder, CSE.
    ``grid`` is the mesh grid shape — the chain DP's step cost then
    includes each candidate multiply's collective bill (comm-aware
    reorder); (1, 1) keeps the pure-FLOPs DP. ``mesh`` makes the bill
    layout-aware (round 5): operand PartitionSpecs steer the reorder.
    ``counts`` (optional) accumulates per-rule hit counts plus a
    ``chain_dp`` entry when the reorder restructured a chain and a
    ``chain_solve`` entry for each chain whose inverse factor the DP
    bracketed — the rewrite-metrics feed of the obs/ event log."""
    cfg = config or default_config()
    if cfg.rewrite_rules:
        e = apply_rewrites(e, counts, _RULES_AHEAD_OF_CHAIN_DP
                           if cfg.chain_opt else None)
    if cfg.chain_opt:
        reordered = chain_lib.reorder_chains(e, grid, mesh, cfg, counts)
        # structural comparison, not identity: reorder_chains rebuilds
        # matmul nodes even when it keeps the original parenthesisation
        if counts is not None and reordered is not e \
                and not _same_structure(reordered, e):
            counts["chain_dp"] = counts.get("chain_dp", 0) + 1
        e = reordered
        if cfg.rewrite_rules:
            e = apply_rewrites(e, counts)  # reorder can expose new folds
    if cfg.rewrite_rules:
        e = common_subexpressions(e)
    return e
