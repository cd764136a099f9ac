"""Lazy matrix expression IR — the TPU-native analogue of MatRel's Catalyst
logical plan (SURVEY.md §2 "Logical operators", §3.2).

In the reference every DSL call (``Dataset.multiply``, ``.t()``, ``rowSum()``
…) constructs a Catalyst ``LogicalPlan`` node; nothing executes until an
action triggers analyze → optimize → plan → RDD execution. Here every DSL
call constructs a ``MatExpr`` node; ``.compute()`` triggers
rewrite → chain-DP → physical planning → one jitted XLA program.

Node set mirrors the reference's logical operators:
  Leaf, Transpose, MatMul, Add/Sub/ElemMul/ElemDiv (elementwise),
  ScalarOp (add/mul/pow by a scalar), Agg (sum/count/avg/max/min over
  row/col/all/diag — covers rowSum/colSum/sum/trace), Vec, RankOneUpdate,
  Inverse/Solve (dense local linear solves — the normal-equations step),
  SelectValue/SelectIndex (relational σ), JoinOnIndex/JoinOnValue (⋈),
  Sampled (an element-sparse leaf ∘ or ./ a dense product, defined only
  at the leaf's entries — SystemML's wdivmm / wsloss family).
  Semiring (an element-sparse leaf times one column under (max, ×) or
  (min, ×): γ_max/min by row over a column join, MatRel's "aggregate
  over a join" whose (sum, mul) case is the matrix product).
  MMChain (t(X) · (w ∘ (X · v)) over a tall dense table and one column:
  SystemML's fused mmchain, the product every iterative solver over a
  tall table turns on; its (n × 1) intermediate is no node).

All shape/sparsity metadata lives on the nodes so the optimizer runs as pure
Python before any tracing.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import stats

_ids = itertools.count()

ELEMWISE_OPS = ("add", "sub", "mul", "div", "min", "max")
AGG_KINDS = ("sum", "count", "avg", "max", "min")
AGG_AXES = ("row", "col", "all", "diag")
SCALAR_OPS = ("add", "mul", "pow")


@dataclasses.dataclass(frozen=True)
class MatExpr:
    """One IR node. Immutable; children are MatExpr instances.

    kind: node type tag.
    children: operand expressions.
    shape: logical output shape.
    nnz: estimated structural nonzeros (None = dense/unknown).
    attrs: kind-specific attributes (scalar value, agg kind/axis,
      predicate/merge callables, strategy hint, …).
    """

    kind: str
    children: Tuple["MatExpr", ...]
    shape: Tuple[int, int]
    nnz: Optional[int]
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    uid: int = dataclasses.field(default_factory=lambda: next(_ids))

    # equality by identity: exprs are DAG nodes, not values
    def __eq__(self, other):  # noqa: D105
        return self is other

    def __hash__(self):
        return self.uid

    # -- metadata ----------------------------------------------------------

    @property
    def density(self) -> float:
        return stats.density_of(self.nnz, self.shape)

    def with_attrs(self, **kw: Any) -> "MatExpr":
        a = dict(self.attrs)
        a.update(kw)
        return dataclasses.replace(self, attrs=a, uid=next(_ids))

    def with_children(self, children: Tuple["MatExpr", ...]) -> "MatExpr":
        return dataclasses.replace(self, children=tuple(children), uid=next(_ids))

    # -- DSL (mirrors the reference's Dataset implicit methods) ------------

    def t(self) -> "MatExpr":
        return transpose(self)

    def multiply(self, other) -> "MatExpr":
        return matmul(self, as_expr(other))

    def matmul(self, other) -> "MatExpr":
        return matmul(self, as_expr(other))

    def add(self, other) -> "MatExpr":
        return elemwise("add", self, as_expr(other))

    def subtract(self, other) -> "MatExpr":
        return elemwise("sub", self, as_expr(other))

    def elem_multiply(self, other) -> "MatExpr":
        return elemwise("mul", self, as_expr(other))

    def divide(self, other) -> "MatExpr":
        return elemwise("div", self, as_expr(other))

    def elem_min(self, other) -> "MatExpr":
        return elemwise("min", self, as_expr(other))

    def elem_max(self, other) -> "MatExpr":
        return elemwise("max", self, as_expr(other))

    def add_scalar(self, s: float) -> "MatExpr":
        return scalar_op("add", self, s)

    def multiply_scalar(self, s: float) -> "MatExpr":
        return scalar_op("mul", self, s)

    def power(self, p: float) -> "MatExpr":
        return scalar_op("pow", self, p)

    def row_sum(self) -> "MatExpr":
        return agg(self, "sum", "row")

    def col_sum(self) -> "MatExpr":
        return agg(self, "sum", "col")

    def sum(self) -> "MatExpr":
        return agg(self, "sum", "all")

    def trace(self) -> "MatExpr":
        return agg(self, "sum", "diag")

    def row_max(self) -> "MatExpr":
        return agg(self, "max", "row")

    def row_min(self) -> "MatExpr":
        return agg(self, "min", "row")

    def col_max(self) -> "MatExpr":
        return agg(self, "max", "col")

    def col_min(self) -> "MatExpr":
        return agg(self, "min", "col")

    def row_count(self) -> "MatExpr":
        return agg(self, "count", "row")

    def col_count(self) -> "MatExpr":
        return agg(self, "count", "col")

    def row_avg(self) -> "MatExpr":
        return agg(self, "avg", "row")

    def col_avg(self) -> "MatExpr":
        return agg(self, "avg", "col")

    def norm(self, kind: str = "fro") -> "MatExpr":
        """Matrix norm as a (1,1) expression — pure sugar over existing
        nodes (so every rewrite applies): "fro" = sqrt(Σ a²), "l1" =
        Σ|a| (entrywise), "max" = max|a|."""
        if kind == "fro":
            return scalar_op("pow", agg(elemwise("mul", self, self),
                                        "sum", "all"), 0.5)
        # |a| = max(a, -a): exact, no under/overflow from squaring, and
        # sparsity-preserving (max(0, 0) = 0)
        if kind in ("l1", "max"):
            absa = elemwise("max", self, self.multiply_scalar(-1.0))
            return agg(absa, "sum" if kind == "l1" else "max", "all")
        raise ValueError(f"unknown norm kind {kind!r} "
                         "(expected 'fro', 'l1', or 'max')")

    def inverse(self) -> "MatExpr":
        return inverse(self)

    def solve(self, b, assume: str = "general") -> "MatExpr":
        return solve(self, as_expr(b), assume=assume)

    def vec(self) -> "MatExpr":
        return vec(self)

    def rank_one_update(self, u, v) -> "MatExpr":
        return rank_one_update(self, as_expr(u), as_expr(v))

    def select_value(self, predicate: Callable, fill: float = 0.0) -> "MatExpr":
        return select_value(self, predicate, fill)

    def select_index(self, *, rows=None, cols=None) -> "MatExpr":
        return select_index(self, rows=rows, cols=cols)

    def join_on_index(self, other, merge: Callable) -> "MatExpr":
        return join_on_index(self, as_expr(other), merge)

    def join_on_value(self, other, merge: Callable, predicate=None) -> "MatExpr":
        return join_on_value(self, as_expr(other), merge, predicate)

    def __matmul__(self, other):
        return self.multiply(other)

    def __add__(self, other):
        if isinstance(other, (int, float)):
            return self.add_scalar(other)
        return self.add(other)

    def __sub__(self, other):
        if isinstance(other, (int, float)):
            return self.add_scalar(-other)
        return self.subtract(other)

    def __mul__(self, other):
        if isinstance(other, (int, float)):
            return self.multiply_scalar(other)
        return self.elem_multiply(other)

    def __rmul__(self, other):
        if isinstance(other, (int, float)):
            return self.multiply_scalar(other)
        return NotImplemented

    def __truediv__(self, other):
        if isinstance(other, (int, float)):
            return self.multiply_scalar(1.0 / other)
        return self.divide(other)

    # -- actions -----------------------------------------------------------

    def compute(self, session=None) -> BlockMatrix:
        """Optimize + jit + execute. The Spark 'action' analogue."""
        from matrel_tpu.session import get_or_create_session
        sess = session or get_or_create_session()
        return sess.compute(self)

    def to_numpy(self, session=None):
        return self.compute(session).to_numpy()

    def optimized(self, config=None) -> "MatExpr":
        from matrel_tpu.ir.rules import optimize
        return optimize(self, config)

    def explain(self, config=None) -> str:
        """Pretty-print logical and optimized plans (Dataset.explain analogue)."""
        opt = self.optimized(config)
        return ("== Logical plan ==\n" + pretty(self)
                + "\n== Optimized plan ==\n" + pretty(opt))

    def __repr__(self):
        return f"MatExpr<{self.kind} {self.shape} nnz={self.nnz}>"


# -- constructors (shape/sparsity inference lives here) ---------------------


def as_expr(x: Union[MatExpr, BlockMatrix]) -> MatExpr:
    if isinstance(x, MatExpr):
        return x
    if isinstance(x, BlockMatrix):
        return leaf(x)
    # sparse leaves (BlockSparseMatrix, COOMatrix) lift through their
    # own .expr() — so S1.multiply(S2) builds the S×S matmul node the
    # SpGEMM dispatch reads, without an import cycle here
    make = getattr(x, "expr", None)
    if callable(make):
        e = make()
        if isinstance(e, MatExpr):
            return e
    raise TypeError(f"cannot lift {type(x)} into MatExpr")


def leaf(m: BlockMatrix) -> MatExpr:
    return MatExpr("leaf", (), tuple(m.shape), m.nnz, {"matrix": m})


def transpose(a: MatExpr) -> MatExpr:
    return MatExpr("transpose", (a,), (a.shape[1], a.shape[0]), a.nnz)


def matmul(a: MatExpr, b: MatExpr) -> MatExpr:
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"matmul shape mismatch: {a.shape} x {b.shape}")
    n, k, m = a.shape[0], a.shape[1], b.shape[1]
    return MatExpr("matmul", (a, b), (n, m),
                   stats.matmul_out_nnz(n, k, m, a.nnz, b.nnz))


def elemwise(op: str, a: MatExpr, b: MatExpr) -> MatExpr:
    if op not in ELEMWISE_OPS:
        raise ValueError(f"unknown elementwise op {op}")
    if a.shape != b.shape:
        # allow (n,1)/(1,m) broadcast against (n,m) — used by normalisation
        bcast_ok = (
            (a.shape[0] == b.shape[0] and (a.shape[1] == 1 or b.shape[1] == 1))
            or (a.shape[1] == b.shape[1] and (a.shape[0] == 1 or b.shape[0] == 1))
            or b.shape == (1, 1) or a.shape == (1, 1)
        )
        if not bcast_ok:
            raise ValueError(f"elementwise shape mismatch: {a.shape} vs {b.shape}")
    shape = (max(a.shape[0], b.shape[0]), max(a.shape[1], b.shape[1]))
    da, db = a.density, b.density
    if op in ("mul", "div"):
        d = stats.elemmul_density(da, db) if op == "mul" else da
    else:
        d = stats.add_density(da, db)
    nnz = None if (a.nnz is None and b.nnz is None) else stats.nnz_from_density(d, shape)
    return MatExpr("elemwise", (a, b), shape, nnz, {"op": op})


SAMPLED_OPS = ("div", "mul")
# The widest dense side the COO SpMV tables multiply, and the longest
# inner dimension of a sampled product: the columns one pass of the
# k-wide kernel takes (a gathered float32 row fills 128 lanes,
# ops/pallas_spmv.WIDE_COLS).
COO_NARROW_MAX = 128


def sampled(op: str, s: MatExpr, a: MatExpr, b: MatExpr) -> MatExpr:
    """``S op (A·B)`` for an element-sparse leaf ``S`` and a dense
    product, ``op`` "div" (S ./ (A·B)) or "mul" (S ∘ (A·B)): a value
    defined only at S's entries and zero elsewhere (0 / x = 0, and
    x / 0 = 0 as the element-wise div gives it), so it has S's
    structure and the product A·B is never wanted whole. Its three
    children are the leaf and the product's two factors; written by
    ``rules.sampled_product``, never by the DSL. Under a product with a
    narrow dense side the executor answers it fused, the quotient never
    stored (executor._sampled_product); anywhere else it lowers as the
    element-wise node it came from, the leaf densified."""
    if op not in SAMPLED_OPS:
        raise ValueError(f"unknown sampled op {op}")
    if s.kind != "coo_leaf":
        raise ValueError("sampled: the sampling operand must be a coo_leaf")
    if a.shape[1] != b.shape[0] or s.shape != (a.shape[0], b.shape[1]):
        raise ValueError(f"sampled shape mismatch: {s.shape} against "
                         f"{a.shape} x {b.shape}")
    return MatExpr("sampled", (s, a, b), s.shape, s.nnz, {"op": op})


SEMIRING_REDUCES = ("max", "min")


def semiring(reduce: str, s: MatExpr, x: MatExpr) -> MatExpr:
    """The (``reduce``, ×) product of an element-sparse leaf ``S``
    (n × m) and ONE column ``x`` (m × 1): ``y[i] = reduce_j S[i, j] ·
    x[j]`` over ALL m columns, a missing cell of ``S`` counting as the 0
    it is in the dense matrix (so a row that misses a cell has a 0 in
    the running for its extremum, a row with no entry gives 0, and only
    a row with all m entries gives its products' own extremum): exactly
    ``agg(reduce, row)(join_cols(S, t(x), mul))``, which
    ``rules.semiring_product`` rewrites to this node and the DSL never
    writes. Its two children are the leaf and the column, so no pass
    ever prices the (n × m) join; the executor answers it from the
    leaf's entries alone (executor._semiring_product)."""
    if reduce not in SEMIRING_REDUCES:
        raise ValueError(f"unknown semiring reduction {reduce}")
    if s.kind != "coo_leaf":
        raise ValueError("semiring: the matrix operand must be a coo_leaf")
    if x.shape != (s.shape[1], 1):
        raise ValueError(f"semiring shape mismatch: {s.shape} against a "
                         f"column {x.shape}")
    return MatExpr("semiring", (s, x), (s.shape[0], 1), None,
                   {"reduce": reduce, "merge": "mul"})


#: The widest ``v`` the rule writes an ``mmchain`` node for: a narrow
#: right side keeps the chain a pass over the table (the kernel itself
#: takes one column, planner.mmchain_plan; a wider one un-fuses by name).
MMCHAIN_NARROW_MAX = COO_NARROW_MAX


def mmchain(x: MatExpr, v: MatExpr, w: Optional[MatExpr] = None) -> MatExpr:
    """``t(X) · (w ∘ (X · v))`` (``w`` None: ``t(X) · (X · v)``) for a
    dense leaf ``X`` (n × k), ``v`` (k × m) and weights ``w`` (n × 1):
    SystemML's fused ``mmchain`` (XtXv / XtwXv), the product
    LinearRegCG, GLM, MLogreg and L2SVM turn on.
    ``rules.mmchain_product`` rewrites the bracketed chain to this node
    and the DSL never writes it. Its children are the table and the
    vectors, so the (n × m) intermediate ``X · v`` is priced and stored
    by no pass; where one pass over ``X`` answers it
    (planner.mmchain_plan) the executor reads the table once
    (ops/mmchain.py), elsewhere the planner writes the two products
    back (planner.unfused_mmchain) and they lower as they always did."""
    if x.kind != "leaf":
        raise ValueError("mmchain: the table must be a dense leaf")
    n, k = x.shape
    if v.shape[0] != k:
        raise ValueError(f"mmchain shape mismatch: {x.shape} against "
                         f"{v.shape}")
    if w is not None and w.shape != (n, 1):
        raise ValueError(f"mmchain: weights {w.shape} are no column of "
                         f"{n} rows")
    kids = (x, v) if w is None else (x, v, w)
    return MatExpr("mmchain", kids, (k, v.shape[1]), None,
                   {"weighted": w is not None})


def scalar_op(op: str, a: MatExpr, s: float) -> MatExpr:
    if op not in SCALAR_OPS:
        raise ValueError(f"unknown scalar op {op}")
    if op == "mul":
        nnz = a.nnz if s != 0 else 0
    elif op == "add":
        nnz = a.nnz if s == 0 else None  # adding a scalar densifies
    else:  # pow
        nnz = a.nnz
    return MatExpr("scalar", (a,), a.shape, nnz, {"op": op, "value": float(s)})


def agg(a: MatExpr, kind: str, axis: str) -> MatExpr:
    if kind not in AGG_KINDS:
        raise ValueError(f"unknown agg kind {kind}")
    if axis not in AGG_AXES:
        raise ValueError(f"unknown agg axis {axis}")
    if axis == "diag" and a.shape[0] != a.shape[1]:
        raise ValueError(f"diag aggregate needs a square matrix, got {a.shape}")
    shape = {"row": (a.shape[0], 1), "col": (1, a.shape[1]),
             "all": (1, 1), "diag": (1, 1)}[axis]
    return MatExpr("agg", (a,), shape, None, {"agg": kind, "axis": axis})


def vec(a: MatExpr) -> MatExpr:
    """Column-major vectorisation vec(A): (n,m) → (n*m, 1)."""
    return MatExpr("vec", (a,), (a.shape[0] * a.shape[1], 1), a.nnz)


def rank_one_update(a: MatExpr, u: MatExpr, v: MatExpr) -> MatExpr:
    """A + u·vᵀ with u:(n,1), v:(m,1)."""
    n, m = a.shape
    if u.shape != (n, 1) or v.shape != (m, 1):
        raise ValueError(
            f"rank_one_update expects u:({n},1) v:({m},1); got {u.shape}, {v.shape}")
    return MatExpr("rank1", (a, u, v), a.shape, None)


def inverse(a: MatExpr) -> MatExpr:
    """A⁻¹ for square A. Dense local solve on the logical (unpadded)
    matrix — the analogue of the reference's driver-side inverse in the
    normal-equations workload ((XᵀX)⁻¹Xᵀy, SURVEY.md §2 workloads row):
    the Gram matrix is small, so the reference inverts it locally, not
    distributively. Prefer ``solve(a, b)`` over ``inverse(a) @ b`` —
    the optimizer rewrites the latter into the former (R7).
    """
    n, m = a.shape
    if n != m:
        raise ValueError(f"inverse needs a square matrix, got {a.shape}")
    return MatExpr("inverse", (a,), a.shape, None)


def solve(a: MatExpr, b: MatExpr, assume: str = "general") -> MatExpr:
    """X = A⁻¹·B (solve A·X = B) for square A, on the logical shapes.

    ``assume="pos"`` takes a Cholesky factorisation instead of LU —
    right for the normal-equations Gram matrix (SPD), ~2× cheaper and
    numerically tighter. ``"general"`` (default) is LU.
    """
    if assume not in ("general", "pos"):
        raise ValueError(f"solve assume must be 'general' or 'pos', "
                         f"got {assume!r}")
    n, m = a.shape
    if n != m:
        raise ValueError(f"solve needs a square lhs, got {a.shape}")
    if b.shape[0] != n:
        raise ValueError(f"solve shape mismatch: {a.shape} x {b.shape}")
    return MatExpr("solve", (a, b), b.shape, None, {"assume": assume})


def select_value(a: MatExpr, predicate: Callable, fill: float = 0.0) -> MatExpr:
    """Relational σ on entry values: keep entries where predicate(v) holds.

    Static-shape semantics (XLA constraint, flagged in SURVEY.md §7.6): the
    result is a same-shaped matrix with non-matching entries set to ``fill``,
    not a shrunk relation. ``fill=0`` keeps sparsity algebra exact.
    """
    return MatExpr("select_value", (a,), a.shape, a.nnz,
                   {"predicate": predicate, "fill": float(fill)})


def select_index(a: MatExpr, *, rows=None, cols=None) -> MatExpr:
    """Relational σ on indices: keep rows/cols where the predicate holds.

    rows/cols are callables over index arrays (vectorised, traceable) or
    None. Non-selected entries become 0 (static shapes).
    """
    return MatExpr("select_index", (a,), a.shape, a.nnz,
                   {"rows": rows, "cols": cols})


def join_on_index(a: MatExpr, b: MatExpr, merge) -> MatExpr:
    """⋈ on block/entry index equality: C[i,j] = merge(A[i,j], B[i,j]).

    The cogroup-style join of two co-partitioned matrices (SURVEY.md §2
    "Physical: relational execs"). ``merge`` is a traceable binary fn OR
    a structured string ("left"/"right"/"add"/"mul") — structured kinds
    let the planner infer the output dtype (jnp promotion).
    """
    if a.shape != b.shape:
        raise ValueError(f"join_on_index shape mismatch: {a.shape} vs {b.shape}")
    merge_kind, merge_fn = resolve_join_merge(merge)
    return MatExpr("join_index", (a, b), a.shape, None,
                   {"merge": merge_fn, "merge_kind": merge_kind})


JOIN_PREDS = ("eq", "lt", "le", "gt", "ge")
JOIN_MERGES = ("left", "right", "add", "mul")


def resolve_join_pred(pred):
    """(pred_kind, callable) for a structured-or-callable predicate.
    Structured kinds compare va ? vb: "lt" means va < vb."""
    if pred is None or callable(pred):
        return None, pred
    if pred not in JOIN_PREDS:
        raise ValueError(f"unknown join predicate {pred!r}; expected a "
                         f"callable or one of {JOIN_PREDS}")
    import operator
    fn = {"eq": operator.eq, "lt": operator.lt, "le": operator.le,
          "gt": operator.gt, "ge": operator.ge}[pred]
    return pred, fn


def resolve_join_merge(merge):
    """(merge_kind, callable) for a structured-or-callable merge."""
    if callable(merge):
        return None, merge
    if merge not in JOIN_MERGES:
        raise ValueError(f"unknown join merge {merge!r}; expected a "
                         f"callable or one of {JOIN_MERGES}")
    def _take_left(a, b):
        import jax.numpy as jnp
        # broadcast WITHOUT arithmetic on b: a + 0*b turns a non-finite
        # discarded operand into NaN (inf·0)
        return a + jnp.zeros_like(b)

    fn = {"left": _take_left,
          "right": lambda a, b: _take_left(b, a),
          "add": lambda a, b: a + b,
          "mul": lambda a, b: a * b}[merge]
    return merge, fn


def join_on_value(a: MatExpr, b: MatExpr, merge,
                  predicate=None) -> MatExpr:
    """⋈ on values: pairs (A[i,j], B[k,l]) where predicate(va, vb).

    Full value-join output is |A|x|B| pairs — unrepresentable statically.
    Faithful static-shape semantics: the result is the (n*m_A) x (n*m_B)
    PAIR MATRIX restricted to merge values where the predicate holds, as
    a lazy node. Materialising it is capped by
    config.join_pair_cap_entries; AGGREGATED value-joins
    (agg(join_on_value(...), ...)) never materialise the pair matrix —
    with STRUCTURED predicate/merge (predicate in "eq"/"lt"/"le"/"gt"/
    "ge" on va ? vb, merge in "left"/"right"/"add"/"mul") they stream in
    O((na+nb)·log nb) via the executor's sort-based path (the
    reference's scalable value-join; SURVEY.md §2 relational execs),
    and with callables they fall back to capped chunkwise enumeration.
    For aligned-entry joins use join_on_index.
    """
    pred_kind, pred_fn = resolve_join_pred(predicate)
    merge_kind, merge_fn = resolve_join_merge(merge)
    na = a.shape[0] * a.shape[1]
    nb = b.shape[0] * b.shape[1]
    return MatExpr("join_value", (a, b), (na, nb), None,
                   {"merge": merge_fn, "predicate": pred_fn,
                    "merge_kind": merge_kind, "pred_kind": pred_kind})


# -- utilities --------------------------------------------------------------


def leaves(e: MatExpr) -> List[MatExpr]:
    """All leaf nodes in evaluation order (deduped by identity)."""
    seen: Dict[int, MatExpr] = {}

    def walk(n: MatExpr):
        if n.kind == "leaf":
            seen.setdefault(n.uid, n)
        for c in n.children:
            walk(c)

    walk(e)
    return list(seen.values())


def pretty(e: MatExpr, indent: int = 0, mesh=None,
           _lmemo: Optional[dict] = None, config=None) -> str:
    """Plan printer. With ``mesh`` given, each non-canonically-laid node
    is annotated ``layout=row/col/rep`` from planner.infer_layout — the
    physical-EXPLAIN view of the co-partitioning credit (round 5), next
    to the strategy provenance it drives. Pass the PLAN's config so the
    printed layouts are the ones the planner actually used (the COO
    "rep" claim is config-dependent — review r5)."""
    pad = "  " * indent
    extra = ""
    if e.kind in ("elemwise", "sampled"):
        extra = f" op={e.attrs['op']}"
    elif e.kind == "scalar":
        extra = f" op={e.attrs['op']} v={e.attrs['value']}"
    elif e.kind == "semiring":
        extra = f" ({e.attrs['reduce']}, {e.attrs['merge']})"
    elif e.kind == "mmchain":
        extra = " weighted" if e.attrs["weighted"] else ""
    elif e.kind == "agg":
        extra = f" {e.attrs['agg']}/{e.attrs['axis']}"
    elif e.kind == "matmul" and "strategy" in e.attrs:
        extra = f" strategy={e.attrs['strategy']}"
        if "strategy_source" in e.attrs:
            extra += f"[{e.attrs['strategy_source']}]"
        if "precision_tier" in e.attrs:
            extra += f" precision={e.attrs['precision_tier']}"
    elif e.kind in ("join_rows", "join_cols") and "replicate" in e.attrs:
        extra = f" replicate={e.attrs['replicate']}"
    elif e.kind == "join_value":
        mk = e.attrs.get("merge_kind") or "<callable>"
        pk = e.attrs.get("pred_kind") or (
            "<callable>" if e.attrs.get("predicate") else "always")
        extra = f" merge={mk} pred={pk}"
    if mesh is not None:
        from matrel_tpu.parallel import planner as _pl   # lazy: no cycle
        if _lmemo is None:
            _lmemo = {}
        lay = _pl.infer_layout(e, mesh, _lmemo, config)
        if lay != "2d":
            extra += f" layout={lay}"
    line = f"{pad}{e.kind}{extra} shape={e.shape} nnz={e.nnz}\n"
    return line + "".join(pretty(c, indent + 1, mesh, _lmemo, config)
                          for c in e.children)
