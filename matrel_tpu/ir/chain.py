"""Matrix-chain multiplication reordering — MatRel's flagship optimization
(SURVEY.md §2 "Optimizer: matrix-chain DP", §3.3).

"The join-order optimizer of linear algebra": collect maximal chains of
matmul nodes A·B·C·…, run the classic O(n³) interval DP with a
dimension- AND sparsity-aware cost model, and re-parenthesise the tree to
the minimum-cost order. Pure Python, runs before tracing; unit-testable
without devices (SURVEY.md §4).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from matrel_tpu.ir import stats
from matrel_tpu.ir.expr import MatExpr, inverse, matmul, solve, transpose


def collect_chain(e: MatExpr) -> List[MatExpr]:
    """Flatten a maximal product tree into its ordered operand list.
    ``solve(A, B)`` IS the product A⁻¹·B: it joins the chain as the
    factor ``inverse(A)`` ahead of B's own factors, so that a solve
    the user (or R7) bracketed around one factor of a longer product
    is associated by cost like any other (:func:`join`)."""
    if e.kind == "matmul":
        return collect_chain(e.children[0]) + collect_chain(e.children[1])
    if e.kind == "solve":
        a, b = e.children
        return [inverse(a).with_attrs(assume=e.attrs["assume"])] \
            + collect_chain(b)
    return [e]


def join(el: MatExpr, er: MatExpr) -> MatExpr:
    """The node for the product el·er of two chain intervals. A bare
    ``inverse`` factor is never materialised next to a product:
    A⁻¹·R is ``solve(A, R)`` and L·A⁻¹ is ``solve(Aᵀ, Lᵀ)ᵀ`` (R7's
    forms, built where the association is decided)."""
    if el.kind == "inverse":
        return solve(el.children[0], er,
                     assume=el.attrs.get("assume", "general"))
    if er.kind == "inverse":
        return transpose(solve(transpose(er.children[0]), transpose(el),
                               assume=er.attrs.get("assume", "general")))
    return matmul(el, er)


def _solve_step_cost(el: MatExpr, er: MatExpr) -> Optional[float]:
    """FLOPs of :func:`join` where one side is a bare inverse factor
    (None where neither is): one factorisation of the k x k side and
    the substitutions against the other side's width. What decides the
    association of (XᵀX)⁻¹·Xᵀ·y is the second term: 2·k²·N against
    the N-wide Xᵀ, 2·k² against Xᵀy. Two inverses side by side leave
    one of them materialised (a solve against the identity)."""
    if el.kind != "inverse" and er.kind != "inverse":
        return None
    k = el.shape[1]
    if el.kind == "inverse" and er.kind == "inverse":
        return 2.0 * stats.solve_cost(k, k)
    return stats.solve_cost(k, er.shape[1] if el.kind == "inverse"
                            else el.shape[0])


def _operand_layouts(operands: List[MatExpr], mesh,
                     config=None) -> List[str]:
    """Layout of each chain operand on the mesh (planner.infer_layout
    under the SESSION config — its COO claim is config-dependent), or
    all-"2d" when no mesh is given (the layout-blind DP)."""
    if mesh is None:
        return ["2d"] * len(operands)
    from matrel_tpu.parallel import planner   # lazy: no import cycle
    memo: dict = {}
    return [planner.infer_layout(op, mesh, memo, config)
            for op in operands]


def optimal_order(operands: List[MatExpr],
                  grid: Tuple[int, int] = (1, 1),
                  mesh=None, config=None) -> Tuple[MatExpr, float]:
    """Interval DP over the operand list; returns (rebuilt expr, est. cost).

    cost[i][j] = min over split s of cost[i][s] + cost[s+1][j]
                 + stepCost(dims, densities, layouts, grid)
    stepCost (stats.chain_step_cost_layout) = sparsity-aware FLOPs + the
    collective bill of the cheapest MM strategy on the grid in
    FLOP-equivalents — two parenthesisations with equal FLOPs but
    different comm bills no longer tie arbitrarily, and with ``mesh``
    given the bill is PER-LAYOUT (round 5): a replicated or 1D-sharded
    operand makes the order that broadcasts it free strictly cheaper,
    and each interval's result carries the layout its cheapest strategy
    would emit. grid == (1, 1) reduces to pure FLOPs. Densities of
    intermediates are re-estimated per split via the same propagation
    the stats module uses, so sparse chains order correctly.

    For chains of ≥3 operands the O(n³) loop runs in the native optimizer
    core (native/chain_dp.cc, same cost semantics incl. the layout-aware
    comm term); the pure-Python DP below is the always-available fallback
    and the reference implementation for equivalence tests.
    """
    n = len(operands)
    gx, gy = grid
    if n == 1:
        return operands[0], 0.0
    lays = _operand_layouts(operands, mesh if gx * gy > 1 else None,
                            config)
    # topology weights (core/mesh.MeshTopology): with a mesh in hand the
    # DP's comm term bills each strategy's legs per axis, so the order
    # that keeps traffic off a slow DCN axis wins; grid-only callers
    # (and single-device grids) stay on the flat model
    weights = (1.0, 1.0)
    if mesh is not None and gx * gy > 1:
        from matrel_tpu.core import mesh as mesh_lib
        weights = mesh_lib.axis_weights(mesh, config)
    # precision tier (round 8): under a non-default SLA the query's
    # MACs retire at the tier's MXU rate, so the comm term weighs
    # relatively more — the DP's FLOP side scales by the tier factor
    # (planner.sla_compute_factor; 1.0 under "default", bit-identical).
    # The native DP mirror predates tiers, so scaled requests run the
    # Python DP — degrade to the reference implementation, never to
    # dishonest pricing (the weighted-topology precedent).
    from matrel_tpu.parallel import planner as _planner   # lazy: no cycle
    flop_scale = _planner.sla_compute_factor(config)
    # staged reshard pricing (round 10): with reshard_peak_budget_bytes
    # set, the planner prices opposite-1D re-lays from the compiled
    # ReshardPlan — which a tight budget forces onto the higher staged
    # bill the native mirror's closed forms do not know. Degrade to the
    # Python DP (the reference implementation) rather than misprice —
    # the flop_scale/topology precedent; the equivalence fuzz
    # cross-checks native vs the plan-derived costs at budget 0, where
    # the two are bit-identical by construction (tests/test_reshard.py).
    reshard_budget = getattr(config, "reshard_peak_budget_bytes", 0) \
        if config is not None else 0
    # learned comm weights (round 19, parallel/coeffs.py — the ML018
    # seam; docs/COST_MODEL.md): under coeff_planner_enable each DP
    # step's byte bill converts to FLOP-equivalents at the MEASURED
    # flops-per-byte ratio of its shape class on the live backend,
    # instead of the analytic COMM_FLOPS_PER_BYTE constant. Cold
    # classes keep the constant. The native mirror predates learned
    # weights, so coefficient-active requests run the Python DP —
    # degrade to the reference implementation, never to dishonest
    # pricing (the flop_scale/reshard-budget precedent).
    coeff_cw = None
    shape_cls = None
    if (config is not None
            and getattr(config, "coeff_planner_enable", False)
            and gx * gy > 1):
        from matrel_tpu.parallel import coeffs as coeffs_lib
        from matrel_tpu.obs import drift as drift_lib
        import jax
        coeff_cw = coeffs_lib.chain_comm_weights(
            drift_lib.table_path(config), jax.default_backend(),
            min_samples=getattr(config, "coeff_min_samples", 1)) or None
        if coeff_cw is not None:
            shape_cls = drift_lib.shape_class
    # an inverse factor is priced as the solve it becomes (the native
    # mirror knows products only: the same degrade to the reference)
    has_inverse = any(op.kind == "inverse" for op in operands)
    if (n >= 3 and flop_scale == 1.0 and reshard_budget == 0
            and coeff_cw is None and not has_inverse):
        from matrel_tpu.utils import native
        dims = [op.shape[0] for op in operands] + [operands[-1].shape[1]]
        dens = [op.density for op in operands]
        codes = [stats.LAYOUT_CODES[l] for l in lays]
        res = native.chain_dp(dims, dens, grid=grid, layouts=codes,
                              weights=weights)
        if res is not None:
            splits, cost = res

            def build(i: int, j: int) -> MatExpr:
                if i == j:
                    return operands[i]
                s = int(splits[i][j])
                return matmul(build(i, s), build(s + 1, j))

            return build(0, n - 1), cost
    # best[i][j] = (cost, expr, layout) for operands[i..j] inclusive
    best: List[List[Optional[Tuple[float, MatExpr, str]]]] = [
        [None] * n for _ in range(n)
    ]
    for i in range(n):
        best[i][i] = (0.0, operands[i], lays[i])
    for span in range(2, n + 1):
        for i in range(0, n - span + 1):
            j = i + span - 1
            cand: Optional[Tuple[float, MatExpr, str]] = None
            for s in range(i, j):
                cl, el, ll = best[i][s]
                cr, er, lr = best[s + 1][j]
                step = _solve_step_cost(el, er)
                if step is not None:
                    # a local solve on the logical shapes: no
                    # collective bill, the canonical layout out
                    step, lay = step * flop_scale, "2d"
                else:
                    cw = (coeff_cw.get(shape_cls(
                        (el.shape[0], el.shape[1], er.shape[1])))
                        if coeff_cw is not None else None)
                    step, lay = stats.chain_step_cost_layout(
                        el.shape[0], el.shape[1], er.shape[1],
                        el.density, er.density, gx, gy, ll, lr,
                        weights=weights, flop_scale=flop_scale,
                        comm_weight=cw,
                    )
                total = cl + cr + step
                if cand is None or total < cand[0]:
                    cand = (total, join(el, er), lay)
            best[i][j] = cand
    cost, e, _ = best[0][n - 1]
    return e, cost


def reorder_chains(e: MatExpr,
                   grid: Tuple[int, int] = (1, 1),
                   mesh=None, config=None,
                   counts: Optional[dict] = None) -> MatExpr:
    """Recursively find maximal product chains and DP-reorder each.
    ``grid`` is the mesh grid shape feeding the comm-aware step cost;
    ``mesh`` additionally makes the step cost layout-aware (the DP sees
    which operands are replicated/1D-sharded on it), under the session
    ``config`` the planner will also use. ``counts`` (optional) gains
    ``chain_solve``: the chains with an inverse (or a solve) among
    their factors that were associated by the solve's cost."""
    if e.kind in ("matmul", "solve"):
        ops = collect_chain(e)
        # optimize below each chain operand first, then the chain itself
        ops = [reorder_chains(o, grid, mesh, config, counts)
               if o.kind != "leaf" else o for o in ops]
        if any(o.kind == "inverse" for o in ops):
            if counts is not None:
                counts["chain_solve"] = counts.get("chain_solve", 0) + 1
        elif len(ops) == 2:
            return matmul(ops[0], ops[1])
        new, _ = optimal_order(ops, grid, mesh, config)
        return new
    if not e.children:
        return e
    new_children = tuple(
        reorder_chains(c, grid, mesh, config, counts) for c in e.children
    )
    if all(nc is oc for nc, oc in zip(new_children, e.children)):
        return e
    return e.with_children(new_children)


def chain_cost(e: MatExpr, grid: Tuple[int, int] = (1, 1)) -> float:
    """Total estimated matmul cost of a (sub)tree, for plan assertions.
    Pure FLOPs at the default grid; comm-aware otherwise."""
    total = 0.0
    if e.kind == "matmul":
        l, r = e.children
        total += stats.chain_step_cost(
            l.shape[0], l.shape[1], r.shape[1], l.density, r.density,
            grid[0], grid[1],
        )
    elif e.kind == "solve":
        total += stats.solve_cost(e.shape[0], e.shape[1])
    for c in e.children:
        total += chain_cost(c, grid)
    return total
