"""Executor — lowers an optimized MatExpr into ONE jitted XLA program.

Reference pipeline (SURVEY.md §3.2): optimized Catalyst plan → physical exec
nodes → RDD DAG → shuffle-bounded Spark stages → per-task BLAS. TPU rebuild:
optimized MatExpr → a single traced function over the leaf arrays, with each
matmul dispatched to its planned strategy (shard_map collective recipe) and
everything else to jnp ops; XLA fuses the elementwise traffic into the
matmuls and schedules the collectives on ICI. The whole post-optimizer
pipeline is one compiled program — no per-stage host round-trips.

Zero-padding invariant: every lowered intermediate is exactly 0 outside its
logical region (padding.py). Ops that would break it (scalar-add, pow≤0,
division, broadcasted add/sub, select fills, join merges) re-mask. Aggregates
mask padding where zeros would change the answer (max/min/avg/count).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib, padding
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir import expr as expr_mod, rules
from matrel_tpu.ir.expr import (COO_NARROW_MAX, MatExpr,  # noqa: F401 (re-exported)
                                leaves as expr_leaves)
from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.parallel import planner, strategies
from matrel_tpu.resilience import faults as faults_lib
from matrel_tpu.utils.profiling import annotate

Array = jax.Array


def _row_mask(n: int, pn: int) -> Array:
    return (jnp.arange(pn) < n)[:, None]


def _col_mask(m: int, pm: int) -> Array:
    return (jnp.arange(pm) < m)[None, :]


def _mask_to_logical(x: Array, shape: Tuple[int, int]) -> Array:
    """Zero out everything outside the logical region."""
    pn, pm = x.shape
    n, m = shape
    if (pn, pm) == (n, m):
        return x
    return jnp.where(_row_mask(n, pn) & _col_mask(m, pm), x, jnp.zeros((), x.dtype))


def _diag_reduce(d: Array, kind: str) -> Array:
    """sum/count/avg/max/min of a 1-D entry vector — the single
    diagonal-aggregate dispatch shared by the dense diag branch and the
    value-join diag branch (count counts nonzero entries; avg divides
    by that count)."""
    if kind == "sum":
        return jnp.sum(d)
    if kind == "count":
        return jnp.sum(d != 0).astype(d.dtype)
    if kind == "avg":
        c = jnp.sum(d != 0)
        return jnp.where(c > 0, jnp.sum(d) / c, 0.0).astype(d.dtype)
    if kind == "max":
        return jnp.max(d)
    if kind == "min":
        return jnp.min(d)
    raise NotImplementedError(kind)


class Lowerer:
    """Recursively lowers MatExpr nodes to jnp ops over padded arrays."""

    def __init__(self, mesh: Mesh, config: MatrelConfig,
                 op_hook: Optional[Callable] = None):
        self.mesh = mesh
        self.config = config
        # analyze-mode per-op wall-clock hook: callable(node, label,
        # seconds), invoked after each node's lowering completes WITH a
        # device sync. Only meaningful when the lowered function runs
        # EAGERLY (obs/analyze.py) — inside a jit trace a perf_counter
        # around tracing measures nothing, so compile_expr never sets
        # it; the hot path stays sync-free (obs_level contract).
        self.op_hook = op_hook
        # layout/dtype memos for the staged-reshard lowering (budget
        # > 0 only): infer_layout/infer_dtype walks at trace time stay
        # O(nodes) across a plan's matmuls (the annotate-pass idiom)
        self._lay_memo: Dict[int, str] = {}
        self._dt_memo: Dict[int, object] = {}
        # id(plan) -> (plan, measured SpMV executor variant "compact" |
        # "expanded"), populated at compile time by the autotune loop
        # (parallel/autotune.lookup_or_measure_spmv); empty = hand
        # defaults decide. The entry CARRIES the plan object and reads
        # validate it by identity (VERDICT r4 "what's weak" #3): a bare
        # id key could misroute a recycled address after the original
        # plan is garbage-collected; the held reference both prevents
        # that collection and proves the match.
        self.spmv_choice: Dict[int, Tuple[object, str]] = {}
        # which executor each matmul lowered to, in lowering order and
        # without repeats: a strategy name, a registry kernel id,
        # "pallas_spmm" / "pallas_spmv", or "xla" — complete once the
        # lowered function has been traced (plan.meta["executors"])
        self.executors: List[str] = []
        # what each coo_leaf product lowered through, in lowering order:
        # the plan's facts where the compact or expanded SpMV tables
        # answered (plan.meta["spmm"]: a ``matrel.spmm.plan`` span at
        # every dispatch), the matrix's shape and bytes where the leaf
        # was densified (plan.meta["densified_products"])
        self.spmm: List[dict] = []
        self.densified: List[dict] = []
        # and what each sampled product lowered through where it was
        # answered fused (plan.meta["sampled"]: core.coo.sampled_facts,
        # a ``matrel.sampled.plan`` span at every dispatch)
        self.sampled: List[dict] = []
        # and how each (max | min, ×) semiring product was answered
        # (plan.meta["semiring"]: core.coo.semiring_facts, a
        # ``matrel.semiring.plan`` span at every dispatch); and the
        # row/col joins with merge "mul" that densified an
        # element-sparse operand instead (plan.meta["densified_joins"])
        self.semiring: List[dict] = []
        self.densified_joins = 0
        # a long Gram and the product that rides its loop
        # (planner.gram_riders: {uid: (gram, rider)} under both uids),
        # and, while a trace runs, the riders' products by uid
        self._riders: Dict[int, Tuple[MatExpr, MatExpr]] = {}
        self._rode: Dict[int, Array] = {}

    def _ran(self, executor: str) -> None:
        if executor not in self.executors:
            self.executors.append(executor)

    def _spmv_forced(self, plan) -> Optional[str]:
        """The measured executor variant forced for THIS plan object, or
        None. The identity check is the point: an id-keyed hit whose
        stored plan is a different object (the original was collected
        and its address recycled) is a stale entry, not a choice."""
        entry = self.spmv_choice.get(id(plan))
        return entry[1] if entry is not None and entry[0] is plan else None

    def lower(self, root: MatExpr, leaf_order: List[MatExpr]) -> Callable:
        multi = self.lower_multi((root,), leaf_order)

        def fn(*leaf_arrays: Array) -> Array:
            return multi(*leaf_arrays)[0]

        return fn

    def lower_multi(self, roots, leaf_order: List[MatExpr]) -> Callable:
        """Lower several roots into ONE traced function with a SHARED memo:
        common subexpressions (by node identity) are computed once — e.g.
        XᵀX and Xᵀy of the normal equations share the Xᵀ resharding."""
        leaf_pos = {l.uid: i for i, l in enumerate(leaf_order)}
        for root in roots:          # a root at a time, as they are stamped
            self._riders.update(planner.gram_riders(
                root, self.mesh, self.config, self._dt_memo))

        def fn(*leaf_arrays: Array):
            memo: Dict[int, Array] = {}
            self._rode = {}
            # analyze-mode bookkeeping: _eval recurses through ev, so a
            # node's wall-clock window CONTAINS its children's — track
            # child time per frame and report the EXCLUSIVE remainder
            # (otherwise a depth-N tree reports ~N× the real runtime)
            child_time = []

            def ev(node: MatExpr) -> Array:
                if node.uid in memo:
                    return memo[node.uid]
                # annotate() per physical operator: the profiler-timeline
                # visibility the reference gets from Spark stage names
                # (SURVEY.md §5 "Tracing / profiling") — the label rides
                # the HLO's op_name and so the ``tf_op`` stat of the
                # operation's event metadata in a trace (PERF.md, PR
                # 25). EVERY node lowering dispatch must go through
                # this one wrapped call — tests/test_obs.py structurally
                # enforces it, so new ops can't silently skip
                # instrumentation. A fused region (ir/fusion.py stamp,
                # config.fusion_enable) is ONE dispatch: the whole
                # member set lowers under this single frame — that
                # per-edge dispatch collapse is the point of the fusion
                # pass.
                sig = (node.attrs.get("fused_region")
                       if self.config.fusion_enable else None)
                if sig is not None:
                    label = f"fused:{sig}"
                else:
                    label = node.kind
                    if node.kind == "matmul":
                        label += ":" + node.attrs.get("strategy", "xla")
                        tier = node.attrs.get("precision_tier")
                        if tier is not None:    # tiered lowering: the
                            label += f"@{tier}"  # per-op label says so
                if self.op_hook is not None:
                    child_time.append(0.0)
                    t0 = time.perf_counter()  # matlint: disable=ML006 analyze-mode op_hook measurement — lands in analyze events
                # fault site "lower": the resilience harness's hook at
                # this ONE dispatch point (fires at trace time — a
                # compile-path fault). Free when fault_inject is "".
                faults_lib.check("lower", self.config)
                with annotate(f"matrel.{label}"):
                    if sig is not None:
                        out = self._eval_region(node, ev, leaf_arrays,
                                                leaf_pos)
                    else:
                        out = self._eval(node, ev, leaf_arrays,
                                         leaf_pos)
                if self.op_hook is not None:
                    # the ONE sanctioned lowering-path sync: analyze
                    # mode only (op_hook is never set on the hot path —
                    # compile_expr leaves it None; obs/analyze.py sets
                    # it for eager per-op wall-clocking)
                    jax.block_until_ready(out)  # matlint: disable=ML001 analyze-mode op_hook
                    dt = time.perf_counter() - t0  # matlint: disable=ML006 analyze-mode op_hook measurement
                    spent_in_children = child_time.pop()
                    if child_time:
                        child_time[-1] += dt
                    self.op_hook(node, label,
                                 max(dt - spent_in_children, 0.0))
                memo[node.uid] = out
                return out

            outs = []
            for root in roots:
                out = ev(root)
                pshape = padding.padded_shape(root.shape, self.mesh)
                if tuple(out.shape) != pshape:
                    out = jnp.pad(out, ((0, pshape[0] - out.shape[0]),
                                        (0, pshape[1] - out.shape[1])))
                if self.config.reshard_peak_budget_bytes > 0:
                    # the ROOT canonical re-lay through the staged
                    # reshard path too (a bmm root's row/col → 2d move
                    # — the _root_reshard_cost leg, made explicit and
                    # per-kind-annotated); the constraint below then
                    # finds the layout already canonical
                    out = self._stage_root_relay(root, out)
                outs.append(jax.lax.with_sharding_constraint(
                    out, padding.canonical_sharding(pshape, self.mesh)))
            return tuple(outs)

        return fn

    # -- per-node lowering --------------------------------------------------

    def _eval(self, node: MatExpr, ev, leaf_arrays, leaf_pos) -> Array:
        k = node.kind
        if k == "leaf":
            return leaf_arrays[leaf_pos[node.uid]]
        if k == "sparse_leaf":
            # densify when a sparse matrix is used outside a matmul; the
            # SpMM fast path handles the matmul case below
            return node.attrs["matrix"].to_dense(self.config).data
        if k == "coo_leaf":
            # an element-sparse leaf read as an array is densified, and
            # said to be (plan.meta["densified_products"]). Its products
            # with a narrow dense side never come here: the SpMV tables
            # answer them in _matmul, and those of a ``sampled`` node
            # over it the fused sampled product
            self._densifies(node.attrs["matrix"])
            return node.attrs["matrix"].to_block(self.mesh,
                                                 self.config).data
        if k == "sampled":
            # not an operand of a product that answers it fused
            # (_sampled_dispatch_plan): the element-wise node it was
            # written as, the leaf densified and the product whole
            s, a, b = node.children
            self._ran("xla")
            return self._elemwise_op(
                node.attrs["op"], ev(s), strategies.run_matmul(
                    "xla", ev(a), ev(b), self.mesh, self.config))
        if k == "semiring":
            return self._semiring_product(node, ev)
        if k == "mmchain":
            return self._mmchain(node, ev)
        if k == "transpose":
            return ev(node.children[0]).T
        if k == "matmul":
            return self._matmul(node, ev)
        if k == "solve":
            return self._solve(node, ev)
        if k == "inverse":
            return self._inverse(node, ev)
        if k == "elemwise":
            return self._elemwise(node, ev)
        if k == "scalar":
            return self._scalar(node, ev)
        if k == "agg":
            return self._agg(node, ev)
        if k == "vec":
            return self._vec(node, ev)
        if k == "rank1":
            a, u, v = (ev(c) for c in node.children)
            return a + u @ v.T
        if k == "select_value":
            x = ev(node.children[0])
            pred, fill = node.attrs["predicate"], node.attrs["fill"]
            out = jnp.where(pred(x), x, jnp.asarray(fill, x.dtype))
            if fill != 0.0:
                out = _mask_to_logical(out, node.shape)
            return out
        if k == "select_index":
            return self._select_index(node, ev)
        if k == "join_index":
            a, b = ev(node.children[0]), ev(node.children[1])
            out = node.attrs["merge"](a, b)
            return _mask_to_logical(out, node.shape)
        if k == "join_value":
            return self._join_value(node, ev)
        if k == "select_block":
            x = ev(node.children[0])
            bs = node.attrs["block_size"]
            pred = node.attrs["predicate"]
            pn, pm = x.shape
            bi = (jnp.arange(pn) // bs)[:, None]
            bj = (jnp.arange(pm) // bs)[None, :]
            return jnp.where(pred(bi, bj), x, jnp.zeros((), x.dtype))
        if k in ("join_rows", "join_cols"):
            return self._join_axis(node, ev)
        raise NotImplementedError(f"lowering for node kind {k!r}")

    def _eval_region(self, root: MatExpr, ev, leaf_arrays,
                     leaf_pos) -> Array:
        """Lower one FUSED REGION (ir/fusion.py stamp) as a single
        dispatch: every member lowers inside the caller's ONE
        ``annotate()`` frame; region INPUTS (non-member children) go
        back through the outer ``ev`` and keep their own frames. The
        member chain ABOVE the anchor matmul is composed into an
        epilogue callable and pushed into the producing kernel's
        epilogue slot (strategies.run_matmul / ops/spmm.apply /
        ops/spgemm.apply_dense → the kernel-registry hook), so XLA
        sees the whole segment as the contraction's epilogue. Member
        lowerings are byte-for-byte the staged ``_eval`` paths —
        every re-mask of the zero-padding invariant runs exactly
        where the staged path runs it (MV111's remask census)."""
        from matrel_tpu.ir import fusion as fusion_lib
        members = fusion_lib.region_nodes(root)
        anchor_uid = root.attrs.get("fused_anchor")

        def make_lev(env: Dict[int, Array]):
            """ONE member evaluator for both the region body and the
            epilogue closure — member-lowering semantics must never
            diverge between the two (the MV111 byte-for-byte
            invariant)."""

            def lev(n: MatExpr) -> Array:
                out = env.get(n.uid)
                if out is not None:
                    return out
                if n.uid not in members:
                    out = ev(n)          # region input: its own frame
                else:
                    out = self._eval(n, lev, leaf_arrays, leaf_pos)  # fused-region member — lowers under the single annotate frame opened by ev
                env[n.uid] = out
                return out

            return lev

        env: Dict[int, Array] = {}
        lev = make_lev(env)
        anchor = members.get(anchor_uid) if anchor_uid is not None \
            else None
        if anchor is None or anchor.uid == root.uid:
            return lev(root)

        def epilogue(x: Array) -> Array:
            env2 = dict(env)
            env2[anchor.uid] = x
            return make_lev(env2)(root)

        epi_ew = fusion_lib.epilogue_elementwise_chain(
            root, members, anchor.uid)
        # the anchor's lowering consumes the epilogue: its output IS
        # the region root's value (operand prologues below the anchor
        # lower through lev when the anchor evaluates its children)
        return self._matmul(anchor, lev, epilogue=epilogue,
                            epilogue_elementwise=epi_ew)

    def _solve(self, node: MatExpr, ev) -> Array:
        """X = A⁻¹·B as a dense solve on the LOGICAL shapes — LU by
        default, Cholesky when attrs["assume"] == "pos" (caller asserts
        SPD; a non-SPD lhs under "pos" yields NaNs, not the LU answer).

        Padded rows/cols must be sliced off first — a zero-padded square
        matrix is singular. Like the reference's normal-equations
        workload, this is a local (replicated) solve intended for
        small/medium systems (e.g. the k×k Gram matrix); it is not a
        distributed triangular solve. Computed in f32 for stability,
        cast back when keep_input_dtype asks for it. On a mesh whose
        devices each hold both operands whole (planner.infer_layout
        "rep": what an all-reduce leaves of a row-partitioned table's
        Gram) the replication is pinned: every device factorises its
        own copy inside a ``shard_map``, with no collective."""
        l, r = node.children
        n = l.shape[0]
        m = r.shape[1]

        def local(a, b):
            if node.attrs.get("assume") == "pos":
                c, low = jax.scipy.linalg.cho_factor(a.astype(jnp.float32))
                out = jax.scipy.linalg.cho_solve((c, low),
                                                 b.astype(jnp.float32))
            else:
                out = jnp.linalg.solve(a.astype(jnp.float32),
                                       b.astype(jnp.float32))
            if self.config.keep_input_dtype and a.dtype == b.dtype:
                out = out.astype(a.dtype)
            return out

        if planner.infer_layout(node, self.mesh, self._lay_memo,
                                self.config) == "rep":
            # operands every device holds whole (a Gram and a right-hand
            # side that an all-reduce left on each): every device's own
            # factorisation, pinned so — the partitioner is not asked
            # whether to cut a 1000^2 LU in four
            from jax.sharding import PartitionSpec as P
            from matrel_tpu.utils.compat import shard_map
            out = shard_map(lambda a, b: local(a[:n, :n], b[:n, :m]),
                            mesh=self.mesh, in_specs=(P(), P()),
                            out_specs=P(), check_vma=False)(ev(l), ev(r))
        else:
            a = ev(l)[:n, :n]
            b = ev(r)[:n, :m]
            out = local(a, b)
        return self._pad_to_node(out, node)

    def _inverse(self, node: MatExpr, ev) -> Array:
        """A⁻¹ on the logical shape (see _solve for the padding/dtype
        contract). Prefer solve(A, B) — R7 rewrites A⁻¹·B into it."""
        (c,) = node.children
        n = c.shape[0]
        a = ev(c)[:n, :n]
        out = jnp.linalg.inv(a.astype(jnp.float32))
        if self.config.keep_input_dtype:
            out = out.astype(a.dtype)
        return self._pad_to_node(out, node)

    def _join_axis(self, node: MatExpr, ev) -> Array:
        """Row/col-index joins: statically-shaped pairwise merge along the
        non-join axis (the replication-scheme joins of the reference).
        The planner's attrs['replicate'] (choose_join_scheme) picks the
        scheme: "left"/"right" replicate that operand across the mesh
        (the other keeps its sharding); "align" replicates NOTHING —
        both operands are constrained 1D-sharded along the join axis so
        the pairwise merge computes shard-locally (v3 layout credit)."""
        out_entries = node.shape[0] * node.shape[1]
        cap = self.config.join_pair_cap_entries
        if out_entries > cap:
            raise ValueError(
                f"row/col join output has {node.shape[0]}x"
                f"{node.shape[1]} = {out_entries} entries (> "
                f"join_pair_cap_entries = {cap}); select/aggregate the "
                f"operands first or raise the cap in MatrelConfig.")
        l, r = node.children
        if (node.attrs.get("merge_kind") == "mul"
                and "coo_leaf" in (l.kind, r.kind)):
            # a semiring product in all but its shape (two rows joined,
            # another aggregate above): the leaf is densified below
            self.densified_joins += 1
        a = ev(l)[: l.shape[0], : l.shape[1]]
        b = ev(r)[: r.shape[0], : r.shape[1]]
        rep = node.attrs.get("replicate")
        if rep is not None and self.mesh.size > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            repl = NamedSharding(self.mesh, P(None, None))
            if rep == "left":
                a = jax.lax.with_sharding_constraint(a, repl)
            elif rep == "right":
                b = jax.lax.with_sharding_constraint(b, repl)
            else:  # align
                axes = tuple(self.mesh.axis_names)
                spec = (P(axes, None) if node.kind == "join_rows"
                        else P(None, axes))
                sh = NamedSharding(self.mesh, spec)
                a = jax.lax.with_sharding_constraint(a, sh)
                b = jax.lax.with_sharding_constraint(b, sh)
        merge = node.attrs["merge"]
        if node.kind == "join_rows":
            out = merge(a[:, :, None], b[:, None, :])       # (n, ma, mb)
            out = out.reshape(l.shape[0], l.shape[1] * r.shape[1])
        else:
            out = merge(a[:, None, :], b[None, :, :])       # (na, nb, m)
            out = out.reshape(l.shape[0] * r.shape[0], l.shape[1])
        pshape = padding.padded_shape(node.shape, self.mesh)
        if tuple(out.shape) != pshape:
            out = jnp.pad(out, ((0, pshape[0] - out.shape[0]),
                                (0, pshape[1] - out.shape[1])))
        return out

    def _densifies(self, S) -> None:
        fell = {"shape": list(S.shape), "entries": S.nnz,
                "bytes": 4 * S.shape[0] * S.shape[1]}
        if fell not in self.densified:      # a retrace says it again
            self.densified.append(fell)

    def _pad_to_node(self, out: Array, node: MatExpr) -> Array:
        pshape = padding.padded_shape(node.shape, self.mesh)
        return jnp.pad(out, ((0, pshape[0] - out.shape[0]),
                             (0, pshape[1] - out.shape[1])))

    def _coo_spmv_stack(self, plan, X) -> Array:
        """A·X for the dense (n_cols, k) operand ``X`` of a coo_leaf
        product (or its columns, a sequence of vectors), as a
        (n_rows, k) array; plan tables ride the trace as
        constants (hoisted into call-time args by _hoist_large_consts).
        On real TPU the compact-table Pallas executor runs — faster, and
        the expanded one-hot tables are never built (17× less HBM); CPU
        keeps the expanded XLA path. One column takes the matvec kernel;
        more the k-wide SpMM (a slot's row of X gathered once for all
        its columns), over every source panel of a PanelledPlan, and its
        dense part on the MXU where the matrix has one (PR 43)."""
        from matrel_tpu.config import pallas_enabled, pallas_interpret_mode
        from matrel_tpu.core.coo import plan_parts
        from matrel_tpu.ops import spmv as spmv_lib
        use_pallas = pallas_enabled(self.config)
        parts = plan_parts(plan)
        chunked = parts[0][1].chunk_block is not None
        if self._spmv_forced(plan) == "expanded" and not chunked:
            # measured: the expanded XLA one-hot path beats the compact
            # Pallas scatter for this plan shape class on this backend
            # (a plan laid out in chunks has no expanded form)
            use_pallas = False
        self._ran("pallas_spmv" if use_pallas else "xla")
        if not hasattr(X, "shape"):
            X = jnp.stack(list(X), axis=1)
        k = X.shape[1]
        if use_pallas:
            from matrel_tpu.ops import pallas_spmv as pc
            interp = pallas_interpret_mode(self.config)
            static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
            if self.mesh.size == 1:
                if k == 1 and not hasattr(plan, "parts"):
                    return pc.compact_apply(static, pc.compact_tables(plan),
                                            plan.overflow, X[:, 0],
                                            interpret=interp)[:, None]
                with trace_lib.phase("spmm.plan.upload"):
                    static, part_statics, part_arrays = pc.plan_operands(plan)
                return pc.compact_matmat_parts(static, part_statics,
                                               part_arrays, X,
                                               interpret=interp)
            # multi-device: pallas_call has no SPMD partitioning rule,
            # but shard_map hands it per-device shapes — row-decompose
            # the compact tables over the mesh and run the scatter on
            # each device's block slice (13 B/slot everywhere; the
            # expanded ~224 B/slot XLA tables are never built).
            return self._coo_compact_sharded(pc, plan, static, X, interp)
        if self.mesh.size > 1:
            # replicate the (small) dense operand before the expanded
            # one-hot contraction. A vector sliced from a 2D-sharded
            # operand arrives PARTIALLY sharded (e.g. P('y',) on a
            # (2, 4) mesh) and this container's jax 0.4.37 GSPMD
            # partitioner miscompiles the gather/one-hot contraction
            # over such inputs: every result entry comes out scaled by
            # exactly gx (the unsharded mesh axis), eager and jitted
            # alike — the pre-existing "COO DSL 2x-scale" failure pair
            # and fuzz[49], root-caused round 6. The compact sharded
            # path replicates x by in_spec already; this pins the same
            # contract on the XLA path. The operand is an SpMV input —
            # n_cols floats a column — so the reshard is noise next to
            # the gather it feeds.
            from jax.sharding import NamedSharding, PartitionSpec as P
            X = jax.lax.with_sharding_constraint(
                X, NamedSharding(self.mesh, P()))
        static = (plan.n_rows, plan.n_cols, plan.block)
        arrays = plan.arrays()
        if k == 1:
            return spmv_lib.spmv_apply(static, arrays, X[:, 0])[:, None]
        extra = plan.spmm_extra(arrays)   # reuse the staged expansion
        # ≤64-column chunks bound the (B, C, k) gather/weight
        # intermediates, matching spmv.spmm's col_chunk
        outs = [spmv_lib.spmm_apply(static, arrays, extra, X[:, j:j + 64])
                for j in range(0, k, 64)]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _coo_compact_sharded(self, pc, plan, static, X,
                             interp: bool) -> Array:
        """Compact-table SpMV/SpMM inside the executor's traced program
        on a multi-device mesh: shard_map over the mesh with the tables
        row-decomposed per device (shard_compact_tables), dense operand
        replicated, one tiled all_gather of the result. The sharded
        tables ride the trace as committed device arrays and are hoisted
        into call-time args by _hoist_large_consts like any other
        payload constant."""
        from matrel_tpu.utils.compat import shard_map
        from jax.sharding import PartitionSpec as P
        tables = pc.shard_compact_tables(plan, self.mesh)
        axes = tuple(self.mesh.axis_names)
        ov = plan.overflow
        wide = X.shape[1] > 1
        x = (X if wide else X[:, 0]).astype(jnp.float32)

        def kern(src8, lane, off, val, xx, *ovv):
            apply = (pc.compact_sharded_matmat_apply if wide
                     else pc.compact_sharded_apply)
            return apply(static, (src8, lane, off, val), ovv, xx, axes,
                         interpret=interp)

        sm = shard_map(kern, mesh=self.mesh,
                       in_specs=pc.compact_sharded_specs(axes, len(ov)),
                       out_specs=P(), check_vma=False)
        out = sm(*tables, x, *ov)
        return out if wide else out[:, None]

    def _as_block_sparse(self, leaf_node: MatExpr, bs: int):
        """The BlockSparseMatrix form of an S×S matmul operand:
        sparse_leaf carries one already; coo_leaf is BUCKETED into
        block-granular tiles (never densified — only touched tiles
        materialise), memoised on the matrix per (block_size, mesh)."""
        m = leaf_node.attrs["matrix"]
        if leaf_node.kind == "sparse_leaf":
            return m
        from matrel_tpu.core.sparse import BlockSparseMatrix
        memo = getattr(m, "_block_sparse_memo", None)
        if memo is not None and memo[0] == bs and memo[1] is self.mesh:
            return memo[2]
        # eager even when the cache miss happens inside an outer trace:
        # the conversion builds committed device arrays that must stay
        # static metadata, not tracers (the spmm transpose-memo lesson)
        with jax.ensure_compile_time_eval():
            S = BlockSparseMatrix.from_coo_arrays(
                m.rows, m.cols, m.vals, m.shape, block_size=bs,
                mesh=self.mesh, config=self.config, dtype="float32")
        m._block_sparse_memo = (bs, self.mesh, S)
        return S

    def _spgemm(self, node: MatExpr, epilogue=None,
                epilogue_elementwise: bool = False) -> Array:
        """S×S below the density crossover: tile-intersection SpGEMM —
        neither operand is densified (ops/spgemm.py); the product is
        scattered to the padded dense canonical layout every consumer
        expects (apply_dense pads to padded_shape(node.shape, mesh) —
        the same pair this lowering's consumers compute). The KERNEL
        comes from the planner's ``spgemm_kernel`` stamp (registry
        dispatch — MV110 verifies it); an unstamped node (direct
        execute of a hand-built tree) asks the shared chooser
        itself, so the two can never drift."""
        from matrel_tpu.ops import spgemm as spgemm_lib
        bs = _spgemm_block_size(node, self.config)
        SA = self._as_block_sparse(node.children[0], bs)
        SB = self._as_block_sparse(node.children[1], bs)
        kid = node.attrs.get("spgemm_kernel")
        if kid is None:
            kid, _, _ = spgemm_kernel_choice(node, self.config,
                                             self.mesh)
        self._ran(kid)
        return spgemm_lib.apply_dense(
            SA, SB, self.config, kernel=kid, epilogue=epilogue,
            epilogue_elementwise=epilogue_elementwise)

    def _matmul(self, node: MatExpr, ev, epilogue=None,
                epilogue_elementwise: bool = False) -> Array:
        """``epilogue`` is the fused-region slot (ir/fusion.py): a
        callable applied to THIS matmul's canonical output inside the
        same traced region — the staged consumer chain pushed into the
        producing contraction. Dense strategies, SpMM and SpGEMM
        consume it through their own epilogue slots; every other
        dispatch applies it to the branch's finished output (``fin``),
        so fused and staged lowerings are numerically identical."""
        fin = (lambda out: out) if epilogue is None else epilogue
        l, r = node.children
        # S×S (block-sparse AND element-sparse leaves, any mix): the
        # tile-intersection SpGEMM when the ESTIMATED output block
        # density sits below the crossover — above it the densify
        # fallthrough below wins on MXU throughput. ONE dispatch
        # predicate (_spgemm_dispatch) shared with the planner's
        # pricing/layout/decision readers so they can never drift.
        if _spgemm_dispatch(node, self.config):
            return self._spgemm(node, epilogue=epilogue,
                                epilogue_elementwise=epilogue_elementwise)
        # sampled × dense: (S op (A·B))·Z through S's SpMV plan, the
        # sampled values made on the way (_sampled_product); where no
        # plan answers it the node lowers as an array below, densified
        if l.kind == "sampled" or r.kind == "sampled":
            plan = _sampled_dispatch_plan(node, self.mesh, self.config)
            if plan is not None:
                return fin(self._sampled_product(node, plan, ev))
        # coo_leaf × dense: the SpMV tables' k-wide product where the
        # dense side has at most COO_NARROW_MAX columns (a gathered row
        # of up to 128 float32 fills 128 lanes: 128 columns cost what 8
        # do) and the matrix's plan was not refused; else the leaf is
        # DENSIFIED and the MXU runs the plain dot — a fall-through the
        # planner prices and, on one device, refuses by name where the
        # dense copy does not fit (planner.coo_product). The
        # dispatch predicate is shared with the planner and the
        # autotune walk (_coo_dispatch_plan) so they can never drift.
        if l.kind == "coo_leaf" or r.kind == "coo_leaf":
            # A·S = (Sᵀ·Aᵀ)ᵀ — the matrix's transposed plan, built at
            # most once
            flipped = l.kind != "coo_leaf"
            S = (r if flipped else l).attrs["matrix"]
            dense = l if flipped else r
            plan = _coo_dispatch_plan(node)
            if plan is None:
                self._densifies(S)
                blk = S.to_block(self.mesh, self.config).data
                self._ran("xla")
                a, b = (ev(l), blk) if flipped else (blk, ev(r))
                return strategies.run_matmul("xla", a, b, self.mesh,
                                             self.config, epilogue=epilogue)
            from matrel_tpu.core.coo import plan_facts
            k = dense.shape[0] if flipped else dense.shape[1]
            facts = {"orientation": "transposed" if flipped else "forward",
                     "k": k, **plan_facts(plan, S.nnz)}
            if facts not in self.spmm:      # a retrace says it again
                self.spmm.append(facts)
            x = ev(dense)
            x = (x.T if flipped else x)[: plan.n_cols, :k]
            # here the plan's tables move to the device, once a process
            with trace_lib.phase("spmm.plan", hit=False, **facts):
                out = self._coo_spmv_stack(plan, x)
            return fin(self._pad_to_node(out.T if flipped else out, node))
        if l.kind == "sparse_leaf":
            from matrel_tpu.ops import spmm as spmm_lib
            return spmm_lib.apply(l.attrs["matrix"], ev(r), r.shape,
                                  self.config, epilogue=epilogue,
                                  ran=self._ran)
        if r.kind == "sparse_leaf" and l.kind != "sparse_leaf":
            # A·S = (Sᵀ·Aᵀ)ᵀ — transpose the tile stack once, EAGERLY:
            # this code runs inside the executor's trace, and a traced
            # transpose()/device_put would turn the matrix's static tile
            # metadata into tracers (the SpMM builder reads it on host).
            from matrel_tpu.ops import spmm as spmm_lib
            S = r.attrs["matrix"]
            st = getattr(S, "_transposed_memo", None)
            if st is None:
                with jax.ensure_compile_time_eval():
                    st = S.transpose()
                S._transposed_memo = st
            at = ev(l).T
            out = spmm_lib.apply(st, at, (l.shape[1], l.shape[0]),
                                 self.config, ran=self._ran)
            return fin(out.T)
        # a stamped precision tier OWNS the matmul's numerics — the
        # config-level matmul_precision="high" gram shortcut must not
        # second-guess it (the tier path below emits its own passes):
        # gram_operand finds no Gram there
        gram = planner.gram_operand(node)
        if gram is not None and self.config.matmul_precision == "high":
            side, base = gram
            x = ev(base)
            if x.dtype == jnp.float32:
                # symmetric 2-pass bf16 split for AᵀA / AAᵀ under
                # precision="high": of XLA's three bf16x3 products
                # (hi·hi, hi·lo, lo·hi) the cross terms are transposes
                # of each other in a Gram, so one MXU pass is a k×k
                # transpose instead — 33% fewer matmul FLOPs for the
                # same three products (round-3 floor analysis,
                # docs/ROUND3.md; what a v5e really computes there is
                # in ops/gram.py's docstring). XLA's generic dot
                # cannot apply this: it does not know both operands
                # are the same matrix. The transpose operand is never
                # materialised either.
                from matrel_tpu.ops.gram import symmetric_gram
                strategy = node.attrs.get("strategy", "xla")
                self._ran(strategy)
                if side == "AtA":
                    mm = lambda p, q: strategies.run_matmul(
                        strategy, p.T, q, self.mesh, self.config)
                else:                    # A·Aᵀ
                    mm = lambda p, q: strategies.run_matmul(
                        strategy, p, q.T, self.mesh, self.config)
                return fin(symmetric_gram(x, mm).astype(jnp.float32))
        strategy = node.attrs.get("strategy", "xla")
        if (strategy == planner.in_place_strategy(self.mesh)
                and node.attrs.get("precision_tier") in (None, "f32")):
            out = self._long_contraction(node, ev)
            if out is not None:
                self._ran(strategy)
                return fin(out)
        if strategy == planner.OWN_ROWS:
            raise ValueError(
                f"matmul {node.shape} is stamped {planner.OWN_ROWS!r} but "
                "is no long float32 contraction over operands that lie by "
                "rows (planner.long_in_place): the stamp was not the "
                "planner's")
        a, b = ev(node.children[0]), ev(node.children[1])
        self._ran(strategy)
        if self.config.reshard_peak_budget_bytes > 0:
            # staged reshard lowering (parallel/reshard.py): re-lay
            # each operand to the layout the strategy consumes through
            # the compiled peak-bounded step sequence — explicit
            # per-step collectives under per-kind annotate labels —
            # instead of whatever one-shot move XLA would emit from
            # the shard_map in_spec. Off (the default) this branch
            # constructs nothing and the lowering is bit-identical.
            a, b = self._stage_matmul_operands(node, a, b)
        tier = node.attrs.get("precision_tier")
        if tier is not None and tier != "f32":
            # precision-tiered execution (ops/precision.py): the
            # multi-pass decomposition runs every pass through the SAME
            # stamped strategy recipe, so tiering composes with the
            # distribution plan. Dispatch stays at this one site — the
            # annotate() wrapper above already labels it. The tier owns
            # the output dtype (int tiers keep their exact int32
            # accumulator; bf16 tiers return the f32 accumulation), so
            # the keep_input_dtype cast below does not apply.
            from matrel_tpu.ops import precision as precision_lib
            mm = lambda p, q: strategies.run_matmul(
                strategy, p, q, self.mesh, self.config)
            return fin(precision_lib.tiered_matmul(tier, a, b, mm))

        def storage_epi(out: Array) -> Array:
            # the keep_input_dtype storage cast composes BEFORE the
            # fused epilogue, so the epilogue chain sees exactly the
            # value the staged consumer would (bit-identical numerics
            # between fused and staged lowerings)
            if (self.config.keep_input_dtype and a.dtype == b.dtype
                    and out.dtype != a.dtype):
                out = out.astype(a.dtype)
            return fin(out)

        # a plan the memory reckoning went over carries its verdict: the
        # panelled rmm runs the panel counts the planner derived (one
        # each where none is stamped), and rounds each panel to the
        # storage dtype as it leaves the dot
        panels = (tuple(node.attrs.get("panels", (1, 1)))
                  if "hbm_plan_bytes" in node.attrs else None)
        store = (a.dtype if self.config.keep_input_dtype
                 and a.dtype == b.dtype else None)
        return strategies.run_matmul(strategy, a, b, self.mesh,
                                     self.config, epilogue=storage_epi,
                                     panels=panels, out_dtype=store)

    def _sampled_product(self, node: MatExpr, plan, ev) -> Array:
        """``sampled · Z`` or ``Z · sampled`` (= (sampledᵀ · Zᵀ)ᵀ, on
        the matrix's transposed plan) for a sampled node ``S op (A·B)``
        and a dense side of at most COO_NARROW_MAX columns, fused
        (ops/pallas_spmv.sampled_matmat_parts): an entry's value is made
        from the two rows its coordinates name in ``A`` and ``t(B)``
        and scattered at once; neither ``A·B`` nor the sampled values
        exist whole. The kernel takes the destination's rows off the
        block tile it adds into and makes the dot (``dot`` "kernel" in
        the facts); where ``Z`` is the factor whose rows the plan's
        sources name (t(W) · (V ./ (W·H)); (V ./ (W·H)) · t(H)) the one
        gather there is serves the dot and the scatter."""
        from matrel_tpu.config import pallas_interpret_mode
        from matrel_tpu.core.coo import sampled_facts
        from matrel_tpu.ops import pallas_spmv as pc
        l, r = node.children
        flipped = l.kind != "sampled"
        smp, dense = (r, l) if flipped else (l, r)
        S, A, B = smp.children
        k = dense.shape[0] if flipped else dense.shape[1]
        inner = A.shape[1]

        def a_rows() -> Array:
            return ev(A)[: S.shape[0], :inner]

        def b_rows() -> Array:
            return ev(B).T[: S.shape[1], :inner]

        # the plan's sources are S's columns (t(B)'s rows) in the
        # forward product, its rows (A's) in the transposed one
        of_src, of_dst = (a_rows, b_rows) if flipped else (b_rows, a_rows)
        shared = _same_table(dense, flipped, A if flipped else B,
                             not flipped)
        facts = {"orientation": "transposed" if flipped else "forward",
                 "op": smp.attrs["op"], "k": k, "inner": inner,
                 **sampled_facts(plan, S.attrs["matrix"].nnz, shared)}
        if facts not in self.sampled:       # a retrace says it again
            self.sampled.append(facts)
        self._ran("pallas_spmv")

        z = ev(dense)
        z = (z.T if flipped else z)[: plan.n_cols, :k]
        with trace_lib.phase("sampled.plan", hit=False, **facts):
            with trace_lib.phase("spmm.plan.upload"):
                static, part_statics, part_arrays = pc.plan_operands(plan)
            out = pc.sampled_matmat_parts(
                static, part_statics, part_arrays, z, smp.attrs["op"],
                None if shared else of_src(), of_dst(),
                interpret=pallas_interpret_mode(self.config))
        return self._pad_to_node(out.T if flipped else out, node)

    def _semiring_product(self, node: MatExpr, ev) -> Array:
        """``semiring(max | min, S, x)``: each row's extremum of
        ``S[i, j] · x[j]`` over ALL columns — what ``_agg`` over the
        dense ``join_cols`` gives, missing cells' zeros and all — from
        S's entries alone (core.coo.semiring_apply): through the
        matrix's forward plan and the chunk grid's reduction kernels
        (its hub chunks' slots from the hub table in VMEM, the others'
        through the row gather) where :func:`_semiring_dispatch` finds
        one they read, else XLA's
        segment reduction over the sorted entries. Neither the (n × m)
        join nor a dense S exists."""
        from matrel_tpu.config import pallas_interpret_mode
        from matrel_tpu.core.coo import semiring_apply, semiring_facts
        x = node.children[1]
        m, plan = _semiring_dispatch(node, self.mesh, self.config)
        facts = semiring_facts(m, plan, node.attrs["reduce"])
        if facts not in self.semiring:      # a retrace says it again
            self.semiring.append(facts)
        self._ran("xla" if plan is None else "pallas_spmv")
        col = ev(x)[: m.shape[1], 0]
        if self.mesh.size > 1:
            # one replicated column: the gather and the segment
            # reduction then need no partitioner (see _coo_spmv_stack)
            from jax.sharding import NamedSharding, PartitionSpec as P
            col = jax.lax.with_sharding_constraint(
                col, NamedSharding(self.mesh, P()))
        # here the plan's tables move to the device, once a process
        with trace_lib.phase("semiring.plan", hit=False, **facts):
            y = semiring_apply(m, plan, col, node.attrs["reduce"],
                               interpret=pallas_interpret_mode(self.config))
        return self._pad_to_node(y[:, None], node)

    def _mmchain(self, node: MatExpr, ev) -> Array:
        """``mmchain(X, v[, w])`` = ``t(X) * (w .* (X * v))`` in ONE pass
        over X where it lies (ops/mmchain.py: row tiles of the table
        read from HBM once, both products on the vector unit in
        float32), as the planner stamped it (planner.mmchain_plan's
        facts: a node it declined never reaches the lowering, it was
        written back as its two products)."""
        from matrel_tpu.config import pallas_interpret_mode
        from matrel_tpu.ops import mmchain as mmchain_lib
        facts = node.attrs["mmchain"]
        x, v, *w = node.children
        n, k = x.shape
        self._ran("pallas_mmchain")
        with trace_lib.phase("mmchain.plan", hit=False, **facts):
            out = mmchain_lib.mmchain(
                ev(x), ev(v)[:k, :1], *(ev(c)[:n, :1] for c in w),
                tile=facts["tile_rows"],
                interpret=pallas_interpret_mode(self.config))
        return self._pad_to_node(out, node)

    def _long_contraction(self, node: MatExpr, ev) -> Optional[Array]:
        """A product over a LONG float32 contraction (the regression's
        t(X)·X and t(X)·y over millions of rows) multiplied where its
        operands lie: accumulated in panels (strategies.dot_in_panels),
        or None where the product is not one (bfloat16 and integer
        tables round, or do not round, their answers on other terms).
        A transposed operand is handed over by its dimension,
        untransposed. Where both operands are the same one
        (planner.long_gram) the panels multiply the upper block
        triangle alone (strategies.gram_in_panels), and where a second
        product over the same table rides that loop
        (planner.gram_riders) the pair is evaluated once: whichever of
        the two is reached first runs the loop, and the other's product
        waits in ``_rode``. One device runs the loop as it is — or,
        where planner.gram_kernel_plan says ``one_read``, ONE kernel
        over the table where it lies in place of the loop
        (:meth:`_gram_kernel`), the rider inside it; a mesh
        (the stamp planner.OWN_ROWS: the tables lie cut over all
        devices along the contraction) runs it a device at a time
        inside one ``shard_map`` and all-reduces the accumulators once
        (strategies.over_own_rows), and its products come out
        replicated."""
        l, r = node.children
        if l.shape[1] < strategies.LONG_CONTRACTION:
            return None
        own = functools.partial(strategies.over_own_rows, self.mesh)
        gram, rider = self._riders.get(node.uid, (None, None))
        if node is gram:
            x, y = ev(l.children[0]), ev(rider.children[1])
            pair = self._gram_kernel(node, x, y)
            if pair is None:
                pair = own(
                    lambda reduce, x, y: strategies.gram_in_panels(
                        x, 0, self.config, rhs=y, reduce=reduce),
                    (x, y), (0, 0))
            out, self._rode[rider.uid] = pair
            return out
        if node is rider:
            ev(gram)
            if node.uid in self._rode:  # else the Gram was lowered elsewhere
                return self._rode[node.uid]
        found = planner.long_gram(node, self.mesh, self.config,
                                  self._dt_memo)
        if found is not None:
            side, base = found
            c = 0 if side == "AtA" else 1
            x = ev(base)
            out = self._gram_kernel(node, x)
            if out is None:
                out = own(lambda reduce, x: strategies.gram_in_panels(
                    x, c, self.config, reduce=reduce), (x,), (c,))
            return out
        (a, ca), (b, cb) = planner.own_rows_operands(node, self.mesh)
        a, b = ev(a), ev(b)
        if a.dtype != jnp.float32 or b.dtype != jnp.float32:
            return None
        return own(lambda reduce, a, b: strategies.dot_in_panels(
            a, ca, b, cb, self.config, reduce=reduce), (a, b), (ca, cb))

    def _gram_kernel(self, node: MatExpr, x: Array, y=None):
        """A long Gram ``t(x) * x`` (with ``y``: the pair with ``t(x) *
        y``) as ONE kernel over the table where it lies
        (strategies.gram_in_tiles), or None where
        planner.gram_kernel_plan declines it and the loop of panels
        multiplies it (its ``why_not`` is on the plan's record)."""
        from matrel_tpu.config import pallas_interpret_mode
        facts = planner.gram_kernel_plan(node, self.mesh, self.config,
                                         self._dt_memo)
        if not facts["one_read"]:
            return None
        facts["rider"] = 0 if y is None else y.shape[1]
        self._ran("pallas_gram")
        with trace_lib.phase("gram.plan", hit=False, **facts):
            return strategies.gram_in_tiles(
                x, self.config, rhs=y, tile=facts["tile_rows"],
                interpret=pallas_interpret_mode(self.config))

    def _stage_root_relay(self, root: MatExpr, out: Array) -> Array:
        """Root output → canonical 2d through the compiled reshard
        steps (budget > 0 only; vectors and indivisible shapes keep
        the legacy constraint). The derivation is
        ``reshard.root_relay_plan`` — shared with MV109, which is the
        layer that BLOCKS an over-budget root move pre-trace
        (verify_plans="error"); the lowering itself still applies the
        min-peak plan, which is never worse than the one-shot move."""
        from matrel_tpu.parallel import reshard as reshard_lib
        plan = reshard_lib.root_relay_plan(root, self.mesh, self.config,
                                           self._lay_memo,
                                           self._dt_memo)
        if plan is None:
            return out
        return reshard_lib.apply_staged(out, plan, self.mesh)

    def _stage_matmul_operands(self, node: MatExpr, a: Array,
                               b: Array) -> Tuple[Array, Array]:
        """Apply the staged ReshardPlans of a dense matmul's operand
        re-lays (reshard.staged_matmul_moves — the ONE derivation
        shared with matmul_decisions and MV109). With autotune on, a
        MEASURED "naive" winner for the move's shape class skips the
        staging (the closed measurement loop overrules the model, the
        matmul-strategy contract)."""
        from matrel_tpu.parallel import reshard as reshard_lib
        moves = reshard_lib.staged_matmul_moves(
            node, self.mesh, self.config, self._lay_memo, self._dt_memo)
        arrs = [a, b]
        for i, plan in moves:
            if self.config.autotune:
                from matrel_tpu.parallel import autotune
                choice = autotune.lookup_or_measure_reshard(
                    plan, self.mesh, self.config)
                if choice == "naive":
                    continue
            arrs[i] = reshard_lib.apply_staged(arrs[i], plan, self.mesh)
        return arrs[0], arrs[1]

    def _elemwise(self, node: MatExpr, ev) -> Array:
        l, r = node.children
        a, b = ev(l), ev(r)
        broadcast = l.shape != r.shape
        if broadcast:
            # slice logical size-1 dims so padded shapes broadcast correctly
            a = self._slice_for_broadcast(a, l.shape, node.shape)
            b = self._slice_for_broadcast(b, r.shape, node.shape)
        op = node.attrs["op"]
        out = self._elemwise_op(op, a, b)
        if broadcast and op != "mul":
            out = _mask_to_logical(out, node.shape)
        return out

    @staticmethod
    def _elemwise_op(op: str, a: Array, b: Array) -> Array:
        if op == "add":
            return a + b
        if op == "sub":
            return a - b
        if op == "mul":
            return a * b
        if op == "div":
            safe_b = jnp.where(b == 0, jnp.ones((), b.dtype), b)
            return jnp.where(b == 0, jnp.zeros((), jnp.result_type(a, b)),
                             a / safe_b)
        if op == "min":
            return jnp.minimum(a, b)
        if op == "max":
            return jnp.maximum(a, b)
        raise NotImplementedError(op)

    @staticmethod
    def _slice_for_broadcast(x: Array, lshape, out_shape) -> Array:
        if lshape[0] == 1 and out_shape[0] != 1 and x.shape[0] != 1:
            x = x[:1, :]
        if lshape[1] == 1 and out_shape[1] != 1 and x.shape[1] != 1:
            x = x[:, :1]
        return x

    def _scalar(self, node: MatExpr, ev) -> Array:
        x = ev(node.children[0])
        op, v = node.attrs["op"], node.attrs["value"]
        if op == "mul":
            return x * jnp.asarray(v, x.dtype)
        if op == "add":
            out = x + jnp.asarray(v, x.dtype)
            return _mask_to_logical(out, node.shape) if v != 0.0 else out
        if op == "pow":
            out = jnp.power(x, jnp.asarray(v, x.dtype))
            return _mask_to_logical(out, node.shape) if v <= 0 else out
        raise NotImplementedError(op)

    def _agg(self, node: MatExpr, ev) -> Array:
        (child,) = node.children
        if child.kind == "join_value":
            # never materialise the pair matrix under an aggregate —
            # stream it (sort-based or chunked; value_join.py)
            return self._agg_join_value(node, child, ev)
        x = ev(child)
        kind, axis = node.attrs["agg"], node.attrs["axis"]
        n, m = child.shape
        pn, pm = x.shape
        if axis == "diag":
            d = jnp.diagonal(x)[:n]
            return _diag_reduce(d, kind).reshape(1, 1).astype(x.dtype)
        ax = {"row": 1, "col": 0, "all": None}[axis]

        def finish(res: Array) -> Array:
            if axis == "row":
                return res.reshape(pn, 1) if res.ndim == 1 else res
            if axis == "col":
                return res.reshape(1, pm) if res.ndim == 1 else res
            return res.reshape(1, 1)

        if kind == "sum":
            out = finish(jnp.sum(x, axis=ax))
        elif kind == "count":
            out = finish(jnp.sum((x != 0), axis=ax).astype(x.dtype))
        elif kind == "avg":
            s = jnp.sum(x, axis=ax)
            c = jnp.sum((x != 0), axis=ax)
            out = finish(jnp.where(c > 0, s / c, 0).astype(x.dtype))
        elif kind in ("max", "min"):
            fill = -jnp.inf if kind == "max" else jnp.inf
            valid = _row_mask(n, pn) & _col_mask(m, pm)
            masked = jnp.where(valid, x, jnp.asarray(fill, x.dtype))
            red = jnp.max if kind == "max" else jnp.min
            out = finish(red(masked, axis=ax))
            out = jnp.where(jnp.isfinite(out), out, jnp.zeros((), x.dtype))
        else:
            raise NotImplementedError(kind)
        # zero out aggregate rows/cols that lie in the padded region
        return _mask_to_logical(out, node.shape)

    def _vec(self, node: MatExpr, ev) -> Array:
        (child,) = node.children
        x = ev(child)
        n, m = child.shape
        v = x[:n, :m].T.reshape(n * m, 1)  # column-major vec
        pshape = padding.padded_shape(node.shape, self.mesh)
        if v.shape[0] != pshape[0]:
            v = jnp.pad(v, ((0, pshape[0] - v.shape[0]), (0, 0)))
        return v

    def _select_index(self, node: MatExpr, ev) -> Array:
        x = ev(node.children[0])
        rows, cols = node.attrs["rows"], node.attrs["cols"]
        pn, pm = x.shape
        keep = jnp.ones((), dtype=bool)
        if rows is not None:
            keep = keep & rows(jnp.arange(pn))[:, None]
        if cols is not None:
            keep = keep & cols(jnp.arange(pm))[None, :]
        return jnp.where(keep, x, jnp.zeros((), x.dtype))

    def _entry_vectors(self, node: MatExpr, ev):
        """Column-major logical-entry vectors (va, vb) of a join_value
        node's operands — the pair matrix's row/col coordinates — plus
        the dtype the DENSE lowering would produce (operand promotion),
        so the streaming result is cast to match it."""
        l, r = node.children
        a, b = ev(l), ev(r)
        va = a[: l.shape[0], : l.shape[1]].T.reshape(-1)
        vb = b[: r.shape[0], : r.shape[1]].T.reshape(-1)
        out_dtype = jnp.result_type(a.dtype, b.dtype)
        return va.astype(jnp.float32), vb.astype(jnp.float32), out_dtype

    def _agg_join_value(self, node: MatExpr, jnode: MatExpr, ev) -> Array:
        """agg(join_on_value(A, B)) without materialising the (na, nb)
        pair matrix: sort-based O((na+nb)·log nb) for structured
        predicate+merge, bounded chunkwise enumeration for black-box
        callables (capped), elementwise for the diagonal."""
        from matrel_tpu.relational import value_join as vj
        kind, axis = node.attrs["agg"], node.attrs["axis"]
        merge_fn = jnode.attrs["merge"]
        pred_fn = jnode.attrs["predicate"]
        pred_kind = jnode.attrs.get("pred_kind")
        merge_kind = jnode.attrs.get("merge_kind")
        na, nb = jnode.shape
        structured = (merge_kind is not None
                      and (pred_kind is not None or pred_fn is None)
                      and kind in vj.AGG_KINDS)
        if (axis != "diag" and not structured
                and na * nb > self.config.join_bruteforce_max_pairs):
            # guard BEFORE evaluating the operands — same guard-first
            # pattern as _join_value; shapes are static
            raise ValueError(
                f"aggregated value-join with callable merge/"
                f"predicate must enumerate {na}x{nb} = {na * nb} "
                f"pairs (> join_bruteforce_max_pairs = "
                f"{self.config.join_bruteforce_max_pairs}). Use "
                f"structured forms (predicate in "
                f"{expr_mod.JOIN_PREDS}, merge in "
                f"{expr_mod.JOIN_MERGES}) for the O(n log n) sort "
                f"path, or raise the cap.")
        va, vb, out_dtype = self._entry_vectors(jnode, ev)
        # a tiny QUERY side isn't worth resharding (GSPMD falls back to
        # full rematerialisation moving small leaf shardings around);
        # the query side is va for row/all aggregates, vb for col
        query_n = na if axis in ("row", "all") else nb
        if (axis != "diag" and self.mesh.size > 1
                and query_n >= 128 * self.mesh.size):
            # BOTH streaming paths are embarrassingly parallel over the
            # query side: the sort path's searchsorted/prefix-gathers
            # and the chunked path's per-row tile reductions each run
            # on query_n/P entries per chip once the query entries are
            # sharded across every device (the other operand
            # replicated — it is read whole by every row's scan)
            from jax.sharding import NamedSharding, PartitionSpec as P
            axes = tuple(self.mesh.axis_names)
            flat = NamedSharding(self.mesh, P(axes))
            repl = NamedSharding(self.mesh, P())
            sa, sb = ((flat, repl) if axis in ("row", "all")
                      else (repl, flat))            # col: roles swap
            va = jax.lax.with_sharding_constraint(va, sa)
            vb = jax.lax.with_sharding_constraint(vb, sb)
        if axis == "diag":
            L = min(na, nb)
            d = merge_fn(va[:L], vb[:L])
            if pred_fn is not None:
                d = jnp.where(pred_fn(va[:L], vb[:L]), d, 0.0)
            out = _diag_reduce(d, kind)
            return self._pad_to_node(
                out.reshape(1, 1).astype(out_dtype), node)
        if structured:
            out = vj.axis_agg_sorted(va, vb, pred_kind or "always",
                                     merge_kind, kind, axis)
        else:
            out = vj.axis_agg_chunked(va, vb, merge_fn, pred_fn, kind,
                                      axis,
                                      self.config.join_chunk_entries)
        if axis == "row":
            out = out.reshape(-1, 1)
        elif axis == "col":
            out = out.reshape(1, -1)
        else:
            out = out.reshape(1, 1)
        return self._pad_to_node(out.astype(out_dtype), node)

    def _join_value(self, node: MatExpr, ev) -> Array:
        """Value-join: all pairs (a_entry, b_entry) with predicate; output is
        the (|A|, |B|) pair matrix (entries merge(va, vb) where predicate
        holds, else 0). Blockwise outer construction. MATERIALISING the
        pair matrix is capped (config.join_pair_cap_entries) — aggregate
        the join for the streaming path (_agg_join_value)."""
        na, nb = node.shape
        cap = self.config.join_pair_cap_entries
        if na * nb > cap:
            raise ValueError(
                f"materialising a {na}x{nb} value-join pair matrix "
                f"({na * nb} entries) exceeds join_pair_cap_entries = "
                f"{cap}. Aggregate the join (e.g. agg(join, 'sum', "
                f"'row')) to stream it without materialisation, or "
                f"raise the cap in MatrelConfig.")
        l, r = node.children
        a, b = ev(l), ev(r)
        va = a[: l.shape[0], : l.shape[1]].T.reshape(-1)  # column-major entries
        vb = b[: r.shape[0], : r.shape[1]].T.reshape(-1)
        merge, pred = node.attrs["merge"], node.attrs["predicate"]
        A = va[:, None]
        B = vb[None, :]
        out = merge(A, B)
        if pred is not None:
            out = jnp.where(pred(A, B), out, jnp.zeros((), out.dtype))
        pshape = padding.padded_shape(node.shape, self.mesh)
        if tuple(out.shape) != pshape:
            out = jnp.pad(out, ((0, pshape[0] - out.shape[0]),
                                (0, pshape[1] - out.shape[1])))
        return out


_HOIST_BYTES = 1 << 20


def _hoist_large_consts(fn, example_args):
    """Turn large trace constants into call-time arguments.

    Sparse leaves embed their payloads (tile stacks, one-hot plan
    tables) as constants of the traced program. XLA treats array
    constants as parameters, but they still ship INSIDE the compiled
    executable: compile memory and time grow with them, HBM holds a
    second copy, and the persistent compile cache refuses the entry
    (measured on a v5e, PR 22: a closed-over 384-tile f32 stack made a
    1.07 GB executable, over the cache's entry limit).
    Small constants (masks, iotas) stay embedded so XLA can fold them.

    Returns (wrapped_fn, big_consts): call wrapped_fn(*leaves,
    *big_consts). (jax.closure_convert is NOT usable here: it only
    hoists consts that might carry AD perturbations; concrete payload
    arrays stay closed over.)
    """
    from jax.tree_util import tree_unflatten

    import numpy as _np

    def _nbytes(c):
        # consts may be jax Arrays, numpy arrays, or TypedNdArray
        # wrappers (jax 0.9) that expose shape/dtype but not nbytes
        try:
            return int(_np.prod(c.shape)) * _np.dtype(c.dtype).itemsize
        except (AttributeError, TypeError):
            return 0

    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
        *example_args)
    consts = closed.consts
    big_ix = [i for i, c in enumerate(consts)
              if _nbytes(c) >= _HOIST_BYTES]
    # keep only the jaxpr and the SMALL consts: holding `closed` (or the
    # full consts list) in the closure would pin the big payload host
    # copies for the plan's lifetime — the very arrays the hoist manages
    jaxpr = closed.jaxpr
    small = {i: c for i, c in enumerate(consts) if i not in set(big_ix)}
    big_vals = [jnp.asarray(consts[i]) for i in big_ix]
    n_leaf = len(example_args)
    n_consts = len(consts)
    out_tree = jax.tree_util.tree_structure(out_shape)
    del closed, consts

    def hoisted(*args):
        leafs, bigs = args[:n_leaf], args[n_leaf:]
        it = iter(bigs)
        cs = [small[i] if i in small else next(it)
              for i in range(n_consts)]
        flat = jax.core.eval_jaxpr(jaxpr, cs, *leafs)
        return tree_unflatten(out_tree, flat)

    # returned even when nothing was hoisted: the trace is already paid
    # for, and handing back the raw fn would make jax.jit trace the
    # whole program a second time on every dense compile
    return hoisted, big_vals


def _example_avals(leaf_order):
    return [jax.ShapeDtypeStruct(l.attrs["matrix"].data.shape,
                                 l.attrs["matrix"].data.dtype)
            for l in leaf_order]


@dataclasses.dataclass
class CompiledPlan:
    """A jitted plan plus its leaf binding order — re-runnable with fresh
    leaf data (the analogue of re-executing an RDD lineage on new blocks).
    ``extra_args`` are hoisted large constants (sparse payloads), appended
    to every call."""

    jitted: Callable
    leaf_order: List[MatExpr]
    optimized: MatExpr
    mesh: Mesh
    config: MatrelConfig
    extra_args: List = dataclasses.field(default_factory=list)
    _donating: Dict[tuple, Callable] = dataclasses.field(default_factory=dict)
    #: compile-time observability record (obs/ event log + explain):
    #: optimize_ms, trace_ms, rewrite-rule hit counts; per-matmul
    #: planner decisions ("matmuls") are added lazily by
    #: :func:`plan_matmul_decisions` so the obs-off compile path does
    #: not pay for them.
    meta: Dict = dataclasses.field(default_factory=dict)

    def run(self, bindings: Optional[Dict[int, BlockMatrix]] = None,
            donate: bool = False) -> BlockMatrix:
        """Execute with current or rebound leaves.

        donate=True hands the REBOUND leaf buffers to XLA (input/output
        aliasing — halves HBM traffic in C←f(C) iteration patterns); the
        donated BlockMatrices must not be used afterwards.
        """
        arrays = []
        donated = []
        for i, l in enumerate(self.leaf_order):
            bound = (bindings or {}).get(l.uid)
            if bound is not None:
                donated.append(i)
            m = bound if bound is not None else l.attrs["matrix"]
            arrays.append(m.data)
        # jax's and the runtime's share of the session's ``dispatch``
        with trace_lib.span("dispatch.launch"):
            if donate and donated and self.config.donate_intermediates:
                out = self._donating_fn(tuple(donated))(*arrays,
                                                        *self.extra_args)
            else:
                out = self.jitted(*arrays, *self.extra_args)
        return BlockMatrix.from_array(
            out, self.optimized.shape, self.mesh,
            padding.canonical_spec(tuple(out.shape), self.mesh),
            nnz=self.optimized.nnz,
        )

    def bound_runner(self, rebind_uids: tuple = (), donate: bool = False):
        """Low-overhead repeated-execution path for iteration loops (the
        analogue of re-executing a compiled plan across RDD iterations).

        Precomputes the leaf layout ONCE and returns ``fn(*arrays) ->
        jax.Array``: positional raw padded arrays for the leaves named in
        ``rebind_uids`` (in that order), raw padded output — none of
        ``run``'s per-call dict walking, spec derivation or BlockMatrix
        wrapping. With donate=True the rebound buffers are donated
        (C←f(C) patterns run with input/output aliasing).
        """
        uid_pos = {l.uid: i for i, l in enumerate(self.leaf_order)}
        positions = [uid_pos[u] for u in rebind_uids]
        base = [l.attrs["matrix"].data for l in self.leaf_order]
        if donate and positions and self.config.donate_intermediates:
            jfn = self._donating_fn(tuple(sorted(positions)))
        else:
            jfn = self.jitted

        extra = tuple(self.extra_args)
        if not positions:
            return lambda: jfn(*base, *extra)

        def call(*arrays):
            if len(arrays) != len(positions):
                raise ValueError(
                    f"bound runner expects {len(positions)} rebound "
                    f"array(s), got {len(arrays)}")
            argv = list(base)
            for p, a in zip(positions, arrays):
                argv[p] = a
            return jfn(*argv, *extra)

        return call

    def _donating_fn(self, key: tuple):
        """Cached donating variant of the jitted program (key = sorted
        donated argument positions)."""
        jfn = self._donating.get(key)
        if jfn is None:
            jfn = jax.jit(self.jitted.__wrapped__, donate_argnums=key)
            self._donating[key] = jfn
        return jfn

    def hlo(self) -> str:
        """Optimized HLO text — for plan-shape assertions on collectives."""
        arrays = [l.attrs["matrix"].data for l in self.leaf_order]
        return self.jitted.lower(*arrays,
                                 *self.extra_args).compile().as_text()

    def collectives(self) -> Dict[str, int]:
        """Count of each collective op in the compiled HLO — the assertable
        'plan shape' (SURVEY.md §4: the Catalyst comparePlans analogue at
        the physical level)."""
        import re as _re
        text = self.hlo()
        counts: Dict[str, int] = {}
        for op in ("all-gather", "reduce-scatter", "all-reduce",
                   "collective-permute", "all-to-all"):
            n = len(_re.findall(rf"\b{op}\b", text))
            if n:
                counts[op] = n
        return counts

    def explain(self) -> str:
        """Logical/physical plan summary incl. strategies and collectives."""
        from matrel_tpu.ir.expr import pretty
        lines = ["== Optimized plan ==",
                 pretty(self.optimized, mesh=self.mesh,
                        config=self.config)]
        try:
            lines += ["== Collectives ==", str(self.collectives())]
        except Exception as e:  # matlint: disable=ML007 explain() best-effort — the plan text above still renders, and the failure is shown
            lines += ["== Collectives ==",
                      f"unavailable: {type(e).__name__}: {e}"]
        return "\n".join(lines)


@dataclasses.dataclass
class MultiPlan:
    """Several optimized roots compiled into ONE XLA program (one fusion
    and CSE domain, one dispatch) — the analogue of a multi-action Spark
    job sharing its lineage. Parity with :class:`CompiledPlan`: rebound
    leaves can be donated (``donate=True``), and the session caches
    compiled MultiPlans in its plan cache alongside single plans
    (``extra_args`` carries the hoisted payloads the byte budget
    accounts)."""

    jitted: Callable
    leaf_order: List[MatExpr]
    optimized: Tuple[MatExpr, ...]
    mesh: Mesh
    config: MatrelConfig
    extra_args: List = dataclasses.field(default_factory=list)
    _donating: Dict[tuple, Callable] = dataclasses.field(
        default_factory=dict)
    meta: Dict = dataclasses.field(default_factory=dict)

    def run(self, bindings: Optional[Dict[int, BlockMatrix]] = None,
            donate: bool = False) -> Tuple[BlockMatrix, ...]:
        """Execute with current or rebound leaves. ``donate=True``
        hands REBOUND leaf buffers to XLA (input/output aliasing —
        the same contract as CompiledPlan.run: donated BlockMatrices
        must not be used afterwards)."""
        arrays = []
        donated = []
        for i, l in enumerate(self.leaf_order):
            bound = (bindings or {}).get(l.uid)
            if bound is not None:
                donated.append(i)
            m = bound if bound is not None else l.attrs["matrix"]
            arrays.append(m.data)
        with trace_lib.span("dispatch.launch"):
            if donate and donated and self.config.donate_intermediates:
                outs = self._donating_fn(tuple(donated))(*arrays,
                                                         *self.extra_args)
            else:
                outs = self.jitted(*arrays, *self.extra_args)
        return tuple(
            BlockMatrix.from_array(
                out, root.shape, self.mesh,
                padding.canonical_spec(tuple(out.shape), self.mesh),
                nnz=root.nnz)
            for out, root in zip(outs, self.optimized))

    def _donating_fn(self, key: tuple):
        """Cached donating variant (key = sorted donated argument
        positions) — CompiledPlan's idiom."""
        jfn = self._donating.get(key)
        if jfn is None:
            jfn = jax.jit(self.jitted.__wrapped__, donate_argnums=key)
            self._donating[key] = jfn
        return jfn


def _precision_meta(opts, cfg) -> Optional[Dict]:
    """Plan-level precision metadata for ``plan.meta`` (obs events /
    explain): the query SLA, the stamped tier census, and the
    documented worst-case relative error bound over every tiered
    matmul (TIER_EPS · k — the bound bench/soak assert against). None
    under the "default" SLA, so the default compile path pays zero
    extra walks (the bit-identity contract)."""
    if cfg.precision_sla == "default":
        return None
    from matrel_tpu.parallel import planner as planner_mod
    tiers: Dict[str, int] = {}
    bound = [0.0]
    seen: set = set()

    def walk(n: MatExpr):
        if n.uid in seen:
            return
        seen.add(n.uid)
        for c in n.children:
            walk(c)
        t = n.attrs.get("precision_tier")
        if n.kind == "matmul" and t is not None:
            tiers[t] = tiers.get(t, 0) + 1
            eps = planner_mod.TIER_EPS.get(t)
            if eps:
                bound[0] = max(bound[0], eps * n.children[0].shape[1])

    for o in opts:
        walk(o)
    return {"sla": cfg.precision_sla, "tiers": tiers,
            "est_rel_err_bound": bound[0]}


def _fusion_meta(opts, cfg) -> Optional[Dict]:
    """Plan-level fusion roll-up for ``plan.meta`` (obs query events,
    ``history --summary``'s fusion line): region count, merged member
    census, and the modelled dispatch/HBM savings of every stamped
    boundary. None with fusion off — the default compile path pays
    zero extra walks (the bit-identity contract, the _precision_meta
    idiom)."""
    if not cfg.fusion_enable:
        return None
    from matrel_tpu.ir import fusion as fusion_lib
    regions = 0
    census: Dict[str, int] = {}
    saved_d = 0
    saved_b = 0.0
    for o in opts:
        for node in fusion_lib.collect_stamps(o):
            regions += 1
            for k, v in (node.attrs.get("fused_census") or {}).items():
                census[k] = census.get(k, 0) + v
            saved_d += int(node.attrs.get("fused_saved_dispatches") or 0)
            saved_b += float(node.attrs.get("fused_saved_hbm_bytes")
                             or 0.0)
    return {"regions": regions, "census": census,
            "est_saved_dispatches": saved_d,
            "est_saved_hbm_bytes": saved_b}


def _hbm_meta(opts, mesh, cfg) -> Dict:
    """The plan-level memory reckoning's verdict for ``plan.meta`` (and,
    through it, the ``dispatch`` span and a Deployment's notes): the
    mesh's grid, the plan's reckoned peak on one device
    (``hbm_plan_bytes``: the largest over its products, solves and
    materialised transposes) and one record each (planner.hbm_report),
    each also a ``plan.strategy`` span under ``compile``. A one-device
    plan over the limit is refused here, before anything is traced
    (planner.refuse_over_limit)."""
    meta: Dict = {"mesh": "x".join(
        str(g) for g in mesh_lib.mesh_grid_shape(mesh))}
    products = [rec for o in opts for rec in planner.hbm_report(o)]
    if products:
        meta["hbm_plan_bytes"] = max(p["hbm_plan_bytes"] for p in products)
        meta["products"] = products
        chains = [rec["mmchain"] for rec in products if "mmchain" in rec]
        if chains:
            # planner.mmchain_plan's facts of every fused chain, and of
            # every chain the planner un-fused (``why_not``): a
            # ``matrel.mmchain.plan`` span each at every dispatch
            meta["mmchain"] = chains
        for rec in products:
            with trace_lib.span("plan.strategy", **rec):
                pass
    planner.refuse_over_limit(opts, mesh, cfg)
    return meta


def _coo_meta(meta: Dict, low: "Lowerer") -> None:
    """What the lowering said of the plan's coo_leaf products, for
    ``plan.meta`` — present only where the plan has such a product (the
    trace has run: the lists are whole): ``spmm``, the SpMV plans that
    answer (a ``matrel.spmm.plan`` span each at every dispatch), and
    ``densified_products``, the leaves that were densified instead."""
    if low.spmm:
        meta["spmm"] = low.spmm
    if low.sampled:
        meta["sampled"] = low.sampled
    if low.semiring:
        meta["semiring"] = low.semiring
    if low.densified_joins:
        meta["densified_joins"] = low.densified_joins
    if low.densified:
        meta["densified_products"] = low.densified


def _verify_plans(opts, mesh, cfg) -> Optional[List[dict]]:
    """Run the static verifier (matrel_tpu/analysis/) over annotated
    roots when ``config.verify_plans`` asks for it — PRE-execution,
    pre-trace: at "error" an infeasible/misdescribed plan raises here
    and nothing is ever lowered, at "warn" the findings are logged and
    recorded. Returns the diagnostic dicts for plan.meta (None when the
    gate is off, so the obs-off compile path pays nothing). Lazily
    imported to keep the analysis->executor dependency one-way at
    module load."""
    if cfg.verify_plans == "off":
        return None
    from matrel_tpu import analysis
    diags = []
    for o in opts:
        diags.extend(analysis.verify_plan(o, mesh, cfg))
    analysis.enforce(diags, cfg.verify_plans)
    return [d.to_dict() for d in diags]


# -- rows deltas: the in-place update and the views' patches ------------------
# (serve/ivm.py runs them; they are programs, so they are emitted here)

_ROWS_PROGRAMS: Dict[tuple, Callable] = {}


def rows_update(contiguous: bool) -> Callable:
    """The program that REPLACES c rows of a dense table where it lies:
    ``fn(table, new, at) -> (table', old)`` with ``table`` DONATED (its
    buffer is the output's: at no point do two tables exist), ``new``
    the c x m replacement rows, ``at`` the first row id (``contiguous``:
    one run of ids, a dynamic slice) or the c ids (a gather and a
    scatter), and ``old`` the rows that leave, read out before they are
    overwritten. ``at`` is an argument: a window that moves compiles
    once."""
    key = ("update", contiguous)
    fn = _ROWS_PROGRAMS.get(key)
    if fn is None:
        def update(table, new, at):
            new = new.astype(table.dtype)
            if contiguous:
                old = jax.lax.dynamic_slice_in_dim(table, at, new.shape[0],
                                                   axis=0)
                return (jax.lax.dynamic_update_slice_in_dim(
                    table, new, at, axis=0), old)
            return table.at[at].set(new), jnp.take(table, at, axis=0)

        fn = _ROWS_PROGRAMS[key] = jax.jit(update, donate_argnums=0)
    return fn


def _two_sum(hi, lo, d):
    """(hi, lo) + d, the pair carried as an unevaluated sum of two
    float32 words (Knuth's TwoSum, then one renormalisation): ``hi`` is
    the view as it is read, ``lo`` what its roundings left over, so
    that thousands of ``view += small - small`` at a large view lose
    nothing the next patch cannot see."""
    s = hi + d
    b = s - hi
    lo = lo + ((hi - (s - b)) + (d - b))
    out = s + lo
    return out, lo - (out - s)


def rows_patch(form: str, contiguous: bool,
               config: Optional[MatrelConfig] = None) -> Callable:
    """The program that corrects one view for a rows delta of its table
    (ir/delta.RowsPatch): ``fn(hi, lo, new, old, partner, at) -> (hi',
    lo')`` with the view's two words DONATED. The corrections are
    contractions over the c rows that changed, float32 at the session's
    ``matmul_precision``, in the panels every long contraction of the
    program is multiplied in (strategies.gram_in_panels: the upper
    block triangle, mirrored; strategies.dot_in_panels); the partner's
    same rows are sliced from its table inside the program. A Gram adds
    ``t(new) * new`` and takes ``t(old) * old`` away as two corrections
    of their own, not as their difference: what a slot added when its
    rows came is what it takes away when they leave, bit for bit."""
    cfg = config or default_config()
    key = ("patch", form, contiguous, cfg.matmul_precision)
    fn = _ROWS_PROGRAMS.get(key)
    if fn is None:
        def patch(hi, lo, new, old, partner, at):
            if form == "gram":
                hi, lo = _two_sum(hi, lo,
                                  strategies.gram_in_panels(new, 0, cfg))
                return _two_sum(hi, lo,
                                -strategies.gram_in_panels(old, 0, cfg))
            theirs = (jax.lax.dynamic_slice_in_dim(
                partner, at, new.shape[0], axis=0) if contiguous
                else jnp.take(partner, at, axis=0))
            mine = new - old
            if form == "left":
                d = strategies.dot_in_panels(mine, 0, theirs, 0, cfg)
            else:
                d = strategies.dot_in_panels(theirs, 0, mine, 0, cfg)
            return _two_sum(hi, lo, d)

        fn = _ROWS_PROGRAMS[key] = jax.jit(patch, donate_argnums=(0, 1))
    return fn


def compile_exprs(exprs, mesh: Optional[Mesh] = None,
                  config: Optional[MatrelConfig] = None) -> MultiPlan:
    """Compile several expressions into one program with shared leaves."""
    cfg = config or default_config()
    exprs = tuple(exprs)
    all_leaves = []
    seen = set()
    for e in exprs:
        for l in expr_leaves(e):
            if l.uid not in seen:
                seen.add(l.uid)
                all_leaves.append(l)
    if mesh is None:
        mesh = (all_leaves[0].attrs["matrix"].mesh if all_leaves
                else mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names))
    for e in exprs:
        _check_one_mesh(e, mesh)
    grid = mesh_lib.mesh_grid_shape(mesh)
    rule_hits: Dict[str, int] = {}
    # phase(): timed ALWAYS (meta needs the durations on the obs-off
    # path too) and recorded ALWAYS in the cold ring (a compile is no
    # query's warm path), emitted to a tracer only when one is active
    with trace_lib.phase("plan.optimize", roots=len(exprs)) as sp_opt:
        opts = tuple(planner.annotate_strategies(
            rules.optimize(e, cfg, grid=grid, mesh=mesh,
                           counts=rule_hits),
            mesh, cfg)
            for e in exprs)
        if cfg.fusion_enable:
            # whole-plan fusion boundaries (ir/fusion.py): stamped
            # after strategies/tiers so anchors carry their recipes,
            # before the verifier so MV111 sees every region. Off (the
            # default) this branch constructs nothing — bit-identity.
            from matrel_tpu.ir import fusion as fusion_lib
            opts = tuple(fusion_lib.annotate_fusion(o, mesh, cfg)
                         for o in opts)
        sp_opt.set(**rule_hits)     # which rewrites made this plan
    hbm = _hbm_meta(opts, mesh, cfg)
    with trace_lib.phase("plan.verify"):
        verify_diags = _verify_plans(opts, mesh, cfg)
    leaf_order = []
    seen = set()
    for o in opts:
        for l in expr_leaves(o):
            if l.uid not in seen:
                seen.add(l.uid)
                leaf_order.append(l)
    low = Lowerer(mesh, cfg)
    if cfg.autotune:
        low.spmv_choice = _autotune_spmv_choices(opts, mesh, cfg)
    fn = low.lower_multi(opts, leaf_order)
    with trace_lib.phase("plan.trace") as sp_tr:
        fn, extra = _hoist_large_consts(fn, _example_avals(leaf_order))
    fn.__name__ = "matrel_plan_multi"
    meta = {"optimize_ms": round(sp_opt.dur_ms, 3),
            "trace_ms": round(sp_tr.dur_ms, 3),
            "rule_hits": rule_hits,
            "executors": low.executors or ["xla"], **hbm}
    _coo_meta(meta, low)
    if verify_diags is not None:
        meta["diagnostics"] = verify_diags
    prec_meta = _precision_meta(opts, cfg)
    if prec_meta is not None:
        meta["precision"] = prec_meta
    fus_meta = _fusion_meta(opts, cfg)
    if fus_meta is not None:
        meta["fusion"] = fus_meta
    return MultiPlan(jitted=jax.jit(fn), leaf_order=leaf_order,
                     optimized=opts, mesh=mesh, config=cfg,
                     extra_args=extra, meta=meta)


# COO_NARROW_MAX (ir/expr.py): the widest dense side the COO SpMV tables
# multiply. A wider side, or a matrix whose plan was refused, densifies
# the leaf; the planner asks _coo_dispatch_plan itself (not the
# constant), so it prices, and on one device refuses, exactly the
# fall-through that would run.


#: Matmul operand kinds the SpGEMM dispatch accepts.
_SPGEMM_LEAF_KINDS = ("sparse_leaf", "coo_leaf")


def _spgemm_block_size(node: MatExpr, config=None):
    """The tile edge an S×S matmul's SpGEMM would run at, or None when
    the node is not an S×S candidate at all: both operands must be
    sparse leaves, and two block-sparse operands must already agree on
    block size (their tile grids intersect 1:1). COO operands adopt the
    block-sparse partner's grid, or config.block_size for COO×COO."""
    l, r = node.children
    if (l.kind not in _SPGEMM_LEAF_KINDS
            or r.kind not in _SPGEMM_LEAF_KINDS):
        return None
    sizes = [c.attrs["matrix"].block_size for c in node.children
             if c.kind == "sparse_leaf"]
    if len(sizes) == 2 and sizes[0] != sizes[1]:
        return None
    if sizes:
        return sizes[0]
    cfg = config or default_config()
    return cfg.block_size


def _block_density_of(child: MatExpr, bs: int) -> float:
    """Block-granular density of an S×S operand: block-sparse leaves
    carry it; element-sparse leaves COUNT their touched tiles exactly
    from the host edge lists (memoised per block size). The
    probabilistic lift (ir/stats.block_density) is wrong in both
    directions here: under its uniform-independence assumption any
    element density above ~1/bs² saturates the estimate to ~1.0, so
    CLUSTERED edge lists — the very inputs tile-intersection SpGEMM
    exists for — could never dispatch (review r6), while the exact
    count costs one O(nnz) numpy pass, work from_coo_arrays would
    redo at lowering anyway."""
    import math as _math
    m = child.attrs["matrix"]
    if child.kind == "sparse_leaf":
        return m.density
    memo = getattr(m, "_block_density_memo", None)
    if memo is not None and memo[0] == bs:
        return memo[1]
    import numpy as _np
    gr = _math.ceil(m.shape[0] / bs)
    gc = _math.ceil(m.shape[1] / bs)
    keys = (_np.asarray(m.rows, _np.int64) // bs) * gc \
        + _np.asarray(m.cols, _np.int64) // bs
    d = len(_np.unique(keys)) / max(gr * gc, 1)
    m._block_density_memo = (bs, d)
    return d


def spgemm_out_block_density(node: MatExpr, config=None):
    """Estimated output BLOCK density of an S×S matmul — the quantity
    the dispatch threshold compares. None when not an S×S candidate."""
    from matrel_tpu.ir import stats
    import math as _math
    bs = _spgemm_block_size(node, config)
    if bs is None:
        return None
    l, r = node.children
    kb = max(1, _math.ceil(l.shape[1] / bs))
    return stats.matmul_density(_block_density_of(l, bs),
                                _block_density_of(r, bs), kb)


def _spgemm_dispatch(node: MatExpr, config=None) -> bool:
    """Will this matmul lower through the SpGEMM path? SINGLE source of
    truth, shared by Lowerer._matmul, the planner's strategy pricing
    (choose_strategy_ex), layout inference and matmul_decisions —
    mirroring the _coo_dispatch_plan contract."""
    cfg = config or default_config()
    if cfg.spgemm_density_threshold <= 0.0:
        return False
    est = spgemm_out_block_density(node, cfg)
    return est is not None and est < cfg.spgemm_density_threshold


def spgemm_estimates(node: MatExpr, config=None) -> dict:
    """Observability record for a SpGEMM dispatch (planner.
    matmul_decisions → obs/ query events): estimated output block
    density plus the FLOPs/HBM bytes saved vs the densify fallback."""
    from matrel_tpu.ir import stats
    import math as _math
    cfg = config or default_config()
    bs = _spgemm_block_size(node, cfg)
    l, r = node.children
    k, m = l.shape[1], r.shape[1]
    kb = max(1, _math.ceil(k / bs))

    def nnzb_of(child):
        mtx = child.attrs["matrix"]
        if child.kind == "sparse_leaf":
            return float(mtx.nnzb)
        gr = _math.ceil(child.shape[0] / bs)
        gc = _math.ceil(child.shape[1] / bs)
        return _block_density_of(child, bs) * gr * gc

    rec = stats.spgemm_saved_estimate(nnzb_of(l), nnzb_of(r), kb, k, m,
                                      bs)
    rec["est_out_block_density"] = spgemm_out_block_density(node, cfg)
    rec["block_size"] = bs
    return rec


def spgemm_kernel_choice(node: MatExpr, config=None, mesh=None):
    """(kernel_id, structure_class, source) for a dispatching S×S
    matmul — the SINGLE source of truth shared by the planner's stamp
    (annotate_strategies), the MV110 verifier and the lowering's
    unstamped fallback, mirroring the _spgemm_dispatch contract.
    Structure classification is memoised per operand
    (kernel_registry.structure_of_child, the pair_structure idiom) and
    surfaces in matmul_decisions / explain(analyze=True)."""
    from matrel_tpu.ir import stats
    from matrel_tpu.ops import kernel_registry as kr
    cfg = config or default_config()
    bs = _spgemm_block_size(node, cfg)
    l, r = node.children
    structure = stats.pair_structure_class(
        kr.structure_of_child(l, bs), kr.structure_of_child(r, bs))
    est = spgemm_estimates(node, cfg)
    npairs = max(int(round(est.get("est_pairs") or 0.0)), 1)
    side = max(l.shape[0], l.shape[1], r.shape[1])
    kid, source = kr.select_kernel(structure, bs, npairs, cfg,
                                   side=side, mesh=mesh)
    return kid, structure, source


def _coo_dispatch_plan(node: MatExpr):
    """The plan a coo_leaf matmul node will dispatch through
    _coo_spmv_stack — the orientation's EdgeSpMVPlan, or for a k-wide
    product over more sources than one gather table holds its
    PanelledPlan (core/coo.py) — or None (the densify path). SINGLE
    source of truth for the narrow-operand dispatch, shared by
    Lowerer._matmul, the planner and the autotune walk so they can never
    drift."""
    l, r = node.children
    flipped = l.kind != "coo_leaf"
    if flipped and r.kind != "coo_leaf":
        return None
    m = (r if flipped else l).attrs["matrix"]
    k = l.shape[0] if flipped else r.shape[1]
    if not 0 < k <= COO_NARROW_MAX:
        return None
    if k > 1:
        return m._get_wide_plan(transposed=flipped)
    return m._get_plan_t() if flipped else m._get_plan()


def _sampled_dispatch_plan(node: MatExpr, mesh: Mesh,
                           config: Optional[MatrelConfig] = None):
    """The plan a matmul with a ``sampled`` operand is answered FUSED
    through (Lowerer._sampled_product): the k-wide plan of the sampled
    leaf's matrix in the product's orientation — or None, and the
    sampled node then lowers as the dense array it stands for. Fused
    where the other side is dense with at most COO_NARROW_MAX columns,
    on one device, where the compact-table Pallas executor runs
    (config.pallas_enabled) and the planner did not refuse the matrix.
    SINGLE source of truth, shared by the lowering and the planner's
    memory reckoning (planner.coo_product)."""
    from matrel_tpu.config import pallas_enabled
    l, r = node.children
    flipped = l.kind != "sampled"
    smp, dense = (r, l) if flipped else (l, r)
    if smp.kind != "sampled" or dense.kind in (
            "sampled", "sparse_leaf", "coo_leaf"):
        return None
    k = dense.shape[0] if flipped else dense.shape[1]
    if (not 0 < k <= COO_NARROW_MAX or mesh.size != 1
            or not pallas_enabled(config)):
        return None
    return smp.children[0].attrs["matrix"]._get_wide_plan(
        transposed=flipped)


def _semiring_dispatch(node: MatExpr, mesh: Mesh,
                       config: Optional[MatrelConfig] = None):
    """(matrix, plan) a ``semiring`` node is answered from: the leaf's
    matrix with one entry a cell (COOMatrix.entry_view: an extremum,
    unlike a sum, has to know a repeated cell) and its forward SpMV plan
    where the chunk grid's reduction kernels read it — one device, the
    compact-table Pallas executor on (config.pallas_enabled), a plan in
    chunks, with hub chunks where the build's rule took any (PR 51: the
    plan ``pagerank_edges`` would build of the same entries), in every
    row of 128 slots those of one destination row side by side
    (spmv.rows_in_order) — else None, and XLA's segment reduction over
    the sorted entries answers. SINGLE source of truth, shared by the
    lowering and the planner's memory reckoning
    (planner.semiring_product)."""
    from matrel_tpu.config import pallas_enabled
    from matrel_tpu.ops import spmv as spmv_lib
    m = node.children[0].attrs["matrix"].entry_view()
    if mesh.size == 1 and pallas_enabled(config):
        plan = m._get_plan()
        if plan is not None and spmv_lib.rows_in_order(plan):
            return m, plan
    return m, None


def _same_table(z: MatExpr, z_t: bool, f: MatExpr, f_t: bool) -> bool:
    """Whether ``z`` (read transposed where ``z_t``) and ``f`` (where
    ``f_t``) are the same array: the same node under transposes of the
    same parity."""
    while z.kind == "transpose":
        z, z_t = z.children[0], not z_t
    while f.kind == "transpose":
        f, f_t = f.children[0], not f_t
    return z.uid == f.uid and z_t == f_t


def _autotune_spmv_choices(opts, mesh, cfg) -> dict:
    """Measured SpMV executor variants for every COO matmul this plan
    will dispatch through _coo_spmv_stack (config.autotune on): maps
    id(plan) -> (plan, "compact"/"expanded"). Runs OUTSIDE tracing, at
    compile time — measurement launches its own jitted probes. Dispatch
    conditions come from _coo_dispatch_plan (shared with _matmul);
    anything else keeps the hand defaults."""
    from matrel_tpu.parallel import autotune

    choices: dict = {}
    seen: set = set()

    def visit(n: MatExpr):
        if n.uid in seen:        # expressions are DAGs — walk each
            return               # shared node once
        seen.add(n.uid)
        if n.kind == "matmul" and any(c.kind == "coo_leaf"
                                      for c in n.children):
            plan = _coo_dispatch_plan(n)
            if (plan is not None and not hasattr(plan, "parts")
                    and plan.chunk_block is None
                    and id(plan) not in choices):
                best = autotune.lookup_or_measure_spmv(plan, mesh, cfg)
                if best is not None:
                    choices[id(plan)] = (plan, best)
        for c in n.children:
            visit(c)

    for o in opts:
        visit(o)
    return choices


def _check_one_mesh(expr: MatExpr, mesh: Mesh) -> None:
    """All leaves (dense and sparse) must live on the plan's mesh — mixed
    meshes would silently produce cross-device copies or wrong shardings."""
    def walk(n: MatExpr):
        if n.kind in ("leaf", "sparse_leaf"):
            m = n.attrs["matrix"].mesh
            if m is not mesh and tuple(m.devices.ravel()) != tuple(
                    mesh.devices.ravel()):
                raise ValueError(
                    "expression mixes matrices from different meshes: "
                    f"{dict(m.shape)} vs plan mesh {dict(mesh.shape)}")
        for c in n.children:
            walk(c)
    walk(expr)


def compile_expr(expr: MatExpr, mesh: Optional[Mesh] = None,
                 config: Optional[MatrelConfig] = None) -> CompiledPlan:
    """optimize → plan → lower → jit. The full Catalyst pipeline analogue."""
    cfg = config or default_config()
    lvs = expr_leaves(expr)
    if mesh is None:
        mesh = lvs[0].attrs["matrix"].mesh if lvs else mesh_lib.make_mesh(
            cfg.mesh_shape, cfg.mesh_axis_names)
    _check_one_mesh(expr, mesh)
    rule_hits: Dict[str, int] = {}
    # phase spans: same mechanism (and meta fields) as compile_exprs
    with trace_lib.phase("plan.optimize") as sp_opt:
        opt = rules.optimize(expr, cfg,
                             grid=mesh_lib.mesh_grid_shape(mesh),
                             mesh=mesh, counts=rule_hits)
        opt = planner.annotate_strategies(opt, mesh, cfg)
        if cfg.fusion_enable:
            # fusion boundaries after strategies, before the verifier
            # (the compile_exprs ordering — one contract)
            from matrel_tpu.ir import fusion as fusion_lib
            opt = fusion_lib.annotate_fusion(opt, mesh, cfg)
        sp_opt.set(**rule_hits)     # which rewrites made this plan
    hbm = _hbm_meta((opt,), mesh, cfg)
    with trace_lib.phase("plan.verify"):
        verify_diags = _verify_plans((opt,), mesh, cfg)
    leaf_order = expr_leaves(opt)
    low = Lowerer(mesh, cfg)
    if cfg.autotune:
        low.spmv_choice = _autotune_spmv_choices((opt,), mesh, cfg)
    fn = low.lower(opt, leaf_order)
    with trace_lib.phase("plan.trace") as sp_tr:
        fn, extra = _hoist_large_consts(fn, _example_avals(leaf_order))
    # a stable name for the profiler's ``XLA Modules`` line
    fn.__name__ = f"matrel_plan_{opt.kind}"
    jitted = jax.jit(fn)
    meta = {"optimize_ms": round(sp_opt.dur_ms, 3),
            "trace_ms": round(sp_tr.dur_ms, 3),
            "rule_hits": rule_hits,
            "executors": low.executors or ["xla"], **hbm}
    _coo_meta(meta, low)
    if verify_diags is not None:
        meta["diagnostics"] = verify_diags
    prec_meta = _precision_meta((opt,), cfg)
    if prec_meta is not None:
        meta["precision"] = prec_meta
    fus_meta = _fusion_meta((opt,), cfg)
    if fus_meta is not None:
        meta["fusion"] = fus_meta
    return CompiledPlan(jitted=jitted, leaf_order=leaf_order, optimized=opt,
                        mesh=mesh, config=cfg, extra_args=extra, meta=meta)


def plan_matmul_decisions(plan) -> List[dict]:
    """Per-matmul planner-decision records for a compiled plan (obs/
    event log, ``explain(analyze=True)``), computed on FIRST access and
    cached in ``plan.meta`` — deriving them re-walks the tree through
    ``infer_layout``/``comm_cost``, work the obs-off compile path must
    not pay for."""
    meta = plan.meta
    if meta is None:
        return []
    if "matmuls" not in meta:
        roots = (plan.optimized if isinstance(plan.optimized, tuple)
                 else (plan.optimized,))
        meta["matmuls"] = [
            d for o in roots
            for d in planner.matmul_decisions(o, plan.mesh, plan.config)]
        ivm = meta.get("ivm")
        if isinstance(ivm, dict):
            # delta-patch plans (serve/ivm.py; docs/IVM.md): the
            # optimizer may rebuild the stamped root, so the pricing
            # provenance rides plan.meta and is threaded onto the
            # decision records here (planner.matmul_decisions also
            # reads a surviving root stamp — one meaning, two feeds)
            for d in meta["matmuls"]:
                d.setdefault("delta_rule", ivm.get("rule"))
                d.setdefault("delta_est_saved_flops",
                             ivm.get("est_saved_flops"))
    return meta["matmuls"]


def multiplan_root_decisions(plan: MultiPlan) -> List[List[dict]]:
    """Per-ROOT planner-decision records for a MultiPlan, aligned with
    ``plan.optimized`` — the per-root obs feed (session.run_many emits
    one query event per root, each carrying its OWN matmuls instead of
    the batch aggregate). Lazily derived and cached in ``plan.meta``
    like :func:`plan_matmul_decisions`, so the obs-off batch path pays
    nothing."""
    meta = plan.meta
    if meta is None:
        return [[] for _ in plan.optimized]
    if "matmuls_per_root" not in meta:
        meta["matmuls_per_root"] = [
            planner.matmul_decisions(o, plan.mesh, plan.config)
            for o in plan.optimized]
    return meta["matmuls_per_root"]


#: Decision-record columns the provenance ledger keeps (obs tier 4):
#: the chosen strategy, WHY (autotune/model/override), and the
#: precision tier — the coefficient provenance a lineage audit needs,
#: without the per-matmul byte/FLOP estimates the query event carries.
_PROVENANCE_KEEP = ("strategy", "source", "precision_tier",
                    "delta_rule")


def plan_provenance(plan, decisions: Optional[List[dict]] = None
                    ) -> List[dict]:
    """A compiled plan's strategy/tier/coefficient provenance,
    projected for the answer ledger (obs/provenance.py). ``decisions``
    lets MultiPlan callers pass ONE root's records
    (``multiplan_root_decisions``) instead of the batch aggregate.
    Same lazy-derivation contract as :func:`plan_matmul_decisions`:
    the ledger-off path never calls this."""
    if decisions is None:
        decisions = plan_matmul_decisions(plan)
    return [{k: d[k] for k in _PROVENANCE_KEEP
             if d.get(k) is not None} for d in decisions]


def execute(expr: MatExpr, mesh: Optional[Mesh] = None,
            config: Optional[MatrelConfig] = None) -> BlockMatrix:
    return compile_expr(expr, mesh, config).run()


# ---------------------------------------------------------------------------
# Unit-program emission — the region seam (ir/fusion.py; docs/FUSION.md)
#
# The default executor compiles the WHOLE plan into one program; these
# builders are the measurable decomposition of that spectrum's other
# end: ``compile_staged_units`` emits one jitted program PER PHYSICAL
# OP (the per-op dispatch floor — a dispatch and an HBM round-trip per
# plan edge), ``compile_region_units`` one program PER FUSED REGION
# (XLA sees the whole segment). tests/test_fusion.py runs both; the
# autotune ``fuse|`` loop measures a single region's pair through
# the same machinery. This module is the ONE sanctioned jit seam —
# matlint ML010 keeps jitted-program emission here (and utils/).
# ---------------------------------------------------------------------------

#: Leaf kinds whose payloads stay INSIDE a unit as trace constants
#: (their lowerings read static host metadata off the node attrs).
_UNIT_CONST_LEAVES = ("sparse_leaf", "coo_leaf")


def _unit_fn(low: Lowerer, root: MatExpr,
             input_uids: Tuple[int, ...]):
    """One jitted program computing ``root`` from its unit inputs
    (everything not in ``input_uids`` — members of the unit's region,
    sparse-payload leaves — lowers inside). Members lower through the
    Lowerer's per-node paths, byte-for-byte the staged lowerings, so
    fused and staged units agree exactly."""

    def fn(*arrs):
        env = dict(zip(input_uids, arrs))

        def lev(n: MatExpr):
            v = env.get(n.uid)
            if v is not None:
                return v
            v = low._eval(n, lev, (), {})  # unit-program member — jitted as one region by the seam builders below
            env[n.uid] = v
            return v

        return lev(root)

    return jax.jit(fn)


@dataclasses.dataclass
class UnitPrograms:
    """An expression compiled as a SEQUENCE of jitted unit programs —
    ``dispatches`` programs per run (the quantity fusion shrinks).
    ``run()`` executes the units in topo order over raw padded arrays
    and returns the root unit's output."""

    #: (node, jitted fn, input uids, member count) in execution order.
    units: List
    optimized: MatExpr
    leaf_order: List[MatExpr]
    mesh: Mesh
    config: MatrelConfig

    @property
    def dispatches(self) -> int:
        return len(self.units)

    def run(self, bindings: Optional[Dict[int, Array]] = None):
        env = {l.uid: l.attrs["matrix"].data for l in self.leaf_order}
        if bindings:
            env.update(bindings)
        for node, fn, input_uids, _n in self.units:
            env[node.uid] = fn(*(env[u] for u in input_uids))
        return env[self.optimized.uid]


def _build_units(opt: MatExpr, mesh: Mesh, cfg: MatrelConfig,
                 per_region: bool) -> UnitPrograms:
    from matrel_tpu.ir import fusion as fusion_lib
    low = Lowerer(mesh, cfg)
    units: List = []
    leaf_order: List[MatExpr] = []
    seen: set = set()
    member_of: Dict[int, int] = {}     # member uid -> region root uid
    if per_region:
        for stamp in fusion_lib.collect_stamps(opt):
            for u in stamp.attrs.get("fused_members") or ():
                member_of[u] = stamp.uid

    def walk(n: MatExpr):
        if n.uid in seen:
            return
        seen.add(n.uid)
        for c in n.children:
            walk(c)
        if n.kind == "leaf":
            leaf_order.append(n)
            return
        if n.kind in _UNIT_CONST_LEAVES:
            return                      # consts inside the consumer unit
        if n.uid in member_of:
            return                      # lowers inside its region unit
        if per_region and "fused_region" in n.attrs:
            members = fusion_lib.region_nodes(n)
            inputs = []
            in_seen = set()
            for m in members.values():
                for c in m.children:
                    if (c.uid not in members
                            and c.kind not in _UNIT_CONST_LEAVES
                            and c.uid not in in_seen):
                        in_seen.add(c.uid)
                        inputs.append(c.uid)
            units.append((n, _unit_fn(low, n, tuple(inputs)),
                          tuple(inputs), len(members)))
            return
        inputs = tuple(c.uid for c in n.children
                       if c.kind not in _UNIT_CONST_LEAVES)
        units.append((n, _unit_fn(low, n, inputs), inputs, 1))

    walk(opt)
    if not units:                       # a bare leaf plan: identity unit
        units.append((opt, jax.jit(lambda x: x), (opt.uid,), 1))
    return UnitPrograms(units=units, optimized=opt,
                        leaf_order=leaf_order, mesh=mesh, config=cfg)


def compile_staged_units(expr: MatExpr, mesh: Optional[Mesh] = None,
                         config: Optional[MatrelConfig] = None
                         ) -> UnitPrograms:
    """One jitted program PER PHYSICAL OP — the staged dispatch floor
    the fused form is measured against (fusion stamps, if any, are
    ignored: every plan edge pays its dispatch and HBM round-trip)."""
    cfg = config or default_config()
    lvs = expr_leaves(expr)
    if mesh is None:
        mesh = lvs[0].attrs["matrix"].mesh if lvs else mesh_lib.make_mesh(
            cfg.mesh_shape, cfg.mesh_axis_names)
    opt = planner.annotate_strategies(
        rules.optimize(expr, cfg, grid=mesh_lib.mesh_grid_shape(mesh),
                       mesh=mesh), mesh, cfg)
    return _build_units(opt, mesh, cfg, per_region=False)


def compile_region_units(expr: MatExpr, mesh: Optional[Mesh] = None,
                         config: Optional[MatrelConfig] = None
                         ) -> UnitPrograms:
    """One jitted program PER FUSED REGION (non-region nodes keep one
    each) — requires ``config.fusion_enable``; the region grammar is
    ``ir/fusion.annotate_fusion``'s, so the emitted boundaries are
    exactly the ones MV111 verifies and the bench sweeps."""
    cfg = config or default_config()
    lvs = expr_leaves(expr)
    if mesh is None:
        mesh = lvs[0].attrs["matrix"].mesh if lvs else mesh_lib.make_mesh(
            cfg.mesh_shape, cfg.mesh_axis_names)
    opt = planner.annotate_strategies(
        rules.optimize(expr, cfg, grid=mesh_lib.mesh_grid_shape(mesh),
                       mesh=mesh), mesh, cfg)
    if cfg.fusion_enable:
        from matrel_tpu.ir import fusion as fusion_lib
        opt = fusion_lib.annotate_fusion(opt, mesh, cfg)
    return _build_units(opt, mesh, cfg, per_region=True)


def region_probe_programs(root_node: MatExpr, member_uids,
                          mesh: Mesh, cfg: MatrelConfig):
    """(fused_fn, staged_units, input_uids, probe_arrays, root_uid)
    for ONE region — the autotune ``fuse|`` measurement harness
    (lookup_or_measure_fusion). Region inputs are replaced by
    synthetic padded f32 probes; regions whose members read
    sparse-leaf payloads return None (the probe cannot substitute
    static tile metadata — the model decides there)."""
    import numpy as _np
    members = {root_node.uid: root_node}
    want = set(member_uids)
    stack = [root_node]
    while stack:
        n = stack.pop()
        for c in n.children:
            if c.uid in want and c.uid not in members:
                members[c.uid] = c
                stack.append(c)
    inputs: List[MatExpr] = []
    in_seen: set = set()
    for m in members.values():
        for c in m.children:
            if c.uid in members or c.uid in in_seen:
                continue
            if c.kind in _UNIT_CONST_LEAVES:
                return None
            in_seen.add(c.uid)
            inputs.append(c)
    low = Lowerer(mesh, cfg)
    input_uids = tuple(c.uid for c in inputs)
    fused = _unit_fn(low, root_node, input_uids)
    staged: List = []
    order: List[MatExpr] = []
    seen: set = set()

    def topo(n: MatExpr):
        if n.uid in seen or n.uid not in members:
            return
        seen.add(n.uid)
        for c in n.children:
            topo(c)
        order.append(n)

    topo(root_node)
    for n in order:
        ins = tuple(c.uid for c in n.children)
        staged.append((n, _unit_fn(low, n, ins), ins))
    rng = _np.random.default_rng(0)
    arrays = {c.uid: jnp.asarray(rng.standard_normal(
        padding.padded_shape(c.shape, mesh)).astype(_np.float32))
        for c in inputs}
    return fused, staged, input_uids, arrays, root_node.uid
