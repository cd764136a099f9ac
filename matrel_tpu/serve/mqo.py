"""Multi-query optimization: plan templates and admission-time
cross-query CSE (docs/SERVING.md).

The serve plane dedups whole-query ROOTS (``run_many``'s structural
uniq) and catches interior reuse only AFTER a prior query materialized
it in the result cache — a coalesced batch of near-identical dashboard
queries still computed its shared interior subplans k times on first
contact and paid compile per structural variant. This module is the
MatFast persist/amortization thesis (PAPER.md [P2]) applied ACROSS the
concurrent batch instead of across time, plus the
compile-for-the-observed-workload argument (arXiv:2312.05639) lifted
to the query stream. Two mechanisms, both driven by the session's ONE
structural-key walk (``session._plan_key_spans`` — span-slice joins,
never subtree re-walks):

**Cross-query CSE** (:func:`choose_hoists` / :func:`substitute`;
``config.cse_enable``, off by default): the interior subtrees shared
by >= ``config.cse_min_uses`` occurrences across a batch are hoisted
into a compute-once MultiPlan of their own;
every consumer query re-enters planning with the result substituted as
an already-laid-out leaf carrying a ``cse`` stamp — the result-cache
interior-hit shape, so ``infer_layout``/``comm_cost`` credit the reuse
and ``matmul_decisions`` marks the hoist-fed operands
(``cse_operands``). Hoists happen only at fused-region BOUNDARIES
(kinds outside ``ir/fusion.FUSABLE_KINDS``, i.e. anchors whose output
already crosses a region edge), so per-consumer epilogue chains keep
fusing into their own regions instead of being split by the share.

**Plan-template reuse** (:class:`MqoState` + :func:`template_key`;
every session — the plan cache's second key, asked by
``session._plan_lookup`` on a concrete miss only): queries
structurally identical modulo dense-leaf bindings key one TEMPLATE on
the leaf-abstracted structural key — dense leaves emit a
session-independent token carrying exactly the host metadata planning
consults (shape, PartitionSpec, dtype, density, integrality bounds),
so rebinding a new matrix with the same token into the compiled
program is planning-equivalent by construction; sparse/COO leaves keep
their identity tokens (their payloads are baked into the compiled
program as constants — not rebindable). An iteration's statements
over new factors (NMF, CG, WCC) and steady-state dashboard traffic
rebind leaves into the cached plan via ``plan.run(bindings=...)`` —
the IVM ``ivm_role`` rebinding seam (serve/ivm.py) generalized — and
pay ZERO optimize/trace. The session composes the
``degr:``/``axisw:``/``prec:`` key prefixes onto every template key,
so degrade/topology/SLA isolation is inherited, not re-implemented.

Verification: MV116 (analysis/cse_pass.py) statically checks every
``cse`` stamp against the leaf it rides and dynamically re-executes
recent hoist-substituted batches UNSHARED (the MV113 patched-entry
idiom) — :attr:`MqoState.recent` is the bounded ring it replays.

``cse_enable = False`` (the default) never hoists: ``_CONSTRUCTED``
counts the hoists chosen, and a query the plan cache answers reaches
nothing in this module.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

#: Test hook (tests/test_cse.py): with ``cse_enable`` off no hoist is
#: ever chosen — the count stays where it was over a default session's
#: batches (the ir/fusion._CONSTRUCTED idiom).
_CONSTRUCTED = {"count": 0}

#: Ring depth of :attr:`MqoState.recent` — what MV116's dynamic half
#: can re-prove without the state pinning unbounded device results.
RECENT_MAX = 8


def _fusable_kinds() -> tuple:
    from matrel_tpu.ir import fusion as fusion_lib
    return fusion_lib.FUSABLE_KINDS


@dataclasses.dataclass
class HoistPlan:
    """One shared interior chosen for compute-once execution: the
    canonical subtree (first occurrence — all occurrences are
    structurally identical by key), its standalone structural key
    (byte-identical to ``_plan_key`` of the subtree — the spans
    contract), and the uid of EVERY occurrence across the batch so
    substitution can replace each consumer site."""

    key: str
    expr: object                  # MatExpr — the canonical occurrence
    uses: int
    uids: Tuple[int, ...]

    def __post_init__(self):
        _CONSTRUCTED["count"] += 1


@dataclasses.dataclass
class TemplateEntry:
    """One compiled plan held rebindable: ``slots`` is, in PLAN-ROOT
    order, each root's (abstract key, dense-leaf uids in pre-order) —
    a probe pairs its own roots to slots by abstract key and binds new
    matrices onto the recorded uids. ``pins`` keeps every id()-keyed
    object of the abstract key alive (sparse payload matrices,
    fn-token globals) so the key can never falsely hit a recycled
    address — the plan-cache ``_cache_pin`` discipline."""

    plan: object
    slots: Tuple[Tuple[str, Tuple[int, ...]], ...]
    pins: Tuple


class MqoState:
    """Per-session multi-query-optimization state: the template cache
    (abstract key -> :class:`TemplateEntry`, LRU-bounded by
    ``config.cse_template_max``), the lifetime counters the serve
    events report as deltas, and the bounded ring of recent
    hoist-substituted executions MV116's dynamic half replays."""

    def __init__(self, config):
        self.config = config
        self.templates: "OrderedDict[str, TemplateEntry]" = OrderedDict()
        self.cse_hoisted = 0          # lifetime hoisted interiors
        self.cse_batches = 0          # batches that hoisted anything
        self.template_hits = 0        # lifetime template-served queries
        self.template_inserts = 0
        #: (original root expr, substituted expr) of recent CSE-fed
        #: executions — MV116's dynamic-verify feed: executing both
        #: fresh and comparing proves substituted ≡ unshared.
        self.recent: deque = deque(maxlen=RECENT_MAX)
        #: abstract keys restored from a ``save_state()`` snapshot
        #: (serve/spill.py) — KEYS ONLY: a compiled plan holds device
        #: buffers and traced closures no snapshot can carry, so
        #: programs recompile lazily on first probe and the seeded set
        #: just tracks which pre-restart templates have come back
        #: (``templates_rewarmed``). Bookkeeping, never a plan source.
        self.seeded: set = set()
        self.templates_rewarmed = 0

    def info(self) -> dict:
        """``plan_cache_info``-style surface."""
        return {"templates": len(self.templates),
                "template_hits": self.template_hits,
                "template_inserts": self.template_inserts,
                "cse_hoisted": self.cse_hoisted,
                "cse_batches": self.cse_batches,
                "seeded_templates": len(self.seeded),
                "templates_rewarmed": self.templates_rewarmed}

    def remember(self, orig, substituted) -> None:
        self.recent.append((orig, substituted))

    def put_template(self, key: str, entry: TemplateEntry) -> None:
        # canonical structural key only (matlint ML016): the template
        # cache must never key off id()/uid/spec-repr shortcuts — a
        # recycled address or a re-created same-layout leaf would
        # alias two distinct plans
        self.templates[key] = entry
        self.templates.move_to_end(key)
        if key in self.seeded:
            self.seeded.discard(key)
            self.templates_rewarmed += 1
        while len(self.templates) > self.config.cse_template_max:
            self.templates.popitem(last=False)

    def get_template(self, key: str) -> Optional[TemplateEntry]:
        ent = self.templates.get(key)
        if ent is not None:
            self.templates.move_to_end(key)
        return ent

    def template_keys(self) -> list:
        """LRU-ordered abstract keys (coldest first) for
        ``save_state()`` — plus any still-unrewarmed seeded keys, so
        a restart-of-a-restart does not forget the original hot set."""
        out = sorted(self.seeded)
        out.extend(k for k in self.templates if k not in self.seeded)
        return out

    def seed_templates(self, keys) -> int:
        """Install a snapshot's template keys (``restore()``'s seam)
        — see ``seeded``. Bounded by ``cse_template_max``; non-string
        rows are skipped (a snapshot is never a correctness
        surface)."""
        installed = 0
        for k in keys:
            if len(self.seeded) >= self.config.cse_template_max:
                break
            if isinstance(k, str) and k not in self.templates:
                self.seeded.add(k)
                installed += 1
        return installed


# -- leaf-abstracted structural keys (plan templates) -------------------


def template_key(e) -> Tuple[str, list, list]:
    """(abstract key, pins, dense leaves in pre-order) for one root.

    Dense leaves emit a session-independent token carrying EXACTLY the
    host metadata the planner consults about a leaf — shape and
    PartitionSpec (``_layout_of``/``infer_layout``), dtype (HBM gates,
    autotune classes), density (``comm_cost``), integrality flag and
    bound (``infer_integral``/``integral_abs_bound`` — the precision
    tier chooser) — PLUS the leaf's identity CLASS (first-occurrence
    numbering of the matrix object within this root): the optimizer
    consults which leaves hold the SAME matrix (``t(X) @ X`` dedupes
    its two leaves into one Gram operand; ``t(X) @ Y`` cannot), so the
    equality pattern is part of what determines the compiled program
    and must be part of the key — ``#0/#0`` and ``#0/#1`` never share
    a template. With metadata and pattern both encoded, any tree with
    the same token sequence optimizes to the identical program modulo
    leaf bindings (the optimizer never reads leaf VALUES), and
    rebinding is as safe as re-running the plan. Sparse/COO leaves
    keep their identity tokens (payloads are trace constants in the
    compiled program — not rebindable) and are pinned. Interior tokens
    come byte-identical from the session's one structural-walk
    implementation."""
    from matrel_tpu import session as session_mod

    pins: list = []
    leaves: list = []
    classes: dict = {}

    def tok(n):
        m = n.attrs.get("matrix")
        if n.kind == "leaf":
            leaves.append(n)
            cls = classes.setdefault(id(m), len(classes))
            return ("tleaf#{}:{}:{}:{}:{}:{}:{}".format(
                cls, m.shape, m.spec, np.dtype(m.dtype),
                getattr(m, "density", None),
                bool(getattr(m, "integral", False)),
                getattr(m, "int_abs_max", None)))
        # sparse payloads are compiled-in constants — identity-keyed
        # and pinned, exactly like the concrete key
        pins.append(m)
        return f"{n.kind}:{id(m)}:{m.shape}"

    parts, wpins, _spans = session_mod._plan_key_spans(e, leaf_token=tok)
    return "|".join(parts), pins + wpins, leaves


def rebindable(entry: TemplateEntry) -> bool:
    """A template is rebindable iff every DENSE leaf of its compiled
    program is a leaf its abstract key recorded — a program leaf the
    key never saw (an optimizer rewrite that re-created the node with
    a fresh uid) would silently keep its compiled-in matrix on a
    rebind: stale data, the one failure mode this guard exists for.
    Recorded leaves the program DROPPED (``t(X) @ X`` dedup, algebraic
    elimination) are fine: their bindings are simply ignored, and the
    identity classes in the abstract key guarantee the new batch's
    leaves dedupe the same way."""
    plan = entry.plan
    plan_uids = {l.uid for l in plan.leaf_order if l.kind == "leaf"}
    recorded = {u for _k, uids in entry.slots for u in uids}
    return plan_uids <= recorded


# -- cross-query CSE ----------------------------------------------------


def choose_hoists(entries, min_uses: int = 2) -> List[HoistPlan]:
    """Pick the shared interiors of one batch, top-down maximal.

    ``entries`` is ``[(root expr, parts, spans), ...]`` — each root's
    single ``_plan_key_spans`` walk. A node is a hoist CANDIDATE when
    it is a proper interior (not a leaf, not its query's root — whole-
    root sharing is the MultiPlan uniq's job), its kind lies outside
    ``FUSABLE_KINDS`` (the hoist boundary must coincide with a fused-
    region edge so epilogue fusion composes instead of splitting), and
    its subtree carries at least one matmul (a shared transpose-of-a-
    leaf is not worth a dispatch). Candidates group by their standalone
    span key; groups with >= ``min_uses`` occurrences hoist. Marking
    is top-down: inside a hoisted subtree nothing is re-considered —
    the interior computes once either way."""
    fusable = _fusable_kinds()
    counts: Dict[str, int] = {}
    canon: Dict[str, object] = {}

    def candidate(n, is_root: bool) -> bool:
        return (not is_root and bool(n.children)
                and n.kind not in fusable and _has_matmul(n))

    for e, parts, spans in entries:
        for n, is_root in _walk_interiors(e):
            if not candidate(n, is_root):
                continue
            s, t = spans[n.uid]
            k = "|".join(parts[s:t])
            counts[k] = counts.get(k, 0) + 1
            canon.setdefault(k, n)

    shared = {k for k, c in counts.items() if c >= min_uses}
    if not shared:
        return []
    hoists: Dict[str, List[int]] = {}

    def mark(n, parts, spans, is_root: bool):
        if n.uid in spans and not is_root and bool(n.children):
            s, t = spans[n.uid]
            k = "|".join(parts[s:t])
            if k in shared and n.kind not in fusable \
                    and _has_matmul(n):
                hoists.setdefault(k, []).append(n.uid)
                return                      # top-down maximal
        for c in n.children:
            mark(c, parts, spans, False)

    for e, parts, spans in entries:
        mark(e, parts, spans, True)
    return [HoistPlan(key=k, expr=canon[k], uses=len(uids),
                      uids=tuple(uids))
            for k, uids in sorted(hoists.items())]


def _walk_interiors(e):
    """Yield (node, is_root) for every interior node, pre-order."""
    out = []

    def walk(n, is_root):
        if not n.children:
            return
        out.append((n, is_root))
        for c in n.children:
            walk(c, False)

    walk(e, True)
    return out


def _has_matmul(n) -> bool:
    if n.kind == "matmul":
        return True
    return any(_has_matmul(c) for c in n.children)


def substitute(e, leaf_of: Dict[int, object]):
    """Rebuild ``e`` with every uid in ``leaf_of`` replaced by its
    compute-once leaf — the ``_rc_substitute`` shape, but keyed on the
    exact occurrence uids ``choose_hoists`` marked (no re-probing)."""
    hit = leaf_of.get(e.uid)
    if hit is not None:
        return hit
    if not e.children:
        return e
    new_children = tuple(substitute(c, leaf_of) for c in e.children)
    if all(nc is c for nc, c in zip(new_children, e.children)):
        return e
    return e.with_children(new_children)
