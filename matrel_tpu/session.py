"""MatrelSession — the entry point, analogue of the reference's
``MatfastSession`` (SURVEY.md §2 "Session & catalog", §3.1).

The reference subclasses SparkSession and installs its own analyzer /
optimizer / planner into the session state; executors register with the
cluster manager. Here the session owns the device mesh (the "cluster"), the
config (the SparkConf analogue), a tiny named-matrix catalog, and the
optimize→plan→jit pipeline, plus a compiled-plan cache keyed by expression
structure so repeated actions don't re-trace (the Spark query-cache
analogue).
"""

from __future__ import annotations

import functools
import hashlib
import itertools
import logging
import os
import threading
import time
import types
from collections import OrderedDict
from typing import List, Optional, Tuple

import numpy as np
from jax.sharding import Mesh

from matrel_tpu import executor as executor_lib
from matrel_tpu.config import (MatrelConfig, configure_compile_cache,
                               default_config, normalize_sla)
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir.expr import MatExpr, as_expr
from matrel_tpu.obs import export as export_lib
from matrel_tpu.obs import provenance as provenance_lib
from matrel_tpu.obs import slo as slo_lib
from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.resilience import breaker as breaker_lib
from matrel_tpu.resilience import brownout as brownout_lib
from matrel_tpu.resilience import degrade as degrade_lib
from matrel_tpu.resilience import errors as rerrors
from matrel_tpu.resilience import faults as faults_lib
from matrel_tpu.resilience import retry as retry_lib
from matrel_tpu.resilience.retry import RetryPolicy
from matrel_tpu.serve import mqo as mqo_lib
from matrel_tpu.serve import replan as replan_lib
from matrel_tpu.serve.result_cache import (CacheEntry, ResultCache,
                                           result_nbytes)
from matrel_tpu.utils import lockdep

log = logging.getLogger("matrel_tpu")

_active: Optional["MatrelSession"] = None


_deadline_left = retry_lib.deadline_left

_query_seq = itertools.count()


class MatrelSession:
    """Owns mesh + config + catalog; compiles and runs matrix queries."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 config: Optional[MatrelConfig] = None):
        self.config = config or default_config()
        configure_compile_cache()
        # concurrency sanitizer (utils/lockdep.py;
        # docs/CONCURRENCY.md): armed BEFORE any of this session's
        # locks construct, so they all come back instrumented. Off
        # (the default) this is one false branch — the seam keeps
        # returning raw threading primitives and zero lockdep objects
        # exist (poisoned-init test-enforced). The emit hook is wired
        # after the obs attributes exist (end of __init__).
        if self.config.lockdep_enable:
            lockdep.enable(
                raise_on_violation=self.config.lockdep_raise)
        self.mesh = mesh or mesh_lib.make_mesh(
            self.config.mesh_shape, self.config.mesh_axis_names)
        self.catalog: dict[str, BlockMatrix] = {}
        # LRU plan cache: every cached plan pins its hoisted sparse
        # payloads (extra_args) in device HBM and its leaf matrices via
        # leaf_order — unbounded growth OOMs long-lived sessions, so
        # least-recently-used plans evict at the config's plan-count /
        # hoisted-byte bounds
        self._plan_cache: "OrderedDict[str, executor_lib.CompiledPlan]" \
            = OrderedDict()
        # the newest dispatched plan and whether its lookup was a hit
        # (last_plan())
        self._last_plan = None
        self._last_hit = False
        # what the result cache answered of the newest statement
        # (last_plan(): ``views_hit``, ``table_pass``); None while the
        # cache is off
        self._last_rc = None
        self._plan_cache_bytes = 0
        self._plan_cache_evicted = 0
        self._event_log = None      # lazily built (obs_level != "off")
        # serving layer (matrel_tpu/serve/): cross-query result cache
        # (inert until config.result_cache_max_bytes > 0) and the async
        # submit pipeline (worker built on first submit). The lock
        # keeps the plan cache consistent when the pipeline's admission
        # worker and the caller's thread compile concurrently.
        self._result_cache = ResultCache()
        self._serve = None
        self._compile_lock = lockdep.make_rlock("session.compile")
        # durable spill hierarchy (serve/spill.py; docs/DURABILITY.md):
        # host/disk tiers under the result cache + the warm-restart
        # snapshot index — None for the default config (spill_enable
        # off: the structural zero-object contract, poisoned-init
        # test-enforced; spill._CONSTRUCTED stays 0)
        self._spill = None
        if self.config.spill_enable:
            from matrel_tpu.serve.spill import SpillManager
            self._spill = SpillManager(self)
            self._spill.emit = self._emit_spill_event
            self._result_cache.attach_spill(self._spill)
        # multi-query optimization (serve/mqo.py; docs/SERVING.md):
        # the plan templates (built on the first plan-cache miss) and
        # cross-query CSE's hoist state (cse_enable)
        self._mqo = None
        # obs tier 2 (obs/trace.py): the flight-recorder ring is
        # independent of obs_level (always-cheap post-mortem trail);
        # the tracer exists iff ANY span consumer does — with neither,
        # compute()'s fast path never creates a span object at all
        fr_cap = self.config.obs_flight_recorder
        self._flight = (trace_lib.FlightRecorder(fr_cap)
                        if fr_cap > 0 else None)
        self._tracer = (trace_lib.Tracer(self._obs_emit)
                        if (self._flight is not None
                            or self.config.obs_level != "off")
                        else None)
        # overload control plane (docs/OVERLOAD.md): adaptive brownout
        # controller + per-plan-class circuit breakers — both None for
        # the default config (the structural zero-object contract the
        # faults harness set: nothing constructed, nothing consulted)
        self._brownout = brownout_lib.from_config(self.config)
        self._breakers = breaker_lib.BreakerRegistry.from_config(
            self.config)
        # incremental view maintenance (serve/ivm.py; docs/IVM.md):
        # the delta plane is built lazily on the FIRST register_delta
        # — generation 0 means it was never used, every result-cache
        # key keeps the historical format, and zero delta-plane
        # objects exist (the brownout/breaker zero-object contract)
        self._delta_plane = None
        self._delta_gen = 0
        # live telemetry plane (obs/slo.py, obs/export.py;
        # docs/OBSERVABILITY.md tier 3): per-tenant SLO burn-rate
        # monitors + the in-process metrics endpoint — both None for
        # the default config (no slo_targets / port 0: zero monitor
        # objects, zero exporter threads — the brownout/breaker
        # structural-zero contract, test-enforced). The exporter is
        # built LAST: its handler snapshots session state, so every
        # subsystem it reads must already exist.
        self._slo = slo_lib.from_config(self.config,
                                        emit=self._emit_alert_event)
        # multi-slice serving fleet (serve/fleet.py; docs/FLEET.md):
        # built lazily on the first submit when config.fleet_slices
        # >= 1 — None for the default config (the structural
        # zero-object contract: no slice sessions, no directory,
        # poisoned-init test-enforced). _slice_tag marks THIS session
        # as slice N of a fleet: its obs events carry the tag so the
        # per-slice roll-up can attribute them.
        self._fleet = None
        self._slice_tag: Optional[int] = None
        # fleet device arbitration (serve/fleet.py): an RLock SHARED
        # by the parent and every slice session whose execution
        # domains overlap — collective programs from two sessions
        # sharing devices must never be in flight together (colliding
        # run-ids over the same device list deadlock the
        # cross-program rendezvous; the classic multi-program
        # collective hazard). None (the default) = plain async
        # dispatch, bit-identical.
        self._exec_lock = None
        # answer provenance ledger (obs/provenance.py;
        # docs/OBSERVABILITY.md tier 4): None for the default config
        # (obs_provenance = 0 — the brownout/breaker structural-zero
        # contract: no ledger, no record objects, poisoned-init
        # test-enforced). When on, every served answer appends one
        # lineage record here and emits a ``provenance`` event.
        self._prov = provenance_lib.from_config(self.config)
        # cost-model re-plan controller (serve/replan.py;
        # docs/COST_MODEL.md): watches the query event stream and
        # turns a firing DRIFT rank-order flag into a coefficient
        # re-calibration + background re-warm of the affected cached
        # plans — None unless config.coeff_replan_enable (the
        # structural-zero contract: replan._CONSTRUCTED stays 0,
        # poisoned-init test-enforced)
        self._replan = replan_lib.from_config(self.config, self)
        self._exporter = export_lib.from_config(self)
        # lockdep diagnostics ride the ONE obs funnel as ``lockdep``
        # events (event log + flight ring; history --summary rolls
        # them up, --check fails on inversions). Wired last: the
        # funnel reads _slice_tag/_flight, which now exist.
        if self.config.lockdep_enable:
            lockdep.set_emit(
                lambda rec: self._obs_emit("lockdep", rec))

    # -- builder (MatfastSession.builder().getOrCreate() analogue) ---------

    class Builder:
        def __init__(self):
            self._cfg = default_config()
            self._mesh = None
            self._explicit_cfg = False

        def config(self, **kw) -> "MatrelSession.Builder":
            self._cfg = self._cfg.replace(**kw)
            self._explicit_cfg = True
            return self

        def mesh(self, mesh: Mesh) -> "MatrelSession.Builder":
            self._mesh = mesh
            return self

        def get_or_create(self) -> "MatrelSession":
            global _active
            if _active is None:
                _active = MatrelSession(self._mesh, self._cfg)
                return _active
            # a live session wins — but silently ignoring an
            # explicitly-requested different config/mesh hands the
            # caller settings they did not ask for
            if self._explicit_cfg and self._cfg != _active.config:
                log.warning(
                    "MatrelSession.builder(): a session already exists; "
                    "ignoring the requested config (differs from the "
                    "live session's — call reset_session() first to "
                    "rebuild with new settings)")
            if self._mesh is not None and self._mesh != _active.mesh:
                log.warning(
                    "MatrelSession.builder(): a session already exists; "
                    "ignoring the requested mesh (differs from the live "
                    "session's — call reset_session() first)")
            return _active

    @staticmethod
    def builder() -> "MatrelSession.Builder":
        return MatrelSession.Builder()

    # -- catalog (matrix tables, SQL-facing names) -------------------------

    def register(self, name: str, matrix: BlockMatrix) -> None:
        old = self.catalog.get(name)
        self.catalog[name] = matrix
        if self._fleet is not None and old is not matrix:
            # fleet write-through (docs/FLEET.md): the table
            # replicates into every slice, slice caches invalidate
            # through each slice session's own rebind path, and
            # directory records naming it drop. Gated like the
            # single-controller rebind below: an idempotent
            # re-register of the SAME object is a no-op there and
            # must be one here too — unconditional it would wipe the
            # directory and every slice cache and re-replicate the
            # table on every no-op call
            self._fleet.on_register(name, matrix)
        if old is not None and old is not matrix:
            # catalog REBIND: every cached result computed from the old
            # binding is stale the moment the name means something else
            # — drop them (and their pinned device bytes) now, not at
            # some later false hit. Dep sets are transitive, so results
            # built FROM cached intermediates of the old binding drop
            # too. Safe when the cache is off/empty (no-op). With a
            # brownout controller the invalidated entries move to the
            # bounded STALE graveyard instead: rung 2 may serve them to
            # queries declaring a staleness_ms tolerance
            # (docs/OVERLOAD.md); the default path drops them exactly
            # as before.
            self._result_cache.invalidate_deps(
                {id(old)},
                keep_stale=self._brownout is not None,
                stale_max=self.config.result_cache_max_entries,
                stale_max_bytes=self.config.result_cache_max_bytes)
            if self._spill is not None:
                # restored snapshot entries carry dep NAMES, not ids
                # (serve/spill.py): the rebind kill reaches them by
                # name — the id cascade above already covered the
                # live host/disk tiers
                self._spill.invalidate_names({name})

    def table(self, name: str) -> BlockMatrix:
        return self.catalog[name]

    def register_delta(self, name: str, delta, kind: str = "auto"
                       ) -> dict:
        """Rebind a catalog name to ``A + ΔA`` and MAINTAIN dependent
        cached results instead of invalidating them (incremental view
        maintenance — serve/ivm.py, ir/delta.py; docs/IVM.md).

        ``delta`` is the update in whichever form the caller has it:
        ``(rows, cols[, vals])`` edge arrays or a COOMatrix (``kind=
        "coo"``), a ``(U, V)`` pair with ``ΔA = U·Vᵀ`` (``kind=
        "lowrank"``), or a same-shaped array (``kind="dense"``);
        ``kind="auto"`` disambiguates by shape. ``kind="rows"`` with
        ``(row_ids, values)`` REPLACES rows: after the call the table's
        rows ``row_ids`` equal ``values`` bit for bit; against a dense
        float32 table on one device the rows are overwritten IN PLACE
        (the registered BlockMatrix stays the object it was and takes
        the new array: no second table) and the views with a rows rule
        (``t(X) * X``, ``t(X) * y``) are corrected from the rows that
        left and the rows that came (docs/IVM.md). Each cached entry
        depending on the old binding is patched in place through the
        delta algebra where a rule applies AND the patch prices below
        recompute (``config.delta_patch_mode``; a measured autotune
        ``ivm|`` winner overrides the estimate); everything else falls
        back to exactly the historical transitive kill, so answers are
        never wrong — at worst a repeat pays recompute like today.

        Patched entries carry ``delta:<gen>|`` provenance in their
        cache keys and a composed error bound MV113 verifies against
        fresh execution. Returns the maintenance summary (also emitted
        as a ``delta`` obs event)."""
        old = self.catalog.get(name)
        if old is None:
            raise KeyError(
                f"register_delta: {name!r} is not a bound catalog "
                f"name — register() it first")
        from matrel_tpu.ir import delta as delta_lib
        with trace_lib.entry("delta", self._tracer, kind=kind) as sp:
            d = delta_lib.as_delta(delta, old, kind, self.config)
            with self._compile_lock:
                if self._delta_plane is None:
                    from matrel_tpu.serve.ivm import DeltaPlane
                    self._delta_plane = DeltaPlane(self)
                out = self._delta_plane.apply(name, old, d)
            if sp.live:
                sp.set(**{k: out.get(k) for k in (
                    "delta_kind", "in_place", "patched", "killed",
                    "no_rule", "rebased", "table_passes")})
        if self._fleet is not None:
            # fleet slices hold REPLICAS of the old binding: the delta
            # plane patched the parent's caches in place, but a slice
            # replica cannot be patched remotely — re-replicate the
            # new binding (slice caches invalidate through their own
            # rebind path, directory records naming it drop). Answers
            # stay correct; a slice repeat pays one recompute.
            self._fleet.on_register(name, self.catalog[name])
        # SLO feed (obs/slo.py): patch latency reports under the
        # pseudo-tenant "ivm", so a dashboard stream's maintenance
        # path can carry its own latency objective (docs/IVM.md
        # events are the offline view of the same number). No-op
        # without a declared ivm target.
        if self._slo is not None and isinstance(out.get("ms"),
                                                (int, float)):
            self._slo.observe_latency(slo_lib.IVM_TENANT,
                                      float(out["ms"]))
        return out

    def save_catalog(self, directory: str,
                     step: Optional[int] = None) -> str:
        """Persist every registered table (atomic step dir, sharding
        metadata included) — the session-level face of the checkpoint
        subsystem, so a catalog survives process restarts the way the
        reference's persisted tables do. ``step`` defaults to the NEXT
        step in the directory (a fixed default like 0 would be GC'd by
        the keep-k policy the moment older saves carry higher steps).
        Returns the step path."""
        from matrel_tpu.utils.checkpoint import CheckpointManager
        mgr = CheckpointManager(directory, config=self.config)
        if step is None:
            step = mgr.next_step()
        return mgr.save(step, matrices=dict(self.catalog))

    def load_catalog(self, directory: str,
                     step: Optional[int] = None) -> list:
        """Restore tables saved by save_catalog into this session's
        catalog (sharding-preserving, existing names overwritten).
        Returns the restored names; empty directory → empty list."""
        from matrel_tpu.utils.checkpoint import CheckpointManager
        got = CheckpointManager(directory,
                                config=self.config).restore(self.mesh,
                                                            step)
        if got is None:
            return []
        _step, mats, _arrays, _state = got
        # through register(), not a bare dict update: an overwritten
        # name is a catalog REBIND, and cached results computed from
        # the old binding must invalidate here exactly as they do for
        # an explicit register() (serve/result_cache.py contract)
        for name in sorted(mats):
            self.register(name, mats[name])
        return sorted(mats)

    # -- durable state (serve/spill.py; docs/DURABILITY.md) -----------------

    def save_state(self, directory: Optional[str] = None) -> dict:
        """Snapshot this session's durable state — catalog bindings
        (the checkpoint step format), the result-cache index (entries
        with catalog-name-computable keys, frozen as sha1-verified
        disk artifacts), the fleet directory, MQO template keys, and
        the autotune/drift tables — under ``directory`` (default
        ``config.state_dir``; neither set raises ValueError). A later
        :meth:`restore` in a NEW process comes back serving warm:
        repeats thaw the frozen entries instead of recomputing.
        Without ``spill_enable`` only the catalog + tables persist
        (cached results are skipped, counted in the summary) — the
        zero-object default stays zero. Returns the save summary,
        also emitted as a ``spill`` event (op ``save_state``)."""
        from matrel_tpu.serve import spill as spill_lib
        with self._compile_lock:
            out = spill_lib.save_state(self, directory)
        self._emit_spill_event({"op": "save_state", **out})
        return out

    def restore(self, directory: Optional[str] = None) -> dict:
        """Warm-restart this session from a :meth:`save_state`
        snapshot: catalog restored through :meth:`register`, tables
        written if absent, the result-cache index seeded into the
        spill hierarchy's restored tier (requires ``spill_enable``;
        entries thaw lazily on first consult, paying only the priced
        transfer), the fleet directory re-seeded as affinity hints,
        MQO template keys re-indexed. ROBUST: a corrupt/truncated
        snapshot (or any single bad component) warns and cold-starts
        — restore never crashes a restart; a disk-tier entry failing
        its sha1 later surfaces as a per-entry miss (typed
        ``SnapshotCorruption`` internally), never a wrong answer.
        Returns the restore summary, also emitted as a ``spill``
        event (op ``restore``)."""
        from matrel_tpu.serve import spill as spill_lib
        with self._compile_lock:
            out = spill_lib.load_snapshot(self, directory)
        self._emit_spill_event({"op": "restore", **out})
        return out

    # -- constructors bound to this session's mesh/config ------------------

    def from_numpy(self, arr: np.ndarray, **kw) -> BlockMatrix:
        return BlockMatrix.from_numpy(arr, mesh=self.mesh, config=self.config, **kw)

    def random(self, shape: Tuple[int, int], **kw) -> BlockMatrix:
        return BlockMatrix.random(shape, mesh=self.mesh, config=self.config, **kw)

    def zeros(self, shape: Tuple[int, int], **kw) -> BlockMatrix:
        return BlockMatrix.zeros(shape, mesh=self.mesh, config=self.config, **kw)

    def eye(self, n: int, **kw) -> BlockMatrix:
        return BlockMatrix.eye(n, mesh=self.mesh, config=self.config, **kw)

    # -- actions ------------------------------------------------------------

    def compile(self, expr: MatExpr,
                precision: Optional[str] = None
                ) -> executor_lib.CompiledPlan:
        e = as_expr(expr)
        return self._compile_entry(e, sla=self._resolve_sla(precision,
                                                            e))[0]

    # -- precision SLA resolution (docs/PRECISION.md) ----------------------

    def _resolve_sla(self, precision, e: Optional[MatExpr] = None) -> str:
        """One query's effective precision SLA: the explicit
        ``precision=`` argument beats a SQL ``PRECISION '...'`` clause
        (stamped out-of-band by sql.parse_sql) beats the session
        default (config.precision_sla)."""
        if precision is not None:
            return normalize_sla(precision)
        sql_sla = getattr(e, "_sql_precision", None) if e is not None \
            else None
        if sql_sla is not None:
            return sql_sla            # parse_sql already normalised
        return self.config.precision_sla

    def _sla_config(self, sla: str) -> MatrelConfig:
        """The config a query at this SLA compiles under — the session
        config itself when they agree (the common case: no dataclass
        churn on the hot path)."""
        if sla == self.config.precision_sla:
            return self.config
        return self.config.replace(precision_sla=sla)

    def _compile_entry(self, e: MatExpr, sla: Optional[str] = None,
                       rung: int = 0
                       ) -> Tuple[executor_lib.CompiledPlan, bool, str]:
        """(plan, cache_hit, key) of ``e``'s OWN plan — the concrete
        key only, no template: for the callers that take no bindings
        (``compile()``, IVM's rebase, ``_replan_warm``). ``rung`` > 0
        compiles a DEGRADED retry attempt (resilience/degrade.py): the
        config loses the rung's features and the key gains the
        ``degr:<rung>|`` prefix, so a degraded plan never shares a cache
        slot with the stamped original (the axisw/prec prefix idiom)."""
        return self._plan_lookup(e, sla, rung, rebind=False)[:3]

    def _plan_lookup(self, e: MatExpr, sla: Optional[str] = None,
                     rung: int = 0, rebind: bool = True):
        """(plan, hit, key, bindings): which compiled program answers
        ``e``. The order lives here and nowhere else — the concrete
        key; on its miss the abstract key (serve/mqo.py: a plan
        template, the program already compiled for this structure,
        its dense leaves rebound); else compile, and insert under both.
        ``bindings`` (dense-leaf uid -> matrix, for ``plan.run``) is
        None on a concrete hit and on a compile. Both keys compose the
        SAME isolation prefixes, so a degraded or fast-SLA template
        can never serve a pristine exact query."""
        sla = sla if sla is not None else self.config.precision_sla
        with trace_lib.span("plan") as sp:
            # fault site "compile" (resilience/faults.py): free when off
            faults_lib.check("compile", self.config)
            key, pins = _plan_key(e)
            prefix = (degrade_lib.key_prefix(rung) + self._axisw_prefix()
                      + self._coeff_prefix() + _prec_prefix(sla))
            key = prefix + key
            plan = self._plan_probe(key, sp)
            if plan is not None:
                return plan, True, key, None
            if rebind:
                abstract = [mqo_lib.template_key(e)]
                tkey = prefix + abstract[0][0]
                tpl = self._template_rebind(tkey, abstract, 1, sp)
                if tpl is not None:
                    plan, _slots, bindings = tpl
                    return plan, True, key, bindings

        def build():
            plan = executor_lib.compile_expr(
                e, self.mesh,
                degrade_lib.apply_rung(self._sla_config(sla), rung))
            # pin every id()-keyed object on the cached plan: a garbage-
            # collected object's address can be REUSED by CPython, and a
            # later distinct object at the recycled address would falsely
            # hit this entry. Pinning the expr alone is not enough — a
            # REBOUND module global referenced by a predicate is no longer
            # reachable from the expr, so its old value is pinned
            # explicitly via the collected pins list.
            plan._cache_pin = (e, pins)
            return plan

        plan, hit = self._plan_build(key, build, rung)
        if rebind and not hit:
            self._template_record(tkey, abstract, plan)
        return plan, hit, key, None

    def _plan_probe(self, key: str, sp):
        """The plan cache's answer for ``key`` (None: a miss), noted on
        the ``plan`` span as ``hit``."""
        with self._compile_lock:
            plan = self._plan_cache.get(key)
            if plan is not None:
                self._plan_cache.move_to_end(key)
        self._last_hit = plan is not None
        sp.set(hit=self._last_hit)
        return plan

    def last_plan(self) -> dict:
        """What the newest dispatched plan says of itself — for a caller
        outside a profiler session, where the spans are dark (the
        ``workloads.pagerank.last_plan`` idiom): ``hit`` (the plan cache
        or a plan template answered the lookup), ``executors``,
        ``hbm_plan_bytes``, ``products`` (the memory reckoning's record
        of each product, solve and materialised transpose:
        planner.hbm_report, with ``gram_tiles`` / ``gram_rides``, a
        long Gram's ``gram_kernel`` (planner.gram_kernel_plan's facts:
        ``one_read`` where ONE kernel multiplies it, else ``why_not``)
        and a
        mesh's ``operand_layout`` / ``devices`` / ``rows_a_device`` /
        ``reduce_bytes`` where they apply), and of its coo_leaf products
        ``spmm`` (one
        record each that the SpMV tables answer: what its
        ``matrel.spmm.plan`` span carries), ``sampled`` (one each
        sampled product answered fused, core.coo.sampled_facts: what its
        ``matrel.sampled.plan`` span carries), ``semiring`` (one each
        (max | min, ×) product of a coo_leaf and a column,
        core.coo.semiring_facts: what its ``matrel.semiring.plan`` span
        carries), ``mmchain`` (one each chain ``t(X) * (w .* (X * v))``
        the rule found, planner.mmchain_plan's facts: ``one_read`` true
        where one pass over X answered it, else ``why_not`` and the two
        products under ``products``; what its ``matrel.mmchain.plan``
        span carries) and ``densified_products``
        (one each leaf that was densified, under a product or read as
        an array). With the result cache on, of the newest STATEMENT:
        ``views_hit`` (the cached results it was answered from: 1 with
        ``root_hit`` where the statement itself was cached and nothing
        was dispatched — the plan's records are then the plan's before
        it) and ``table_pass`` (whether what was dispatched still read
        a catalog table). Copies; {} before the first dispatch."""
        plan = self._last_plan
        if plan is None:
            return {}
        meta = plan.meta or {}
        return {**(self._last_rc or {}),
                "hit": self._last_hit,
                "executors": list(meta.get("executors") or ()),
                "hbm_plan_bytes": meta.get("hbm_plan_bytes"),
                "products": [dict(r) for r in meta.get("products", ())],
                "spmm": [dict(r) for r in meta.get("spmm", ())],
                "sampled": [dict(r) for r in meta.get("sampled", ())],
                "semiring": [dict(r) for r in meta.get("semiring", ())],
                "mmchain": [dict(r) for r in meta.get("mmchain", ())],
                "densified_products": [
                    dict(r) for r in meta.get("densified_products", ())]}

    def _plan_build(self, key: str, build, rung: int):
        """(plan, hit) after a probe missed: compile under the lock and
        the ``compile`` span, insert, evict. ``hit`` is True when a
        racing caller compiled the same key between probe and lock."""
        with self._compile_lock:
            plan = self._plan_cache.get(key)
            if plan is not None:
                return plan, True
            with trace_lib.phase("compile") as sp:
                try:
                    plan = build()
                except Exception as ex:
                    # post-mortem trail BEFORE the error propagates: a
                    # VerificationError / compile failure in the field
                    # leaves the flight-recorder artifact, not just the
                    # exception string (no-op when the recorder is off)
                    self._flight_auto_dump(ex)
                    raise
                sp.set(executors=plan.meta.get("executors"))
            if rung:
                # the rung rides the plan so obs events / explain say
                # WHICH ladder step produced this attempt's plan
                plan.meta["degrade"] = degrade_lib.rung_meta(rung)
            self._plan_cache[key] = plan
            self._plan_cache_bytes += _plan_bytes(plan)
            self._evict_plans()
            return plan, False

    def _axisw_prefix(self) -> str:
        """Topology weights change which strategies get stamped, so
        weighted and unweighted plans must never share a cache entry
        (the detection path can flip weights without any config field
        changing — the expression key alone is not enough). Unweighted
        keys keep the historical format (empty prefix)."""
        wts = mesh_lib.axis_weights(self.mesh, self.config)
        if wts == (1.0, 1.0):
            return ""
        return f"axisw:{wts[0]:g}x{wts[1]:g}|"

    def _coeff_epoch(self) -> Optional[str]:
        """The coefficient epoch in force (parallel/coeffs.epoch — a
        digest of the drift table's blended ratios), or None with
        coeff_planner_enable off. Rides every query record and
        provenance capture, so obs can always say which coefficients
        priced an answer's plan (docs/COST_MODEL.md)."""
        if not self.config.coeff_planner_enable:
            return None
        from matrel_tpu.obs import drift as drift_lib
        from matrel_tpu.parallel import coeffs as coeffs_lib
        return coeffs_lib.epoch(drift_lib.table_path(self.config))

    def _coeff_prefix(self) -> str:
        """Coefficient-epoch plan-key isolation (the axisw/prec/delta
        prefix idiom; docs/COST_MODEL.md): plans ranked under
        different learned coefficients must never share a cache slot —
        a re-calibration (serve/replan.py) bumps the epoch, so every
        affected entry invalidates LAZILY: old plans keep serving
        in-flight queries, new lookups miss and recompile under the
        corrected coefficients. Empty with coeff_planner_enable off
        (the historical key format, bit-identical)."""
        ep = self._coeff_epoch()
        return "" if ep is None else f"coeffv:{ep}|"

    def _compile_multi_entry(self, roots: List[MatExpr],
                             sla: Optional[str] = None,
                             rung: int = 0
                             ) -> Tuple["executor_lib.MultiPlan", bool,
                                        List[str]]:
        """(multiplan, cache_hit, per-root keys) — the MultiPlan twin
        of :meth:`_compile_entry`: the batch's OWN plan, no template.
        Map outputs back to root order through the plan's
        ``_root_keys``."""
        return self._plan_lookup_multi(roots, sla, rung, rebind=False)[:3]

    def _plan_lookup_multi(self, roots: List[MatExpr],
                           sla: Optional[str] = None, rung: int = 0,
                           rebind: bool = True):
        """(multiplan, hit, per-root keys, pos, bindings) — the
        MultiPlan twin of :meth:`_plan_lookup`; ``pos[key]`` is the
        output that answers the root of that key. Compiled MultiPlans
        participate in the SAME session plan cache (one LRU, one byte
        budget — their hoisted payloads pin HBM exactly like single
        plans'), keyed on the SORTED unique root keys plus the
        isolation prefixes, so a batch resubmitted in any order (or
        with duplicate roots) hits instead of recompiling every call.
        A template pairs roots to its outputs by ABSTRACT key:
        structurally identical roots are interchangeable programs."""
        sla = sla if sla is not None else self.config.precision_sla
        with trace_lib.span("plan", roots=len(roots)) as sp:
            # fault site "compile": the MultiPlan twin shares the site
            faults_lib.check("compile", self.config)
            keyed = []
            pins_all: list = []
            for e in roots:
                k, p = _plan_key(e)
                keyed.append(k)
                pins_all.extend(p)
            uniq: "OrderedDict[str, MatExpr]" = OrderedDict()
            for k, e in zip(keyed, roots):
                uniq.setdefault(k, e)
            skeys = sorted(uniq)
            prefix = ("multi:" + degrade_lib.key_prefix(rung)
                      + self._axisw_prefix() + self._coeff_prefix()
                      + _prec_prefix(sla))
            mkey = prefix + "||".join(skeys)
            plan = self._plan_probe(mkey, sp)
            if plan is None and rebind:
                abstract = [mqo_lib.template_key(uniq[k]) for k in skeys]
                tkey = prefix + "||".join(sorted(a[0] for a in abstract))
                tpl = self._template_rebind(tkey, abstract, len(roots),
                                            sp)
                if tpl is not None:
                    plan, slots, bindings = tpl
                    return (plan, True, keyed, dict(zip(skeys, slots)),
                            bindings)

        def build():
            plan = executor_lib.compile_exprs(
                [uniq[k] for k in skeys], self.mesh,
                degrade_lib.apply_rung(self._sla_config(sla), rung))
            plan._cache_pin = (tuple(uniq[k] for k in skeys), pins_all)
            plan._root_keys = tuple(skeys)
            return plan

        hit = plan is not None
        if not hit:
            plan, hit = self._plan_build(mkey, build, rung)
            if rebind and not hit:
                # ``abstract`` is in plan-root order by construction
                self._template_record(tkey, abstract, plan)
        return (plan, hit, keyed,
                {k: j for j, k in enumerate(plan._root_keys)}, None)

    def _template_rebind(self, tkey: str, abstract: list, served: int,
                         sp):
        """(plan, the template output answering each root, bindings)
        where the template under ``tkey`` can answer roots whose
        ``mqo_lib.template_key`` walks are ``abstract`` by REBINDING
        its dense leaves — None when there is none, or sound bindings
        cannot be formed (a shared template leaf facing two distinct
        matrices — miss, never a guess). Any assignment within an
        abstract-key group is sound as long as the caller routes each
        root to its assigned output."""
        with self._compile_lock:
            st = self._mqo_state()
            ent = st.get_template(tkey)
            if ent is None or not mqo_lib.rebindable(ent):
                return None
            pool: dict = {}
            for s, (ak, _uids) in enumerate(ent.slots):
                pool.setdefault(ak, []).append(s)
            slots: list = []
            bindings: dict = {}
            for ak, _pins, leaves in abstract:
                free = pool.get(ak)
                if not free:
                    return None
                slots.append(free.pop(0))
                uids = ent.slots[slots[-1]][1]
                if len(uids) != len(leaves):
                    return None
                for u, l in zip(uids, leaves):
                    m = l.attrs["matrix"]
                    if bindings.setdefault(u, m) is not m:
                        return None
            if any(pool.values()):
                return None     # template has roots this batch lacks
            st.template_hits += served
        self._last_hit = True
        sp.set(hit=True, via="template")
        return ent.plan, slots, bindings

    def _template_record(self, tkey: str, abstract: list, plan) -> None:
        """Hold a freshly compiled plan rebindable under its abstract
        key. Guarded by :func:`mqo_lib.rebindable`: when the optimizer
        dropped or re-created a dense leaf (fresh uid), the recorded
        uids and the program's real binding order disagree — a rebind
        would silently feed stale data, so no template is stored (the
        only cost is no speedup)."""
        ent = mqo_lib.TemplateEntry(
            plan=plan,
            slots=tuple((ak, tuple(l.uid for l in leaves))
                        for ak, _pins, leaves in abstract),
            pins=tuple(p for _ak, pins, _lv in abstract for p in pins))
        if not mqo_lib.rebindable(ent):
            return
        with self._compile_lock:
            st = self._mqo_state()
            st.put_template(tkey, ent)
            st.template_inserts += 1

    def _evict_plans(self) -> None:
        """Drop least-recently-used plans past the config bounds. The
        byte budget counts hoisted payloads (extra_args) — the device
        memory a cached plan pins beyond its leaves."""
        cfg = self.config
        while self._plan_cache and (
                len(self._plan_cache) > cfg.plan_cache_max_plans
                or self._plan_cache_bytes > cfg.plan_cache_max_bytes):
            if len(self._plan_cache) == 1 and \
                    len(self._plan_cache) <= cfg.plan_cache_max_plans:
                break    # never evict the sole (just-inserted) plan
            _, old = self._plan_cache.popitem(last=False)
            self._plan_cache_bytes -= _plan_bytes(old)
            self._plan_cache_evicted += 1
        self._plan_cache_bytes = max(self._plan_cache_bytes, 0)

    def plan_cache_info(self) -> dict:
        """Cache observability: entry count + pinned hoisted bytes +
        lifetime eviction count."""
        return {"plans": len(self._plan_cache),
                "hoisted_bytes": self._plan_cache_bytes,
                "evicted": self._plan_cache_evicted}

    def plan_executors(self) -> List[List[str]]:
        """Which executors each cached plan runs
        (``plan.meta["executors"]``), least recently used first. Kept
        out of ``plan_cache_info()``, which rides every obs-on query
        record and metrics snapshot: this one is per plan. Takes no
        lock — a compile in another thread holds ``_compile_lock`` for
        seconds; the copy of the values is one C call."""
        return [list(p.meta.get("executors") or ())
                for p in list(self._plan_cache.values())]

    def _replan_warm(self, classes) -> dict:
        """Proactively recompile cached plans whose matmul decisions
        touch the given shape classes, under the CURRENT coefficient
        epoch (serve/replan.py's background thread calls this after a
        re-calibration). Correctness never depends on it — the
        ``coeffv:`` key prefix already makes every post-bump lookup
        miss and recompile lazily; this pass just pays the compiles
        off the query path. Each entry re-warms from its pinned root
        expr(s) at the session default SLA / rung 0 — SLA-variant and
        degraded entries re-warm lazily on first use (a warm is an
        optimization, so fidelity loss there costs one compile, never
        an answer). Old-epoch entries stay until LRU eviction: an
        in-flight query holding one is never invalidated under it."""
        from matrel_tpu.obs import drift as drift_lib
        with self._compile_lock:
            snapshot = list(self._plan_cache.values())
        matched = warmed = 0
        for plan in snapshot:
            pin = getattr(plan, "_cache_pin", None)
            if pin is None:
                continue
            try:
                decs = executor_lib.plan_matmul_decisions(plan)
            except Exception:  # matlint: disable=ML007 best-effort warm census — an unreadable plan is skipped; the lazy coeffv: miss still re-plans it
                continue
            if not any(drift_lib.shape_class(d.get("dims") or ())
                       in classes for d in decs):
                continue
            matched += 1
            roots = pin[0]
            try:
                if isinstance(roots, tuple):
                    self._compile_multi_entry(list(roots))
                else:
                    self._compile_entry(roots)
                warmed += 1
            except Exception:
                log.warning("replan: warm recompile failed",
                            exc_info=True)
        return {"matched": matched, "replanned": warmed}

    # -- cross-query result cache (matrel_tpu/serve/) ----------------------

    def _rc_enabled(self) -> bool:
        return self.config.result_cache_max_bytes > 0

    def result_cache_info(self) -> dict:
        """``plan_cache_info``-style surface for the materialized-result
        cache: entries, pinned device bytes, hit/miss/interior-hit,
        eviction and invalidation counts."""
        info = self._result_cache.info()
        info["max_bytes"] = self.config.result_cache_max_bytes
        info["max_entries"] = self.config.result_cache_max_entries
        return info

    def _rc_key_prefix(self, sla: str) -> str:
        """The full result-cache key prefix of one query: the delta
        GENERATION prefix (``delta:<gen>|`` — docs/IVM.md; empty until
        ``register_delta`` is ever used, so the historical key format
        is bit-identical) composed with the precision-tier isolation
        prefix. Generations partition the cache the way SLAs do: a
        patched entry from generation N can never answer a query at
        N+1 without having been re-patched (or re-executed)."""
        gen = self._delta_gen
        return (("" if not gen else f"delta:{gen}|")
                + _prec_prefix(sla))

    def _rc_admit(self, e: MatExpr, prefix: str = ""):
        """One result-cache admission for a query: (entry-or-None,
        root key, pins, possibly-substituted expr). ONE structural walk
        (_plan_key_spans) serves both the root-level consult — a hit
        answers without compiling or executing anything — and, on a
        miss, every interior probe of the substitution pass.

        ``prefix`` carries the query's precision-tier isolation
        (_prec_prefix): every consult, interior probe AND insertion
        keys under it, so a ``"fast"`` entry can never answer an
        ``"exact"`` query (or vice versa) — accuracy SLAs partition
        the cache, they do not share it."""
        # fault site "rc_probe": a faulting cache consult is exactly
        # what the ladder's rung-4 bypass exists to route around
        faults_lib.check("rc_probe", self.config)
        parts, pins, spans = _plan_key_spans(e)
        key = prefix + "|".join(parts)
        ent = self._result_cache.lookup(key)
        if ent is None and self._spill is not None \
                and self._spill.restored_count():
            # warm restart (docs/DURABILITY.md): a restored snapshot's
            # name-keyed index may hold this query's value frozen at
            # disk tier — thaw it, and the repeat pays a priced
            # transfer instead of a recompute
            ent = self._rc_thaw_restored(e, prefix, key)
        if ent is not None:
            return ent, key, pins, e
        return None, key, pins, self._rc_substitute(e, parts, spans,
                                                    prefix)

    def _rc_thaw_restored(self, e: MatExpr, prefix: str, key: str):
        """Consult the restored-snapshot index on a cache miss: the
        session-independent NAME key (placement.fleet_key — catalog
        names, not id()s) is the only key format that survives a
        process boundary. A thaw re-resolves dep names against the
        LIVE catalog, re-inserts under the query's live structural
        key (so the next repeat is a plain HBM hit), and corrects the
        miss the first-level lookup already counted. Precision tiers
        stay isolated: the entry thaws only for a query under the
        same ``prec:`` token it was cached under."""
        from matrel_tpu.serve import placement as placement_lib
        nk = placement_lib.fleet_key(
            e, {id(m): n for n, m in self.catalog.items()})
        if nk is None:
            return None
        # the prec component of the admission prefix (the delta:<gen>|
        # part, when present, always precedes it and ends at its "|")
        prec = (prefix.split("|", 1)[1]
                if prefix.startswith("delta:") else prefix)
        ent = self._spill.thaw_restored(nk, prec, self.catalog.get)
        if ent is None:
            return None
        self._result_cache.note_restored_hit()
        self._result_cache.put(key, ent,
                               self.config.result_cache_max_bytes,
                               self.config.result_cache_max_entries)
        return ent

    def _rc_leaf(self, ent: CacheEntry) -> MatExpr:
        """Lift a cache entry into planning as an already-laid-out
        LEAF: ``infer_layout`` reads the cached result's real
        PartitionSpec and ``comm_cost`` credits the reuse — the whole
        subplan it replaces is never re-priced, never re-executed. The
        ``result_cache`` stamp records what the cache promised (layout/
        dtype at insertion) so the MV107 pass can prove the plan and
        the cache still agree, plus the transitive dep ids consumers
        fold into their own invalidation sets."""
        from matrel_tpu.ir import expr as expr_mod
        stamp = {
            "key_hash": ent.key_hash,
            "layout": ent.layout,
            "dtype": ent.dtype,
            "deps": sorted(ent.dep_ids),
        }
        if ent.delta_gen:
            # IVM provenance (docs/IVM.md): the consumed value was
            # delta-PATCHED, not freshly executed — MV113's static
            # half checks the stamp's coherence, its dynamic half
            # re-proves the value against fresh execution
            stamp["delta"] = {"gen": ent.delta_gen,
                              "rule": ent.delta_rule,
                              "err_bound": ent.err_bound}
        if ent.fleet:
            # fleet provenance (docs/FLEET.md): the consumed value was
            # REPLICATED from another slice's cache — MV114 re-checks
            # the owning slice's recorded layout/dtype against the
            # entry's own claims (the MV107 stale-stamp idiom across
            # slices)
            stamp["fleet"] = dict(ent.fleet)
        if ent.spill:
            # spill provenance (docs/DURABILITY.md): the consumed
            # value was THAWED from a lower tier — MV117 re-checks
            # the stamped legs against the step vocabulary and the
            # peak-HBM budget claim
            stamp["spill"] = dict(ent.spill)
        node = expr_mod.leaf(ent.result).with_attrs(result_cache=stamp)
        if self._prov is not None:
            # lineage threading (obs tier 4): the consumed entry's
            # provenance stamp rides the substitution leaf so MV115
            # can cross-check it against the result_cache stamp — the
            # attrs write lives in the ledger (ML015's one seam)
            node = self._prov.stamp_leaf(node, ent)
        return node

    def _rc_chain(self, e: MatExpr, prefix: str) -> Optional[MatExpr]:
        """A product chain with its cached SUB-PRODUCTS substituted, or
        None where it has none. The parser brackets ``inv(t(X) * X) *
        t(X) * y`` to the left, so ``t(X) * y`` is no subtree of it and
        the structural walk of :meth:`_rc_substitute` cannot find the
        view a statement ``t(X) * y`` left in the cache; only the chain
        DP (after this consult) would bracket it so. The chain's
        factors are taken as the DP takes them (ir/chain.collect_chain,
        plain products only), every run of two or more of them — the
        whole chain is the caller's own probe — is keyed as the
        statement that would have cached it (left to right) and probed,
        longest first, and a hit stands in for its factors."""
        from matrel_tpu.ir import expr as expr_mod
        factors = _product_factors(e)
        if len(factors) < 3:
            return None
        hit = False
        width = len(factors) - 1
        while width >= 2:
            i = 0
            while i + width <= len(factors):
                node = functools.reduce(expr_mod.matmul,
                                        factors[i:i + width])
                ent = self._result_cache.probe(prefix + _plan_key(node)[0])
                if ent is None:
                    i += 1
                else:
                    factors[i:i + width] = [self._rc_leaf(ent)]
                    hit = True
            width = min(width, len(factors)) - 1
        return functools.reduce(expr_mod.matmul, factors) if hit else None

    def _rc_substitute(self, e: MatExpr, parts: Optional[list] = None,
                       spans: Optional[dict] = None,
                       prefix: str = "",
                       _in_chain: bool = False) -> MatExpr:
        """Replace every cached INTERIOR subexpression with its result
        leaf (top-down; a hit stops the descent — everything under it
        is already paid for). The root is the caller's business
        (:meth:`_rc_admit`). ``parts``/``spans`` come from the
        admission's single ``_plan_key_spans`` walk, so each interior
        probe is a slice join, not a fresh subtree walk; a bare call
        (tests, external callers) computes its own. ``prefix`` is the
        admission's precision-tier isolation prefix — interior probes
        only ever hit entries computed under the SAME SLA."""
        if not e.children:
            return e
        plain_product = e.kind == "matmul" and not e.attrs
        if plain_product and not _in_chain:
            # the top of a product chain: its cached sub-products
            # first (a view the parser's bracketing hides), then the
            # structural walk over what is left
            chained = self._rc_chain(e, prefix)
            if chained is not None:
                return self._rc_substitute(chained, prefix=prefix,
                                           _in_chain=True)
        if parts is None or spans is None:
            parts, _pins, spans = _plan_key_spans(e)
        new_children = []
        changed = False
        for c in e.children:
            if not c.children and c.kind in ("leaf", "sparse_leaf",
                                             "coo_leaf"):
                new_children.append(c)
                continue
            s, t = spans[c.uid]
            ent = self._result_cache.probe(
                prefix + "|".join(parts[s:t]))
            if ent is not None:
                new_children.append(self._rc_leaf(ent))
                changed = True
                continue
            nc = self._rc_substitute(c, parts, spans, prefix,
                                     _in_chain=plain_product)
            changed = changed or (nc is not c)
            new_children.append(nc)
        return e.with_children(tuple(new_children)) if changed else e

    def _rc_deps(self, e: MatExpr) -> frozenset:
        """id() of every SOURCE matrix a query's result depends on —
        ordinary leaves contribute their matrix, result-cache leaves
        their recorded (transitive) dep set, so invalidating a rebound
        catalog matrix cascades through derived entries."""
        deps: set = set()

        def walk(n: MatExpr):
            if n.kind == "leaf":
                rc = n.attrs.get("result_cache")
                if rc is not None:
                    deps.update(rc["deps"])
                    return
                cse = n.attrs.get("cse")
                if cse is not None:
                    # a hoisted shared interior carries its own
                    # transitive dep set (serve/mqo.py) — consumers
                    # fold it in so rebinding any source matrix under
                    # the hoist cascades into every consumer's entry
                    deps.update(cse["deps"])
                    return
                deps.add(id(n.attrs["matrix"]))
                return
            if n.kind in ("sparse_leaf", "coo_leaf"):
                deps.add(id(n.attrs["matrix"]))
                return
            for c in n.children:
                walk(c)

        walk(e)
        return frozenset(deps)

    def _rc_stale_probe(self, e: MatExpr, sla: str,
                        staleness_ms: Optional[float]):
        """Brownout rung-2 consult (docs/OVERLOAD.md): the STALE
        result-cache entry for this query, iff the query declared a
        ``staleness_ms`` tolerance its age fits. Same structural key +
        precision prefix as a live consult, so a stale "fast" result
        can never answer an "exact" query either."""
        if (not self._rc_enabled() or not staleness_ms
                or staleness_ms <= 0):
            return None
        parts, _pins, _spans = _plan_key_spans(e)
        key = self._rc_key_prefix(sla) + "|".join(parts)
        return self._result_cache.lookup_stale(key, staleness_ms)

    def _rc_insert(self, key: str, pins: list, executed: MatExpr,
                   out: BlockMatrix, orig: Optional[MatExpr] = None,
                   prec: str = "", plan=None,
                   prov: Optional[dict] = None) -> None:
        """Cache one executed query result under its structural key.
        ``executed`` is the (possibly substituted) tree that actually
        ran — its leaves name the dep matrices; ``pins`` are the key's
        id()-referenced objects (kept alive with the entry so the key
        can never falsely hit a recycled address). ``orig`` is the
        PRE-substitution query tree (what the delta plane derives
        patches from — docs/IVM.md); ``prec`` the tier prefix the key
        carries; ``plan`` supplies the stamped tier's error bound so
        patched descendants compose bounds from the right floor."""
        from matrel_tpu.parallel import planner
        from matrel_tpu.ir import expr as expr_mod
        bound = 0.0
        if plan is not None:
            bound = float(((plan.meta or {}).get("precision") or {})
                          .get("est_rel_err_bound") or 0.0)
        ent = CacheEntry(
            key_hash=hashlib.sha1(key.encode()).hexdigest()[:16],
            result=out,
            pins=tuple(pins),
            dep_ids=self._rc_deps(executed),
            layout=planner._layout_of(expr_mod.leaf(out), self.mesh),
            dtype=str(np.dtype(out.dtype)),
            nbytes=result_nbytes(out),
            expr=orig if orig is not None else executed,
            prec=prec,
            err_bound=bound,
        )
        if prov is not None and self._prov is not None:
            # lineage stamp (obs tier 4): the producing query's ledger
            # record names this entry and vice versa — the write
            # itself lives in the ledger (the ML015 one-seam idiom)
            self._prov.stamp_entry(ent, prov["path"],
                                   prov["query_id"])
        self._result_cache.put(key, ent,
                               self.config.result_cache_max_bytes,
                               self.config.result_cache_max_entries)

    # -- multi-query optimization (serve/mqo.py; docs/SERVING.md) -----------

    def _cse_on(self) -> bool:
        return bool(self.config.cse_enable)

    def _mqo_state(self) -> "mqo_lib.MqoState":
        if self._mqo is None:
            self._mqo = mqo_lib.MqoState(self.config)
        return self._mqo

    def mqo_info(self) -> dict:
        """``plan_cache_info``-style surface for the multi-query
        optimizer: template count, lifetime template hits/inserts,
        hoisted-interior counts (zero with ``cse_enable`` off). All
        zeros before the session's first plan-cache miss."""
        if self._mqo is None:
            return {"templates": 0, "template_hits": 0,
                    "template_inserts": 0, "cse_hoisted": 0,
                    "cse_batches": 0}
        return self._mqo.info()

    def _cse_hoist_batch(self, pend: list, sla: str, rung: int,
                         rc: bool) -> Tuple[list, int]:
        """Hoist the shared interiors of one pending batch into a
        compute-once MultiPlan, then substitute each result into its
        consumers as an already-laid-out ``cse``-stamped leaf (the
        result-cache interior-hit shape — ``infer_layout``/``comm_cost``
        credit the reuse, ``matmul_decisions`` marks ``cse_operands``).
        With the result cache on the hoisted results ALSO insert under
        their interior structural keys, so cross-time reuse, fleet
        replication and the provenance ledger ride the existing paths
        — and rebinding any source matrix under a hoist invalidates
        every consumer entry through the transitive dep sets. Returns
        (substituted pend, hoist count)."""
        from matrel_tpu.ir import expr as expr_mod
        from matrel_tpu.parallel import planner
        entries = []
        for _i, e in pend:
            parts, _pins, spans = _plan_key_spans(e)
            entries.append((e, parts, spans))
        hoists = mqo_lib.choose_hoists(entries,
                                       self.config.cse_min_uses)
        if not hoists:
            return pend, 0
        st = self._mqo_state()
        # the hoisted interiors are their own micro-batch: one
        # MultiPlan (plan-cache AND template participation — a
        # steady-state dashboard batch rebinding fresh leaves
        # recompiles nothing at all), one dispatch, one fusion domain
        with trace_lib.span("cse.hoist", shared=len(hoists)):
            plan, _hit, hkeys, pos, bindings = self._plan_lookup_multi(
                [h.expr for h in hoists], sla, rung)
            faults_lib.check("execute", self.config)
            outs = self._arbitrated_run(plan, bindings=bindings)
        rc_prefix = self._rc_key_prefix(sla)
        leaf_of: dict = {}
        for h, hk in zip(hoists, hkeys):
            out = outs[pos[hk]]
            full = rc_prefix + h.key
            stamp = {
                "key_hash": hashlib.sha1(
                    full.encode()).hexdigest()[:16],
                "layout": planner._layout_of(expr_mod.leaf(out),
                                             self.mesh),
                "dtype": str(np.dtype(out.dtype)),
                "deps": sorted(self._rc_deps(h.expr)),
                "uses": h.uses,
            }
            node = expr_mod.leaf(out).with_attrs(cse=stamp)
            summary = None
            if self._prov is not None:
                summary = self._prov_capture(
                    "cse_hoist", full, sla, rung=rung, expr=h.expr,
                    result=out, executed=h.expr, plan=plan,
                    strategies=executor_lib.multiplan_root_decisions(
                        plan)[pos[hk]])
            if rc:
                # the interior key is EXACTLY what a later query's
                # _rc_substitute probe computes for a matching subtree
                # (the spans contract), so the hoisted result serves
                # cross-time interior hits too
                _k2, p2 = _plan_key(h.expr)
                self._rc_insert(full, p2, h.expr, out, orig=h.expr,
                                prec=_prec_prefix(sla), plan=plan,
                                prov=summary)
            for u in h.uids:
                leaf_of[u] = node
        new_pend = []
        for (i, e), _entry in zip(pend, entries):
            se = mqo_lib.substitute(e, leaf_of)
            if se is not e:
                # MV116's dynamic-verify feed: (original, substituted)
                # — re-executing both fresh proves substituted ≡
                # unshared over real traffic
                st.remember(e, se)
            new_pend.append((i, se))
        st.cse_hoisted += len(hoists)
        st.cse_batches += 1
        return new_pend, len(hoists)

    # -- observability (obs/ — the SparkListener analogue) ------------------

    def _obs_enabled(self) -> bool:
        return self.config.obs_level != "off"

    def _obs_event_log(self):
        from matrel_tpu.obs.events import EventLog, resolve_path
        path = resolve_path(self.config.obs_event_log)
        max_bytes = self.config.obs_event_log_max_bytes
        if (self._event_log is None or self._event_log.path != path
                or self._event_log.max_bytes != max_bytes):
            self._event_log = EventLog(path, max_bytes=max_bytes)
        return self._event_log

    def _obs_emit(self, kind: str, record: dict) -> None:
        """The ONE emission funnel for session events AND finished
        spans: JSONL event log when obs is on, flight-recorder ring
        when configured — each independently (flight recording with
        obs off keeps spans in memory only; the ring then holds the
        bare record stamped the way the log would have)."""
        if self._slice_tag is not None and "slice" not in record:
            # fleet attribution (docs/FLEET.md): every event a slice
            # session emits carries its slice id, so history's
            # per-slice roll-up (and top) can tell the slices apart
            # in the shared log. Non-fleet sessions are unchanged.
            record = {**record, "slice": self._slice_tag}
        full = None
        if self._obs_enabled():
            full = self._obs_event_log().emit(kind, record)
        if self._flight is not None:
            if full is None:
                from matrel_tpu.obs.events import SCHEMA_VERSION
                full = {"schema": SCHEMA_VERSION,
                        "ts": round(time.time(), 3), "kind": kind}  # matlint: disable=ML006 record timestamp — mirrors EventLog.emit's stamp for ring-only records
                full.update(record)
            self._flight.add(full)

    # -- answer provenance ledger (obs/provenance.py — tier 4) --------------

    def _prov_capture(self, path: str, key: str, sla: str,
                      rung: int = 0, expr=None, result=None, ent=None,
                      executed=None, plan=None, strategies=None,
                      fleet=None, stale=None, mesh=None,
                      config=None) -> Optional[dict]:
        """One lineage record + ``provenance`` event per served
        answer. Callers guard on ``self._prov is not None`` (the off
        path must not even assemble arguments); capture failures are
        swallowed like every other obs emission — lineage must never
        fail the answer it describes. The record keeps the compile
        config the answer was produced under (SLA + degrade rung), so
        audit replay reconstructs the producing configuration."""
        try:
            cfg = config if config is not None else \
                degrade_lib.apply_rung(self._sla_config(sla), rung)
            summary = self._prov.capture(
                path, key, sla, rung=rung, expr=expr, result=result,
                ent=ent, executed=executed, plan=plan,
                strategies=strategies,
                mesh=mesh if mesh is not None else self.mesh,
                config=cfg, fleet=fleet, stale=stale,
                coeff_epoch=self._coeff_epoch())
            self._obs_emit("provenance", summary)
            return summary
        except Exception:
            log.warning("obs: provenance record dropped",
                        exc_info=True)
            return None

    def _prov_capture_stale(self, e: MatExpr, ent,
                            meta: dict) -> None:
        """Rung-2 stale-serve capture (serve/pipeline.py): recompute
        the structural key (the probe's own walk is gone by now —
        only paid when the ledger is on) and record the staleness
        grant the answer was served under. ``meta`` is the queue
        tuple's ``AdmissionQueue.entry_provenance`` projection."""
        sla = meta.get("sla") or self.config.precision_sla
        parts, _pins, _spans = _plan_key_spans(e)
        key = self._rc_key_prefix(sla) + "|".join(parts)
        stale = {"staleness_ms": float(meta.get("staleness_ms")
                                       or 0.0)}
        if meta.get("tenant"):
            stale["tenant"] = meta["tenant"]
        self._prov_capture("stale", key, sla, ent=ent, stale=stale)

    def why(self, query=None, last: int = 10) -> list:
        """Lineage of recently served answers (obs tier 4,
        docs/OBSERVABILITY.md): the JSON-safe summary dicts of the
        in-memory ledger, newest last — ``python -m matrel_tpu why``
        renders the same records from the event log. ``query`` filters
        by key/key-hash substring or ledger query id, or by the ANSWER
        itself (a BlockMatrix matches by identity). Empty when
        ``config.obs_provenance`` is 0."""
        if self._prov is None:
            return []
        if query is None:
            recs = self._prov.last(last)
        elif isinstance(query, BlockMatrix):
            recs = [r for r in self._prov.records()
                    if r.result is query]
        else:
            recs = self._prov.find(str(query))
        return [r.summary for r in recs]

    def provenance_info(self) -> dict:
        """``plan_cache_info``-style surface for the ledger."""
        if self._prov is None:
            return {"records": 0, "cap": 0, "captured": 0,
                    "chains": 0}
        return self._prov.info()

    # -- flight recorder (obs/trace.py — post-mortem ring) ------------------

    def dump_flight_recorder(self, path: Optional[str] = None,
                             reason: str = "explicit",
                             error: Optional[str] = None
                             ) -> Optional[str]:
        """Write the flight-recorder ring as a JSON artifact and return
        its path (None when the recorder is off). The automatic dump
        sites (VerificationError, compile failure, serve-batch
        failure) route through here too."""
        if self._flight is None:
            return None
        p = (path or self.config.obs_flight_recorder_path
             or trace_lib.DEFAULT_FLIGHT_PATH)
        return self._flight.dump(p, reason, error=error)

    def _flight_auto_dump(self, ex: BaseException,
                          reason: Optional[str] = None) -> None:
        """Best-effort dump on a failure path — a post-mortem artifact
        must never mask (or replace) the original exception."""
        if self._flight is None:
            return
        if reason is None:
            from matrel_tpu.analysis import VerificationError
            reason = ("verification_error"
                      if isinstance(ex, VerificationError)
                      else "compile_failure")
        try:
            p = self.dump_flight_recorder(reason=reason,
                                          error=repr(ex)[:500])
            log.warning("flight recorder dumped to %s (%s)", p, reason)
        except Exception:
            log.warning("flight recorder dump failed", exc_info=True)

    def _emit_query_event(self, e: MatExpr, plan, hit: bool, key: str,
                          execute_ms: float, first_execution: bool,
                          out: BlockMatrix, matmuls=None,
                          rule_hits=None, batch=None,
                          tenant: Optional[str] = None,
                          cache_label: Optional[str] = None) -> None:
        """One event-log record + metrics-registry updates per query run.
        Assembled entirely OUTSIDE jitted code, from data the compile
        path already produced (plan.meta) — the only device sync the obs
        path adds is the one execute-time block in compute().

        ``matmuls``/``rule_hits`` override the plan-level derivations
        for batched (MultiPlan) roots: each root's record carries ITS
        matmul decisions, and rewrite-rule hits are attributed to one
        root only so history's roll-up never double-counts a compile.
        ``batch`` tags records produced by one micro-batched admission
        (``{"size": N, "index": i}``; execute_ms is then the batch
        wall amortised per root).

        ``cache_label`` overrides the hit/miss vocabulary — a
        plan-template hit (serve/mqo.py) records ``"template_hit"``
        with optimize/trace FORCED to 0.0: unlike a plan-cache hit
        (whose record describes the plan that ran), the template
        contract is that steady-state traffic pays ZERO optimize/trace
        this query, and the event is the proof the acceptance test
        reads."""
        from matrel_tpu.obs.metrics import REGISTRY
        meta = plan.meta or {}
        if matmuls is None:
            matmuls = executor_lib.plan_matmul_decisions(plan)
        sql_hash = getattr(e, "_sql_hash", None)
        record = {
            "query_id": f"q{os.getpid()}-{next(_query_seq)}",
            "source": "sql" if sql_hash else "dsl",
            "source_hash": sql_hash
            or hashlib.sha1(key.encode()).hexdigest()[:16],
            "root_kind": e.kind,
            "cache": cache_label or ("hit" if hit else "miss"),
            "optimize_ms": (0.0 if cache_label == "template_hit"
                            else meta.get("optimize_ms")),
            "trace_ms": (0.0 if cache_label == "template_hit"
                         else meta.get("trace_ms")),
            # compile-scoped: a cache hit ran no rewrite rules, so hit
            # records carry {} and history's roll-up counts real
            # optimizer work (optimize_ms/trace_ms DO repeat on hits —
            # they describe the plan, "cache" says no compile ran)
            "rule_hits": (rule_hits if rule_hits is not None
                          else ({} if hit else meta.get("rule_hits",
                                                        {}))),
            "matmuls": matmuls,
            "execute_ms": round(execute_ms, 3),
            "first_execution": first_execution,
            "out_shape": list(out.shape),
            "out_nnz": out.nnz,
            "plan_cache": self.plan_cache_info(),
        }
        if batch is not None:
            record["batch"] = batch
        if tenant:
            # multi-tenant attribution (docs/OVERLOAD.md): absent for
            # untagged queries, so historical records are unchanged
            record["tenant"] = tenant
        if meta.get("fusion"):
            # plan-level fusion roll-up (executor._fusion_meta):
            # regions, member census, est saved dispatches/HBM — the
            # `history --summary` fusion line's feed. Absent with
            # fusion off (the bit-identity obs contract).
            record["fusion"] = meta["fusion"]
        if self._rc_enabled():
            record["result_cache"] = self._result_cache.info()
        import jax
        # backend rides every query record so the drift auditor can
        # calibrate per backend (a CPU ms and a TPU ms must never
        # blend into one ratio)
        record["backend"] = jax.default_backend()
        if self.config.coeff_planner_enable:
            # which coefficient epoch priced this answer's plan — the
            # history cost-model roll-up's feed (absent with the loop
            # off: the bit-identity obs contract, docs/COST_MODEL.md)
            record["coeff_epoch"] = self._coeff_epoch()
        self._obs_emit("query", record)
        if self._replan is not None:
            # feed the re-plan controller AFTER emission: it sees the
            # same record the log does (backend + matmuls included),
            # and its own failure can never drop the query event
            self._replan.observe(record)
        REGISTRY.counter("query.count").inc()
        REGISTRY.counter("plan_cache.hit" if hit
                         else "plan_cache.miss").inc()
        if cache_label == "template_hit":
            REGISTRY.counter("mqo.template_hit").inc()
        REGISTRY.gauge("plan_cache.plans").set(len(self._plan_cache))
        REGISTRY.gauge("plan_cache.hoisted_bytes").set(
            self._plan_cache_bytes)
        REGISTRY.gauge("plan_cache.evicted").set(
            self._plan_cache_evicted)
        REGISTRY.histogram("query.execute_ms").observe(execute_ms)
        if not hit:
            if meta.get("optimize_ms") is not None:
                REGISTRY.histogram("query.optimize_ms").observe(
                    meta["optimize_ms"])
            # compile-scoped like optimize_ms: rules fire once per
            # compile, not per run
            for rule, n in meta.get("rule_hits", {}).items():
                REGISTRY.counter(f"optimizer.rule.{rule}").inc(n)
        for d in matmuls:
            REGISTRY.counter(f"planner.strategy.{d['strategy']}").inc()
        # (max | min, ×) products answered from the leaf's entries, and
        # "mul" joins over an element-sparse leaf that densified it
        if meta.get("semiring"):
            REGISTRY.counter("semiring.fused").inc(len(meta["semiring"]))
        if meta.get("densified_joins"):
            REGISTRY.counter("semiring.densified").inc(
                meta["densified_joins"])

    def _emit_verify_event(self, plan) -> None:
        """One ``verify`` record per observed query run (obs_level on
        AND verify_plans on): the diagnostic codes the compile-time
        verifier produced for this plan — empty codes = verified clean.
        Cache hits re-report the compile-time findings (the record
        describes the plan that ran, "cache" on the query record says
        no new verify happened)."""
        diags = (plan.meta or {}).get("diagnostics")
        if diags is None:
            return        # verifier was off when this plan compiled
        from matrel_tpu.obs.metrics import REGISTRY
        self._obs_emit("verify", {
            "mode": self.config.verify_plans,
            "count": len(diags),
            "errors": sum(1 for d in diags if d["severity"] == "error"),
            "codes": sorted({d["code"] for d in diags}),
        })
        REGISTRY.counter("verify.count").inc()
        if diags:
            REGISTRY.counter("verify.diagnostics").inc(len(diags))

    def verify(self, expr: MatExpr) -> list:
        """Run the static plan verifier (matrel_tpu/analysis/) on this
        expression's OPTIMIZED, strategy-annotated plan and return the
        diagnostic list — regardless of ``config.verify_plans`` (that
        gate controls the compile path; this is the on-demand surface).
        Planning only: nothing is traced, jitted, or executed."""
        from matrel_tpu import analysis
        from matrel_tpu.ir import rules
        from matrel_tpu.parallel import planner
        e = as_expr(expr)
        grid = mesh_lib.mesh_grid_shape(self.mesh)
        opt = planner.annotate_strategies(
            rules.optimize(e, self.config, grid=grid, mesh=self.mesh),
            self.mesh, self.config)
        return analysis.verify_plan(opt, self.mesh, self.config)

    def _emit_rc_hit_event(self, e: MatExpr, key: str,
                           out: BlockMatrix,
                           tenant: Optional[str] = None) -> None:
        """Query record for a WHOLE-query result-cache hit: nothing
        compiled, nothing executed — the record says so (``cache:
        "rc_hit"``, no matmuls, zero execute) and carries the cache
        snapshot the hit came from."""
        from matrel_tpu.obs.metrics import REGISTRY
        sql_hash = getattr(e, "_sql_hash", None)
        self._obs_emit("query", {
            **({"tenant": tenant} if tenant else {}),
            "query_id": f"q{os.getpid()}-{next(_query_seq)}",
            "source": "sql" if sql_hash else "dsl",
            "source_hash": sql_hash
            or hashlib.sha1(key.encode()).hexdigest()[:16],
            "root_kind": e.kind,
            "cache": "rc_hit",
            "optimize_ms": None,
            "trace_ms": None,
            "rule_hits": {},
            "matmuls": [],
            "execute_ms": 0.0,
            "first_execution": False,
            "out_shape": list(out.shape),
            "out_nnz": out.nnz,
            "plan_cache": self.plan_cache_info(),
            "result_cache": self._result_cache.info(),
        })
        REGISTRY.counter("query.count").inc()
        REGISTRY.counter("result_cache.hit").inc()

    def _emit_delta_event(self, record: dict) -> None:
        """One ``delta`` record per register_delta (obs on / flight
        recorder on; no-op otherwise — the default path emits nothing):
        the maintenance summary — entries patched / killed / rekeyed,
        per-rule census, modelled FLOPs saved — the ``history
        --summary`` IVM roll-up's feed. Never fails the register."""
        if not self._obs_enabled() and self._flight is None:
            return
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            rec = dict(record)
            if self._rc_enabled():
                rec["result_cache"] = self._result_cache.info()
            self._obs_emit("delta", rec)
            REGISTRY.counter("ivm.registered").inc()
            REGISTRY.counter("ivm.patched").inc(
                record.get("patched", 0))
            REGISTRY.counter("ivm.killed").inc(record.get("killed", 0))
        except Exception:
            log.warning("obs: delta event dropped", exc_info=True)

    def _emit_spill_event(self, record: dict) -> None:
        """One ``spill`` record per tier move (demote / promote /
        thaw — serve/spill.py's emit hook) and per save_state/restore
        (op ``save_state``/``restore``): the measured transfer legs
        the drift auditor calibrates ``spill:<leg>`` rows from and
        the ``history --summary`` spill/restart roll-up's feed. Obs
        on / flight recorder on; no-op otherwise — the default path
        emits nothing. Never fails the cache operation."""
        if not self._obs_enabled() and self._flight is None:
            return
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            self._obs_emit("spill", dict(record))
            REGISTRY.counter(
                f"spill.{record.get('op') or 'op'}").inc()
        except Exception:
            log.warning("obs: spill event dropped", exc_info=True)

    def _emit_serve_event(self, record: dict) -> None:
        """One ``serve`` record per micro-batched admission (obs on
        only): batch size, queue-wait per query, result-cache state,
        in-flight depth — the roll-up ``history --summary`` turns into
        QPS / hit ratio / queue-latency percentiles."""
        from matrel_tpu.obs.metrics import REGISTRY
        record = dict(record)
        record["result_cache"] = self._result_cache.info()
        self._obs_emit("serve", record)
        REGISTRY.counter("serve.batches").inc()
        REGISTRY.counter("serve.queries").inc(
            record.get("batch_size", 0))
        for w in record.get("queue_wait_ms") or ():
            REGISTRY.histogram("serve.queue_wait_ms").observe(w)
        REGISTRY.gauge("result_cache.entries").set(
            record["result_cache"]["entries"])
        REGISTRY.gauge("result_cache.bytes").set(
            record["result_cache"]["bytes"])

    def _emit_alert_event(self, record: dict) -> None:
        """One ``alert`` record per SLO alert TRANSITION (obs/slo.py
        fire/clear edges — never steady state): tenant, objective,
        burn rates, attainment. Lands in the event log when obs is on
        AND in the flight-recorder ring whenever the ring exists —
        REGARDLESS of ``obs_level`` (the _obs_emit funnel's existing
        split): an alert edge is exactly the record a post-mortem
        needs. Never fails the query/outcome that triggered it."""
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            self._obs_emit("alert", record)
            REGISTRY.counter(
                "slo.alerts.fired" if record.get("state") == "firing"
                else "slo.alerts.cleared").inc()
            REGISTRY.gauge("slo.alerts.active").set(
                record.get("active", 0))
        except Exception:   # the never-fail obs contract
            log.warning("obs: alert event dropped", exc_info=True)

    def _emit_overload_event(self, record: dict) -> None:
        """One ``overload`` record per admission cycle while the
        control plane is active (serve/pipeline.py assembles it:
        rung, tenant depths/waits, shed/purge/stale deltas, breaker
        state) — the feed for ``history --summary``'s overload
        roll-up. Never fails a query."""
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            self._obs_emit("overload", record)
            REGISTRY.gauge("overload.rung").set(
                record.get("rung", 0))
        except Exception:
            log.warning("obs: overload event dropped", exc_info=True)

    def _arbitrated_run(self, plan, bindings=None):
        """Dispatch one compiled program under the fleet's execution
        arbitration (see ``_exec_lock``): dispatch-to-COMPLETION is
        serialized across the sessions sharing the lock, because an
        async dispatch would leave the program's collectives in
        flight when the lock dropped — exactly the overlap the lock
        exists to prevent. Cache hits, planning and admission never
        come here, so the fleet's host-side parallelism survives;
        only device programs serialize. Without a lock (every
        non-fleet session) this IS ``plan.run()``. ``bindings`` rebinds
        dense leaves by uid (plan-template hits — serve/mqo.py)."""
        # sanctioned dispatch point (utils/lockdep.py): with the
        # sanitizer on, any lock held HERE that is not declared
        # dispatch_ok (the fleet exec arbitration is, by design) is a
        # HeldAcrossDispatch diagnostic — the PR 8 drain-wedge class
        # caught at runtime. One flag check when off.
        lockdep.note_dispatch("session.dispatch")
        self._last_plan = plan
        with trace_lib.span("dispatch",
                            executors=plan.meta.get("executors"),
                            mesh=plan.meta.get("mesh"),
                            hbm_plan_bytes=plan.meta.get(
                                "hbm_plan_bytes")) as sp:
            if sp.live:
                # the SpMV plan each coo_leaf product of this program
                # runs on: built and uploaded once, answered here
                for name in ("spmm", "sampled", "semiring", "mmchain"):
                    for rec in plan.meta.get(name, ()):
                        with trace_lib.span(name + ".plan", hit=True,
                                            **rec):
                            pass
            if self._exec_lock is None:
                return plan.run(bindings=bindings)
            with self._exec_lock:
                out = plan.run(bindings=bindings)
                for o in (out if isinstance(out, (list, tuple))
                          else (out,)):
                    o.data.block_until_ready()
                return out

    def _emit_placement_event(self, record: dict) -> None:
        """One ``placement`` record per fleet-routed submission
        (serve/fleet.py assembles it: mode, routed target, directory
        outcome, coefficient provenance, the two cost estimates) —
        the feed for ``history --summary``'s fleet roll-up. Never
        fails a query."""
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            self._obs_emit("placement", record)
            REGISTRY.counter(
                f"fleet.placed.{record.get('routed', '?')}").inc()
        except Exception:
            log.warning("obs: placement event dropped", exc_info=True)

    def _emit_fleet_event(self, record: dict) -> None:
        """One ``fleet`` record per fleet lifecycle event (slice
        kill/failover, hot-entry migration, priced-out migration) —
        carried with the fleet snapshot so offline replay can
        reconstruct the fleet's state transitions."""
        from matrel_tpu.obs.metrics import REGISTRY
        try:
            rec = dict(record)
            if self._fleet is not None:
                rec["fleet"] = {
                    "placed": dict(self._fleet.placed),
                    "failovers": self._fleet.failovers,
                    "migrations": self._fleet.migrations,
                }
            self._obs_emit("fleet", rec)
            REGISTRY.counter(
                f"fleet.event.{record.get('event', '?')}").inc()
        except Exception:
            log.warning("obs: fleet event dropped", exc_info=True)

    def _run_observed(self, e: MatExpr, plan, hit: bool, key: str,
                      tenant: Optional[str] = None, bindings=None,
                      cache_label: Optional[str] = None) -> BlockMatrix:
        """Execute one compiled plan with the obs timing/emission
        wrapper (the obs-on half of compute()). ``bindings``/
        ``cache_label`` are the plan-template hit channel
        (serve/mqo.py): fresh leaves rebound into the cached program,
        and the query record saying so (``cache: "template_hit"``)."""
        first = not getattr(plan, "_obs_executed", False)
        # timed(): the one timing mechanism — the duration lands in the
        # query record AND (tracer active here) as an "execute" span;
        # never cold: every observed query comes this way
        with trace_lib.timed("query.execute",
                             cache=cache_label
                             or ("hit" if hit else "miss")) as sp:
            out = self._arbitrated_run(plan, bindings=bindings)
            out.data.block_until_ready()
        execute_ms = sp.dur_ms
        plan._obs_executed = True
        try:
            self._emit_query_event(e, plan, hit, key, execute_ms, first,
                                   out, tenant=tenant,
                                   cache_label=cache_label)
            self._emit_verify_event(plan)
        except Exception:   # the result is already computed — keep the
            # never-fail-a-query contract (obs/events.py) even when
            # record ASSEMBLY breaks, not just the file write
            log.warning("obs: query event dropped", exc_info=True)
        return out

    def compute(self, expr: MatExpr,
                precision: Optional[str] = None,
                deadline_ms: Optional[float] = None,
                tenant: Optional[str] = None) -> BlockMatrix:
        """Execute one query. ``precision`` is the per-query accuracy
        SLA ("exact"/"high"/"fast"/explicit dtype — docs/PRECISION.md);
        None defers to a SQL PRECISION clause, then
        ``config.precision_sla``. ``deadline_ms`` is the per-query
        deadline (None defers to ``config.deadline_ms``; expiry raises
        the typed ``DeadlineExceeded`` — docs/RESILIENCE.md).
        ``tenant`` tags the query's obs records for the multi-tenant
        roll-up (admission fairness itself lives in the async
        ``submit`` pipeline — docs/OVERLOAD.md)."""
        e = as_expr(expr)
        sla = self._resolve_sla(precision, e)
        # resilience gate (retry/deadline/fault-injection): None for
        # the default config + no per-call deadline — the resilient
        # path is never entered and costs nothing
        pol = RetryPolicy.from_config(self.config, deadline_ms)
        rc = self._rc_enabled()
        if self._breakers is None:
            return self._compute_dispatch(e, sla, pol, rc, tenant)
        # circuit breakers (resilience/breaker.py): an OPEN plan class
        # fails fast typed; terminal outcomes feed the class's health
        bclass = self._breakers.plan_class(e)
        self._breakers.admit(bclass)
        try:
            out = self._compute_dispatch(e, sla, pol, rc, tenant)
        except Exception as ex:
            self._breakers.record(
                bclass,
                False if breaker_lib.counts_as_failure(ex) else None)
            raise
        self._breakers.record(bclass, True)
        return out

    def _compute_dispatch(self, e: MatExpr, sla: str,
                          pol: Optional[RetryPolicy], rc: bool,
                          tenant: Optional[str]) -> BlockMatrix:
        """compute() behind the breaker gate: the resilient / fast /
        observed three-way the engine has always had."""
        if pol is not None:
            return self._compute_resilient(e, rc, sla, pol,
                                           tenant=tenant)
        fast = (not rc and not self._obs_enabled()
                and self._tracer is None)
        # the entry span: executor compile phases and every span below
        # parent-link into this query's trail. One span set for the
        # three ways through compute; with no tracer and no profiler
        # session it is the no-op singleton
        with trace_lib.entry("compute", self._tracer, root_kind=e.kind,
                             path="fast" if fast else "observed"):
            if fast:
                # the production path: zero event assembly, zero extra
                # device syncs, zero span objects, zero cache-key walks
                # beyond the lookup's own (the obs_level="off" /
                # result_cache_max_bytes=0 / flight-recorder-off
                # contract nine of the benchmark's cells rely on)
                plan, _hit, _key, bindings = self._plan_lookup(e, sla)
                return self._arbitrated_run(plan, bindings=bindings)
            return self._compute_observed(e, rc, sla, tenant=tenant)

    def _compute_observed(self, e: MatExpr, rc: bool,
                          sla: Optional[str] = None,
                          rung: int = 0,
                          tenant: Optional[str] = None) -> BlockMatrix:
        """compute() behind the fast-path gate: result-cache admission,
        compile, execute — each scoped by a tracing span. ``rung`` is
        the resilient path's degradation-ladder step (0 = none)."""
        sla = sla if sla is not None else self.config.precision_sla
        key = pins = None
        orig = e
        if rc:
            with trace_lib.span("rc.probe") as sp:
                ent, key, pins, e = self._rc_admit(
                    e, self._rc_key_prefix(sla))
                self._last_rc = _rc_answer(e, ent is not None)
                sp.set(hit=ent is not None,
                       views_hit=self._last_rc["views_hit"],
                       table_pass=self._last_rc["table_pass"])
            if ent is not None:
                # repeated query: answered from the materialized-result
                # cache — no optimize, no trace, no device work
                if self._obs_enabled():
                    try:
                        self._emit_rc_hit_event(e, key, ent.result,
                                                tenant=tenant)
                    except Exception:
                        log.warning("obs: query event dropped",
                                    exc_info=True)
                if self._prov is not None:
                    self._prov_capture("rc_hit", key, sla, rung=rung,
                                       ent=ent)
                return ent.result
        plan, hit, pkey, bindings = self._plan_lookup(e, sla, rung)
        cache_label = "template_hit" if bindings is not None else None
        # fault site "execute": the host-side dispatch point — the main
        # retryable site (per attempt, unlike the trace-time sites)
        faults_lib.check("execute", self.config)
        if self._obs_enabled():
            out = self._run_observed(e, plan, hit, pkey, tenant=tenant,
                                     bindings=bindings,
                                     cache_label=cache_label)
        else:
            # profiler / flight-recorder tiers: the ``dispatch`` span
            # inside marks DISPATCH (JAX async — deliberately no added
            # sync; always-cheap)
            out = self._arbitrated_run(plan, bindings=bindings)
        summary = None
        if self._prov is not None:
            # capture BEFORE the cache insert so the new CacheEntry's
            # stamp can carry this record's query id (the ancestry
            # link `why` follows from a later hit back to its producer)
            summary = self._prov_capture(
                "execute", key if key is not None else pkey, sla,
                rung=rung, expr=orig, result=out, executed=e,
                plan=plan)
        if rc:
            self._rc_insert(key, pins, e, out, orig=orig,
                            prec=_prec_prefix(sla), plan=plan,
                            prov=summary)
        return out

    # -- resilient execution (matrel_tpu/resilience/) ----------------------

    def _compute_resilient(self, e: MatExpr, rc: bool, sla: str,
                           pol: RetryPolicy,
                           should_abort=None,
                           tenant: Optional[str] = None) -> BlockMatrix:
        """The attempt loop: run the query; on a TRANSIENT failure
        (errors.classify) retry with backoff, climbing one rung of the
        plan-degradation ladder per retry (resilience/degrade.py) —
        rung 4 additionally bypasses the result cache. Deterministic
        failures, exhausted attempts, and expired deadlines propagate
        typed. Cancellation (``should_abort``) is honored between
        attempts — a running XLA dispatch is never interrupted."""
        deadline = pol.deadline()
        attempt = 0
        rung = 0
        while True:
            deadline.raise_if_expired()
            try:
                with trace_lib.entry("compute", self._tracer,
                                     root_kind=e.kind, path="resilient",
                                     attempt=attempt, rung=rung):
                    out = self._compute_observed(
                        e, rc and rung < degrade_lib.RC_BYPASS_RUNG,
                        sla, rung=rung, tenant=tenant)
                # deadline holds on SUCCESS too: a result delivered
                # past the SLA raises typed, matching submit()'s
                # late-batch semantics (one meaning per knob)
                deadline.raise_if_expired()
                return out
            except Exception as ex:
                self._emit_fault_event(ex, scope="query")
                if not pol.should_retry(ex, attempt):
                    raise
                attempt += 1
                rung, escalated = degrade_lib.next_rung(rung)
                self._emit_retry_event(ex, attempt, rung,
                                       scope="query")
                if escalated:
                    self._emit_degrade_event(rung, ex, scope="query")
                pol.backoff_sleep(attempt, deadline,
                                  should_abort=should_abort)

    def _emit_fault_event(self, ex: BaseException, scope: str) -> None:
        """One ``fault`` record per failure the resilient path caught
        (obs on / flight recorder on; no-op otherwise). Injected
        faults carry their site/kind so the chaos drill and history
        roll-up can attribute them."""
        rec = {"scope": scope, "error": type(ex).__name__,
               "classification": rerrors.classify(ex),
               "message": str(ex)[:200]}
        if isinstance(ex, rerrors.InjectedFault):
            rec["site"] = ex.site
            rec["injected"] = True
        try:
            self._obs_emit("fault", rec)
        except Exception:
            log.warning("obs: fault event dropped", exc_info=True)

    def _emit_retry_event(self, ex: BaseException, attempt: int,
                          rung: int, scope: str) -> None:
        try:
            self._obs_emit("retry", {
                "scope": scope, "attempt": attempt, "rung": rung,
                "rung_label": degrade_lib.rung_label(rung),
                "error": type(ex).__name__})
        except Exception:
            log.warning("obs: retry event dropped", exc_info=True)

    def _emit_degrade_event(self, rung: int, ex: BaseException,
                            scope: str) -> None:
        try:
            self._obs_emit("degrade", {
                "scope": scope, "rung": rung,
                "rung_label": degrade_lib.rung_label(rung),
                "cause": type(ex).__name__})
        except Exception:
            log.warning("obs: degrade event dropped", exc_info=True)

    # alias: the reference's Dataset actions read as "run the query"
    run = compute

    # -- micro-batched admission + async pipeline (serve/) -----------------

    def run_many(self, exprs, precision: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 tenant: Optional[str] = None,
                 _queue_wait_ms=None,
                 _inflight_depth: int = 0,
                 _tenants=None,
                 _brownout_rung: Optional[int] = None
                 ) -> List[BlockMatrix]:
        """Execute several queries as ONE micro-batched admission: the
        batch compiles into a single MultiPlan (one fusion and CSE
        domain, shared leaf transfers — duplicate roots dedupe on their
        structural key) that participates in the session plan cache, so
        a recurring batch recompiles nothing. With the result cache on,
        whole-query hits never reach the batch at all and interior hits
        enter planning as already-laid-out leaves. Results come back in
        input order.

        ``precision`` is the batch-level accuracy SLA — ONE MultiPlan
        means one planning config, so the whole batch shares it (the
        serve pipeline groups mixed-SLA submissions into same-SLA
        batches before calling here).

        ``deadline_ms`` is the BATCH deadline (None defers to
        ``config.deadline_ms``): expiry between retry attempts raises
        the typed ``DeadlineExceeded`` for the whole batch.

        ``tenant`` tags the whole batch for the multi-tenant obs
        roll-up (the serve pipeline instead passes per-query
        ``_tenants``).

        The underscore parameters are the serve pipeline's channel for
        queue-wait/in-flight/tenant/brownout observability; direct
        callers leave them alone."""
        es = [as_expr(x) for x in exprs]
        if not es:
            return []
        if _tenants is None and tenant:
            _tenants = [tenant] * len(es)
        sla = (normalize_sla(precision) if precision is not None
               else self.config.precision_sla)
        pol = RetryPolicy.from_config(self.config, deadline_ms)
        if pol is not None:
            return self._run_many_resilient(es, sla, pol,
                                            _queue_wait_ms,
                                            _inflight_depth,
                                            _tenants=_tenants,
                                            _brownout_rung=_brownout_rung)
        rc = self._rc_enabled()
        obs = self._obs_enabled()
        with trace_lib.entry("serve.batch", self._tracer,
                             size=len(es)) as sp_batch:
            return self._run_many_observed(es, rc, obs, sp_batch,
                                           _queue_wait_ms,
                                           _inflight_depth, sla,
                                           _tenants=_tenants,
                                           _brownout_rung=_brownout_rung)

    def _run_many_resilient(self, es, sla: str, pol: RetryPolicy,
                            _queue_wait_ms, _inflight_depth,
                            should_abort=None, _tenants=None,
                            _brownout_rung: Optional[int] = None
                            ) -> List[BlockMatrix]:
        """``_compute_resilient``'s batch twin: the whole MultiPlan
        retries as one unit, climbing the same ladder (poison-query
        ISOLATION is the serve worker's bisection, not this loop —
        a direct run_many call is one caller asking for one batch)."""
        deadline = pol.deadline()
        attempt = 0
        rung = 0
        while True:
            deadline.raise_if_expired(context="batch")
            rc = (self._rc_enabled()
                  and rung < degrade_lib.RC_BYPASS_RUNG)
            obs = self._obs_enabled()
            try:
                with trace_lib.entry("serve.batch", self._tracer,
                                     size=len(es), attempt=attempt,
                                     rung=rung) as sp_batch:
                    outs = self._run_many_observed(
                        es, rc, obs, sp_batch, _queue_wait_ms,
                        _inflight_depth, sla, rung=rung,
                        _tenants=_tenants,
                        _brownout_rung=_brownout_rung)
                # SLA semantics match _compute_resilient/submit: a
                # batch finishing past its deadline raises typed
                deadline.raise_if_expired(context="batch")
                return outs
            except Exception as ex:
                self._emit_fault_event(ex, scope="batch")
                if not pol.should_retry(ex, attempt):
                    raise
                attempt += 1
                rung, escalated = degrade_lib.next_rung(rung)
                self._emit_retry_event(ex, attempt, rung,
                                       scope="batch")
                if escalated:
                    self._emit_degrade_event(rung, ex, scope="batch")
                pol.backoff_sleep(attempt, deadline,
                                  should_abort=should_abort)

    def _run_many_observed(self, es, rc, obs, sp_batch, _queue_wait_ms,
                           _inflight_depth,
                           sla: Optional[str] = None,
                           rung: int = 0, _tenants=None,
                           _brownout_rung: Optional[int] = None
                           ) -> List[BlockMatrix]:
        sla = sla if sla is not None else self.config.precision_sla
        if _queue_wait_ms is not None:
            # per-query admission wait, readable on the span with obs
            # off (a profiler session, the flight recorder)
            sp_batch.set(queue_wait_ms=_queue_wait_ms)

        def _tenant_of(i):
            return (_tenants[i] if _tenants is not None
                    and i < len(_tenants) else None)
        results: dict = {}
        rc_meta: dict = {}
        pend: list = []
        for i, e in enumerate(es):
            orig = e
            if rc:
                with trace_lib.span("rc.probe", index=i) as sp:
                    ent, key, pins, e = self._rc_admit(
                        e, self._rc_key_prefix(sla))
                    sp.set(hit=ent is not None)
                if ent is not None:
                    results[i] = ent.result
                    if obs:
                        try:
                            self._emit_rc_hit_event(
                                e, key, ent.result,
                                tenant=_tenant_of(i))
                        except Exception:
                            log.warning("obs: query event dropped",
                                        exc_info=True)
                    if self._prov is not None:
                        self._prov_capture("rc_hit", key, sla,
                                           rung=rung, ent=ent)
                    continue
                rc_meta[i] = (key, pins, orig)
            pend.append((i, e))
        execute_ms = 0.0
        plan_hit = None
        cse_hoisted = 0
        tpl_hit = False
        if pend:
            if self._cse_on() and len(pend) > 1:
                # cross-query CSE (serve/mqo.py): shared interiors of
                # the batch compute once; consumers re-enter planning
                # with cse-stamped leaves
                pend, cse_hoisted = self._cse_hoist_batch(pend, sla,
                                                          rung, rc)
            plan, plan_hit, keys, pos, bindings = \
                self._plan_lookup_multi([e for _, e in pend], sla, rung)
            tpl_hit = bindings is not None
            # fault site "execute" — per batch attempt (host side)
            faults_lib.check("execute", self.config)
            # the batch's execute span: under obs the sync happens
            # INSIDE it (dur = device wall); flight-recorder-only runs
            # mark dispatch without adding a sync
            with trace_lib.span("serve.execute",
                                executed=len(pend)) as sp_ex:
                outs = self._arbitrated_run(plan, bindings=bindings)
                if obs:
                    for o in outs:
                        o.data.block_until_ready()
            if obs:
                execute_ms = sp_ex.dur_ms or 0.0
            first = not getattr(plan, "_obs_executed", False)
            plan._obs_executed = True
            for j, ((i, e), k) in enumerate(zip(pend, keys)):
                out = outs[pos[k]]
                results[i] = out
                summary = None
                if self._prov is not None:
                    if rc:
                        p_key, _p, p_orig = rc_meta[i]
                    else:
                        p_key, p_orig = k, e
                    summary = self._prov_capture(
                        "execute", p_key, sla, rung=rung,
                        expr=p_orig, result=out, executed=e,
                        plan=plan,
                        strategies=executor_lib.multiplan_root_decisions(
                            plan)[pos[k]])
                if rc:
                    key, pins, orig = rc_meta[i]
                    self._rc_insert(key, pins, e, out, orig=orig,
                                    prec=_prec_prefix(sla), plan=plan,
                                    prov=summary)
                if obs:
                    try:
                        per_root = executor_lib.multiplan_root_decisions(
                            plan)
                        self._emit_query_event(
                            e, plan, bool(plan_hit), k,
                            execute_ms / max(len(pend), 1), first, out,
                            matmuls=per_root[pos[k]],
                            # one root carries the batch's compile-time
                            # rule hits; the rest {} — the roll-up sums
                            rule_hits=({} if (j > 0 or plan_hit)
                                       else (plan.meta or {}).get(
                                           "rule_hits", {})),
                            batch={"size": len(es), "index": i},
                            tenant=_tenant_of(i),
                            cache_label=("template_hit" if tpl_hit
                                         else None))
                    except Exception:
                        log.warning("obs: query event dropped",
                                    exc_info=True)
            if obs:
                try:
                    self._emit_verify_event(plan)
                except Exception:
                    log.warning("obs: verify event dropped",
                                exc_info=True)
        if obs:
            try:
                record = {
                    "batch_size": len(es),
                    "executed": len(pend),
                    "rc_hits": len(es) - len(pend),
                    "plan_cache_hit": plan_hit,
                    "queue_wait_ms": _queue_wait_ms,
                    "inflight_depth": _inflight_depth,
                    "execute_ms": round(execute_ms, 3),
                    "wall_ms": round(sp_batch.elapsed_ms() or 0.0, 3),
                }
                if _tenants is not None:
                    # per-tenant batch census (docs/OVERLOAD.md):
                    # absent for untagged batches — historical records
                    # unchanged
                    census: dict = {}
                    for t in _tenants:
                        key_t = t or ""
                        census[key_t] = census.get(key_t, 0) + 1
                    record["tenants"] = census
                if _brownout_rung:
                    record["brownout_rung"] = _brownout_rung
                if self._cse_on():
                    # MQO deltas (docs/OBSERVABILITY.md): absent with
                    # cse off — historical serve records unchanged
                    record["cse_hoisted"] = cse_hoisted
                    record["template_hits"] = (len(pend) if tpl_hit
                                               else 0)
                self._emit_serve_event(record)
            except Exception:
                log.warning("obs: serve event dropped", exc_info=True)
        return [results[i] for i in range(len(es))]

    def submit(self, expr, precision: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               staleness_ms: Optional[float] = None):
        """Asynchronous query admission: returns a
        ``concurrent.futures.Future`` resolving to the BlockMatrix.
        Concurrent submissions coalesce into micro-batches
        (``config.serve_max_batch``) and JAX's async dispatch overlaps
        device execution with host planning of the next batch, bounded
        by ``config.serve_max_inflight`` (serve/pipeline.py).
        ``precision`` rides each submission: the admission worker only
        coalesces SAME-SLA queries into one MultiPlan, so a "fast"
        neighbour can never change an "exact" query's numerics.

        ``deadline_ms`` rides each submission too (None defers to
        ``config.deadline_ms``): a future whose deadline expires while
        queued — or whose batch finishes past it — resolves with the
        typed ``DeadlineExceeded``. Submitting into a CLOSED pipeline
        raises the typed ``PipelineClosed``; a full bounded queue
        (per-tenant ``config.serve_tenant_queue_max`` quota first,
        then the global ``config.serve_queue_max``) raises the typed
        ``AdmissionShed``.

        ``tenant`` names the submitting tenant for weighted-fair
        admission (``config.serve_tenant_weights`` —
        docs/OVERLOAD.md); ``staleness_ms`` declares how old a STALE
        result-cache answer this query tolerates (consumed only at
        brownout rung >= 2; None/0 = never served stale).

        With ``config.fleet_slices >= 1`` the submission routes
        through the multi-slice serving fleet (serve/fleet.py;
        docs/FLEET.md): placement decides slice-local vs spanning
        execution, the global directory answers repeats from ANY
        slice's cache, and a dead slice's queue fails over. The
        default (0) runs the historical single-controller pipeline
        bit-identically."""
        e = as_expr(expr)
        if deadline_ms is None and self.config.deadline_ms > 0:
            deadline_ms = self.config.deadline_ms
        sla = self._resolve_sla(precision, e)
        if self.config.fleet_slices >= 1:
            return self._ensure_fleet().submit(
                e, sla, deadline_ms=deadline_ms, tenant=tenant,
                staleness_ms=staleness_ms)
        return self._submit_pipeline(e, sla, deadline_ms=deadline_ms,
                                     tenant=tenant,
                                     staleness_ms=staleness_ms)

    def _ensure_serve(self):
        """This session's (lazily built) admission pipeline."""
        if self._serve is None:
            from matrel_tpu.serve.pipeline import ServePipeline
            # under the lock: two concurrent FIRST submissions must not
            # each build a pipeline — the loser's would be orphaned
            # (invisible to serve_drain/close, its queue never drained)
            with self._compile_lock:
                if self._serve is None:
                    self._serve = ServePipeline(self)
        return self._serve

    def _ensure_fleet(self):
        if self._fleet is None:
            from matrel_tpu.serve.fleet import FleetController
            with self._compile_lock:     # the _ensure_serve discipline
                if self._fleet is None:
                    self._fleet = FleetController(self)
        return self._fleet

    def _submit_pipeline(self, e: MatExpr, sla: str,
                         deadline_ms: Optional[float] = None,
                         tenant: Optional[str] = None,
                         staleness_ms: Optional[float] = None):
        """The single-controller admission path — submit()'s historical
        body, also the fleet's SPAN executor (a span-placed query is
        one program over the full mesh, i.e. exactly this pipeline)."""
        return self._ensure_serve().submit(e, sla,
                                           deadline_ms=deadline_ms,
                                           tenant=tenant,
                                           staleness_ms=staleness_ms)

    def fleet_info(self) -> Optional[dict]:
        """Fleet observability snapshot (None when the fleet is off or
        not yet built): per-slice state, directory counters, placement
        census, migration/failover counts (docs/FLEET.md)."""
        return self._fleet.info() if self._fleet is not None else None

    def serve_drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted query has been dispatched and
        every in-flight batch has materialised. ``timeout`` (seconds)
        bounds the wait: a wedged admission worker raises the typed
        ``DrainTimeout`` instead of hanging the caller forever; the
        queue state is untouched, so a later drain can still finish.
        ONE absolute deadline spans the fleet AND the parent pipeline
        — the documented bound holds however many waits run."""
        t_end = (None if timeout is None
                 else retry_lib.now() + timeout)
        if self._fleet is not None:
            self._fleet.drain(timeout=_deadline_left(t_end))
        if self._serve is not None:
            self._serve.drain(timeout=_deadline_left(t_end))

    def serve_close(self, timeout: Optional[float] = None) -> None:
        """Drain then stop the admission worker. A later ``submit``
        raises the typed ``PipelineClosed`` (never enqueues into a
        dead worker). Also stops the live metrics exporter when one
        is running — "done serving" frees the port deterministically
        (a GC finalizer covers sessions that are simply dropped).
        Like :meth:`serve_drain`, ``timeout`` is ONE shared absolute
        deadline across the fleet and parent waits."""
        t_end = (None if timeout is None
                 else retry_lib.now() + timeout)
        # teardown must not stop at the first typed failure: a wedged
        # slice's DrainTimeout would otherwise leave the parent
        # pipeline's worker running and the metrics port bound until
        # GC — the exporter EADDRINUSE class. Close everything, then
        # let the first failure propagate.
        try:
            if self._fleet is not None:
                self._fleet.close(timeout=_deadline_left(t_end))
        finally:
            try:
                if self._serve is not None:
                    self._serve.close(timeout=_deadline_left(t_end))
            finally:
                if self._exporter is not None:
                    self._exporter.stop()

    def explain(self, expr: MatExpr, physical: bool = True,
                analyze: bool = False,
                precision: Optional[str] = None) -> str:
        """Logical, optimized AND physical plan text. With ``physical``
        (default) the expression is compiled (cached — a following
        compute() reuses the plan), so the optimized section carries
        the chosen matmul strategies / join schemes and a collectives
        summary — the reference's EXPLAIN shows its physical operators
        the same way. ``physical=False`` skips compilation.

        ``analyze=True`` (or ``config.obs_level == "analyze"``) RUNS
        the plan once per-op (eager, each node synced and wall-clocked)
        plus once fused, and appends the measured tree — per-op
        milliseconds next to each matmul's chosen strategy and the
        model's estimated ICI bytes (obs/analyze.py; the reference's
        Spark-UI stage-timeline-next-to-plan view). Off-hot-path by
        construction: nothing is measured unless asked."""
        e = as_expr(expr)
        if not physical:
            if analyze:
                # contradictory ask: measuring requires a compiled plan
                # (the config-level "analyze" default just degrades)
                raise ValueError(
                    "explain(analyze=True) requires physical=True")
            return e.explain(self.config)
        from matrel_tpu.ir.expr import pretty
        head = "== Logical plan ==\n" + pretty(e)
        try:
            plan = self.compile(e, precision=precision)
            text = head + "\n" + plan.explain()
        except Exception as ex:  # EXPLAIN must not fail on exotic plans
            # fall back to the PRE-COMPUTED logical text only: when the
            # failure happened inside optimize(), e.explain() would
            # re-run the optimizer and re-raise the same exception
            return head + f"\n== Physical plan unavailable: {ex!r} =="
        # static-verifier findings next to the physical plan they
        # describe (the reference's EXPLAIN shows analyzer output the
        # same way). Compile-time diagnostics are reused when the
        # verify_plans gate already produced them; otherwise EXPLAIN
        # runs the passes itself — it is off the hot path by contract.
        try:
            from matrel_tpu import analysis
            diags = (plan.meta or {}).get("diagnostics")
            if diags is None:
                # the PLAN's config, not the session's: a per-query
                # precision SLA must be verified against the SLA the
                # plan was actually compiled under (MV108)
                diags = analysis.verify_plan(plan.optimized, self.mesh,
                                             plan.config)
            else:
                diags = [analysis.Diagnostic(**d) for d in diags]
            text += "\n== Verifier ==\n" + analysis.render(diags)
        except Exception as ex:     # verification must not fail EXPLAIN
            text += f"\n== Verifier unavailable: {ex!r} =="
        if analyze or self.config.obs_level == "analyze":
            from matrel_tpu.obs import analyze as analyze_mod
            try:
                per_op, _eager = analyze_mod.measure_per_op(plan)
                fused = analyze_mod.measure_fused(plan)
                text += "\n" + analyze_mod.render(plan, per_op, fused)
                if self._obs_enabled():
                    # the drift auditor's highest-fidelity feed: the
                    # measured per-op tree joined to the SAME plan's
                    # decision records, one `analyze` event per run
                    try:
                        self._obs_emit("analyze",
                                       analyze_mod.analyze_record(
                                           plan, per_op, fused))
                    except Exception:
                        log.warning("obs: analyze event dropped",
                                    exc_info=True)
            except Exception as ex:   # analysis must not fail EXPLAIN
                text += f"\n== Analysis unavailable: {ex!r} =="
        return text

    def sql(self, query: str) -> MatExpr:
        """SQL-ish entry point over registered matrix tables (the reference's
        SQL surface, SURVEY.md §2 'SQL entry point'). See sql.py."""
        from matrel_tpu.sql import parse_sql
        with trace_lib.entry("sql", self._tracer, chars=len(query)):
            return parse_sql(query, self)

    def explain_sql(self, query: str, analyze: bool = False) -> str:
        """Optimized-plan text for a SQL query — the EXPLAIN analogue
        (strategies, join schemes and value-join kinds included).
        ``analyze=True`` appends the measured per-op tree (EXPLAIN
        ANALYZE)."""
        return self.explain(self.sql(query), analyze=analyze)


def _prec_prefix(sla: str) -> str:
    """Cache-key prefix isolating precision tiers (the axisw-prefix
    idiom): plan-cache AND result-cache keys for a non-default SLA
    never collide with default-SLA keys or with each other, so a
    ``"fast"`` plan/result can never answer an ``"exact"`` query.
    "default" keeps the historical key format (empty prefix)."""
    return "" if sla == "default" else f"prec:{sla}|"


def _plan_bytes(plan: executor_lib.CompiledPlan) -> int:
    """Device bytes a cached plan pins beyond its leaf matrices: the
    hoisted constant payloads shipped as call-time args. Computed from
    shape/dtype — jax 0.9 TypedNdArray consts lack .nbytes."""
    total = 0
    for a in plan.extra_args:
        try:
            total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        except (AttributeError, TypeError):
            pass
    return total


def _fn_token(fn, pins: list, seen: frozenset = frozenset()) -> str:
    """Cache-key token for a callable attr. Distinct predicates/merges MUST
    key differently — dropping them (pre-round-3 behaviour) made the second
    of two same-shaped queries silently return the first's cached result.
    Preference order: an attached source key (sql.py tags its compiled
    lambdas, so identical query text still HITS the cache), then a
    code+closure+globals+defaults fingerprint (stable across re-created
    lambdas with the same behaviour), then id(). EVERY object keyed by
    id() is appended to ``pins``, which the session attaches to the
    cached plan: a pinned object's address cannot be garbage-collected
    and reused, so an id-based token can never falsely hit."""
    key = getattr(fn, "__matrel_key__", None)
    if key is not None:
        return f"fnkey:{key}"
    code = getattr(fn, "__code__", None)
    if code is None:
        pins.append(fn)
        return f"fnid:{id(fn)}"
    if id(fn) in seen:
        # recursive reference (fn reachable from its own globals or
        # closure) — key the back-edge by pinned id to terminate
        pins.append(fn)
        return f"fnrec:{id(fn)}"
    seen = seen | {id(fn)}
    parts = [code.co_code.hex(), repr(code.co_consts), repr(code.co_names)]
    # bound-method instance state is part of the behaviour: two
    # Thresh(t).pred with different t share code/closure/globals and
    # would otherwise collide (round-3 advisor finding — the second
    # query silently returned the first's cached result)
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        parts.append("self:" + _attr_token(self_obj, pins, seen))
    for cell in (getattr(fn, "__closure__", None) or ()):
        try:
            parts.append(_attr_token(cell.cell_contents, pins, seen))
        except Exception:
            pins.append(cell)
            parts.append(f"cell:{id(cell)}")
    # referenced globals are part of the behaviour: `thr = 0.5;
    # lambda v: v > thr` re-created after `thr = -0.5` has identical
    # code/consts/names and must NOT key identically. Names are
    # collected TRANSITIVELY through nested code objects (an inner
    # lambda/genexp reads the same __globals__ but its names live on
    # its own code constant, not the outer co_names). Scalars and small
    # containers key by value (so in-place mutation of a global list of
    # thresholds re-keys at the next query); modules/builtins by name
    # (stable); anything else by identity (pinned — a REBOUND global's
    # old value would otherwise free and its address recycle into a
    # false hit).
    g = getattr(fn, "__globals__", None) or {}
    for name in sorted(_code_names(code)):
        if name in g:
            v = g[name]
            if isinstance(v, types.ModuleType):
                parts.append(f"{name}=mod:{v.__name__}")
            else:
                parts.append(f"{name}=" + _attr_token(v, pins, seen))
    # defaults go through _attr_token, NOT bare repr: a default object
    # with a state-independent custom __repr__ would otherwise collide.
    # kw-only defaults are behaviour too — factory-made functions
    # differing only in them must not collide (round-3 advisor finding)
    parts.append(_attr_token(tuple(getattr(fn, "__defaults__", None)
                                   or ()), pins, seen))
    parts.append(_attr_token(getattr(fn, "__kwdefaults__", None) or {},
                             pins, seen))
    digest = hashlib.sha1("|".join(parts).encode()).hexdigest()[:16]
    return f"fncode:{digest}"


def _code_names(code) -> set:
    """co_names of a code object UNION those of every nested code
    object (inner lambdas, genexps, nested defs share __globals__)."""
    names = set(code.co_names)
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            names |= _code_names(c)
    return names


#: Containers above this many elements key by identity+length instead
#: of by value: re-walking a huge module-level list on EVERY plan-cache
#: lookup would turn an O(1) query into O(container) (advisor r4).
_VALUE_KEY_MAX_ELEMS = 256


def _attr_token(v, pins: list, seen: frozenset = frozenset()) -> str:
    """Encode ANY attr value into the plan key — nothing is dropped.
    Containers (tuple/list/dict/set) key by VALUE, so in-place mutation
    of e.g. a global threshold list or dict is re-read at the next query
    and correctly misses the cache. Cyclic containers terminate: a
    container reached again inside its own walk keys the back-edge by
    pinned id. Unknown object types key by identity (and are pinned):
    conservative (may miss the cache) but never shares a plan between
    distinct semantics. Caveats: in-place mutation of an id-keyed OBJECT
    (not a container) between queries is unsupported for cached
    predicates — rebind a fresh object instead; containers above
    ``_VALUE_KEY_MAX_ELEMS`` elements key by pinned identity + length
    (the value-walk would cost O(container) per lookup), so in-place
    mutation of an OVERSIZED container that keeps its length also
    requires rebinding — growth/shrinkage still re-keys via the length."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return repr(v)
    if callable(v):
        return _fn_token(v, pins, seen)
    if isinstance(v, (tuple, list, dict, set, frozenset)):
        if len(v) > _VALUE_KEY_MAX_ELEMS:
            pins.append(v)
            return f"bigcont:{type(v).__name__}:{id(v)}:len{len(v)}"
        if id(v) in seen:
            pins.append(v)
            return f"cyc:{id(v)}"
        seen = seen | {id(v)}
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_attr_token(x, pins, seen) for x in v) + "]"
    if isinstance(v, dict):
        try:
            items = sorted(v.items())
        except TypeError:
            items = sorted(v.items(), key=lambda kv: repr(kv[0]))
        return "{" + ",".join(
            _attr_token(k, pins, seen) + ":" + _attr_token(x, pins, seen)
            for k, x in items) + "}"
    if isinstance(v, (set, frozenset)):
        return "{" + ",".join(
            sorted(_attr_token(x, pins, seen) for x in v)) + "}"
    pins.append(v)
    return f"obj:{type(v).__name__}:{id(v)}"


def _plan_key_spans(e: MatExpr, leaf_token=None
                    ) -> Tuple[list, list, dict]:
    """(parts, pins, spans) in ONE walk. ``"|".join(parts)`` is the
    root's structural key; ``spans[uid] = (start, end)`` slices
    ``parts`` so that ``"|".join(parts[start:end])`` is EXACTLY the
    standalone key of that subtree (the emission is pre-order with a
    closing part, so a subtree's parts are one contiguous run). This
    is what lets the result cache probe every interior node of a query
    without re-walking each subtree through ``_attr_token`` — O(nodes)
    key work per admission instead of O(nodes x depth).

    ``leaf_token`` (serve/placement.py) substitutes the leaf-part
    emission: ``leaf_token(node) -> str or None`` replaces the
    id()-based leaf tokens with session-independent ones (catalog
    names — the fleet directory's cross-slice key), ``None`` meaning
    the leaf has no stable name and the whole key is ineligible
    (signalled by raising :class:`KeyError` from the walk). Interior
    tokens are byte-identical either way — ONE structural-walk
    implementation for every key the engine makes."""
    parts: list = []
    pins: list = []
    spans: dict = {}

    def walk(n: MatExpr):
        start = len(parts)
        if n.kind in ("leaf", "sparse_leaf", "coo_leaf"):
            if leaf_token is not None:
                tok = leaf_token(n)
                if tok is None:
                    raise KeyError(n.kind)
                parts.append(tok)
                spans[n.uid] = (start, len(parts))
                return
        if n.kind == "leaf":
            m = n.attrs["matrix"]
            pins.append(m)
            parts.append(f"leaf:{id(m)}:{m.shape}:{m.spec}")
        elif n.kind in ("sparse_leaf", "coo_leaf"):
            # sparse payloads are captured as CONSTANTS in the compiled
            # program — the cache key must carry the matrix identity or two
            # same-shaped sparse matrices would share one plan
            m = n.attrs["matrix"]
            pins.append(m)
            parts.append(f"{n.kind}:{id(m)}:{m.shape}")
        else:
            attrs = {k: _attr_token(v, pins)
                     for k, v in sorted(n.attrs.items())}
            parts.append(f"{n.kind}:{n.shape}:{attrs}(")
            for c in n.children:
                walk(c)
            parts.append(")")
        spans[n.uid] = (start, len(parts))

    walk(e)
    return parts, pins, spans


def _rc_answer(e: MatExpr, root_hit: bool) -> dict:
    """``last_plan()``'s account of one result-cache admission: ``e``
    is the statement as it will run (cached interiors substituted)."""
    if root_hit:
        return {"root_hit": True, "views_hit": 1, "table_pass": False}
    views = tables = 0
    seen = set()

    def walk(n: MatExpr):
        nonlocal views, tables
        if n.uid in seen:
            return
        seen.add(n.uid)
        if not n.children:
            if "result_cache" in n.attrs:
                views += 1
            elif "cse" not in n.attrs:
                tables += 1
        for c in n.children:
            walk(c)

    walk(e)
    return {"root_hit": False, "views_hit": views,
            "table_pass": tables > 0}


def _product_factors(e: MatExpr) -> List[MatExpr]:
    """The ordered factors of a tree of plain products (no stamped
    attribute on any of them): what the chain DP re-associates."""
    if e.kind == "matmul" and not e.attrs:
        return (_product_factors(e.children[0])
                + _product_factors(e.children[1]))
    return [e]


def _plan_key(e: MatExpr) -> Tuple[str, list]:
    """(key, pins): pins is every object the key references by id() —
    matrices, raw callables, their id-keyed globals/cells. The caller
    must keep pins alive as long as the key maps to a cached plan."""
    parts, pins, _spans = _plan_key_spans(e)
    return "|".join(parts), pins


def get_or_create_session() -> MatrelSession:
    return MatrelSession.builder().get_or_create()


def reset_session() -> None:
    global _active
    _active = None
