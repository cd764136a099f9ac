"""DeltaPlane — incremental view maintenance over the result cache
(docs/IVM.md).

``session.register_delta(name, delta)`` routes here: instead of the
transitive invalidation a catalog rebind pays today, every cached
entry depending on the rebound matrix is PATCHED in place through the
delta algebra (ir/delta.py) when a rule applies and the pricing says
the patch beats recompute; ineligible or priced-out entries fall back
to exactly the historical kill, so correctness never regresses.

The plane owns:
  * generation bookkeeping — the ``delta:<gen>|`` key-prefix idiom
    (session._rc_key_prefix), with surviving un-dependent entries
    RENAMED across the generation so they keep hitting;
  * delta propagation order — dependents patch smallest-expression
    first, and each patched entry's (old, new) value pair enters the
    ``known`` map so downstream entries consume its delta as a leaf
    (the cached-DAG propagation, not per-entry re-derivation);
  * patch-vs-recompute pricing — the flop estimate
    (``delta_est_saved_flops``, recorded on the patch plan's
    matmul_decisions) decided by default, a measured autotune ``ivm|``
    winner overriding it (the ``fuse|`` precedent);
  * steady-state plan reuse — a patch plan whose delta signature and
    sibling set repeat is RE-RUN with rebound factor/dense/result
    leaves (CompiledPlan.run(bindings=...)) instead of recompiled:
    constant-batch streams pay one compile per entry, ever.

  * rows deltas IN PLACE (``kind="rows"`` against a dense float32
    table on one device — :meth:`DeltaPlane._apply_rows`): the rows
    that leave are read out, the table's buffer is overwritten where
    it lies (its BlockMatrix, and so every plan and key that names it,
    stays the object it was), and each view with a rule
    (ir/delta.derive_rows_patch) is corrected from the rows that left,
    the rows that came and its partner's same rows — carried as a
    compensated pair of words, its composed bound kept, and RE-BASED
    (recomputed from the table) before the bound passes
    ``ir/delta.ROWS_REBASE_BOUND``. Nothing on that path copies the
    table or passes over it; anything else a rows delta meets (a mesh,
    another dtype) takes the copying path below, dependents killed.

Entry mutation happens ONLY through the result cache's patch/apply
seam (apply_patch / rekey / drop — matlint ML012 pins that).

Nothing here constructs on the default path: the session builds a
DeltaPlane lazily on the first ``register_delta`` (the brownout /
breaker zero-object contract).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import logging
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from matrel_tpu.ir import delta as delta_lib
from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.serve.result_cache import CacheEntry, result_nbytes

log = logging.getLogger("matrel_tpu.ivm")


@dataclasses.dataclass
class PatchProgram:
    """One compiled patch plan, reusable across delta generations for
    the same entry when the delta signature (and the sibling entries
    the plan reads) repeat — the steady-state path of a constant-batch
    stream."""

    plan: object                              # executor.CompiledPlan
    binds: Tuple[Tuple[int, tuple], ...]      # (leaf uid, ivm_role)
    signature: tuple                          # (delta sig, entry core key)
    known_keys: Tuple[str, ...]
    rule: str
    rules: Dict[str, int]
    est_patch_flops: float
    est_full_flops: float
    err_bound: float


@dataclasses.dataclass
class RowsView:
    """A view that follows rows deltas in place: its second word (the
    compensated accumulation's: executor.rows_patch), the bound a fresh
    execution of it carries (what a re-base resets it to), and its
    compiled patches (executor.rows_patch) — one a (delta signature,
    form, partner): ``t(X) * y`` has one for a delta of X and one for a
    delta of y."""

    lo: object
    base_bound: float
    programs: Dict[tuple, object] = dataclasses.field(default_factory=dict)


class DeltaPlane:
    """Per-session IVM orchestrator (see module docstring)."""

    def __init__(self, session):
        delta_lib._CONSTRUCTED["count"] += 1
        self.sess = session
        self._programs: Dict[int, PatchProgram] = {}
        # what a rows-patched view carries beside its entry, by ivm_id
        # (reconciled against the live entries with _programs)
        self._rows_views: Dict[int, RowsView] = {}
        self._ivm_ids = itertools.count(1)
        self.stats = {"patch_compiles": 0, "patch_reuses": 0,
                      "measured_overrides": 0, "rebases": 0}

    # -- entry point --------------------------------------------------------

    def apply(self, name: str, old, delta: delta_lib.MatrixDelta) -> dict:
        from matrel_tpu.resilience.retry import now as _now
        sess = self.sess
        cfg = sess.config
        mesh = sess.mesh
        if delta.kind == "rows" and _in_place_table(old, mesh):
            return self._apply_rows(name, old, delta)
        t0 = _now()
        with trace_lib.span("delta.update", in_place=False,
                            rows=(int(delta.rows.shape[0])
                                  if delta.kind == "rows" else None)):
            new = delta.apply_to(old, mesh, cfg)
        gen_old = sess._delta_gen
        gen = gen_old + 1
        old_prefix = delta_lib.delta_prefix(gen_old)
        new_prefix = delta_lib.delta_prefix(gen)
        rc = sess._result_cache
        keep_stale = sess._brownout is not None
        deps = frozenset({id(old)})
        snapshot = rc.items_snapshot()
        dependents = [(k, e) for k, e in snapshot if e.dep_ids & deps]
        others = [(k, e) for k, e in snapshot
                  if not (e.dep_ids & deps)]
        # smallest expression first: interior entries (A·A) patch
        # before the composites (trace(A·A·A)) that read their deltas
        dependents.sort(key=lambda kv: _expr_size(kv[1].expr))
        # known-sibling values are NAMESPACED BY TIER PREFIX: a
        # default-SLA patch must never consume a fast-tier sibling's
        # (old, new) pair — that would inject bf16-tier error into a
        # result whose composed bound was built from f32 units (the
        # prec:-prefix isolation contract, applied to propagation)
        known_by_prec: Dict[str, Dict[str, tuple]] = {}
        counters = {"patched": 0, "killed": 0, "priced_out": 0,
                    "reused_plans": 0}
        rules_census: Dict[str, int] = {}
        saved_total = 0.0
        for key, ent in dependents:
            ok = False
            if cfg.delta_patch_mode != "off" and ent.expr is not None:
                try:
                    ok, saved = self._patch_entry(
                        key, ent, old, new, delta, gen, new_prefix,
                        known_by_prec.setdefault(ent.prec, {}),
                        rules_census, counters)
                    saved_total += saved
                except Exception:
                    # a failing patch must degrade to the kill, never
                    # fail the register — the correctness floor
                    log.warning("ivm: patch failed for %s; falling "
                                "back to invalidation",
                                ent.key_hash, exc_info=True)
                    ok = False
            if not ok:
                rc.drop(key, keep_stale=keep_stale,
                        stale_max=cfg.result_cache_max_entries,
                        stale_max_bytes=cfg.result_cache_max_bytes)
                counters["killed"] += 1
        # survivors rename across the generation so they keep hitting
        # (generation 0 had the historical empty prefix)
        for key, _ent in others:
            if key.startswith(old_prefix):
                rc.rekey(key, new_prefix + key[len(old_prefix):])
        rc.rebuild_stale(
            lambda k: (new_prefix + k[len(old_prefix):]
                       if k.startswith(old_prefix) else k), deps)
        # the catalog rebind itself — DIRECT, not register(): the
        # dependent entries were just maintained or killed above;
        # register()'s blanket invalidation would kill the patches
        sess.catalog[name] = new
        sess._delta_gen = gen
        # reconcile the patch-plan cache against the LIVE entry set:
        # entries killed above, evicted under byte pressure, or
        # invalidated by a plain register() since the last delta leave
        # orphaned PatchPrograms whose plans pin old-generation device
        # arrays — unbounded over a long session (the ML011 failure
        # class), so they drop the moment their entry is gone
        self._reconcile(rc)
        record = {
            "name": name, "gen": gen, "delta_kind": delta.kind,
            "delta_rank": delta.rank, "delta_nnz": delta.nnz,
            "in_place": False,
            "examined": len(dependents),
            "patched": counters["patched"],
            "killed": counters["killed"],
            "priced_out": counters["priced_out"],
            "reused_plans": counters["reused_plans"],
            "rekeyed": len(others),
            "rules": rules_census,
            "est_saved_flops": round(saved_total, 1),
            "ms": round((_now() - t0) * 1e3, 3),
        }
        sess._emit_delta_event(record)
        return record

    def _reconcile(self, rc) -> None:
        """Drop the patch programs and second words of entries that
        are gone (killed, evicted, invalidated by a plain register)."""
        live = {e.ivm_id for _k, e in rc.items_snapshot()
                if e.ivm_id is not None}
        self._programs = {i: p for i, p in self._programs.items()
                          if i in live}
        self._rows_views = {i: v for i, v in self._rows_views.items()
                            if i in live}

    # -- rows, in place -----------------------------------------------------

    def _apply_rows(self, name: str, table,
                    delta: delta_lib.MatrixDelta) -> dict:
        """A rows delta against a dense float32 table on one device
        (module docstring): upload, read out and overwrite in place,
        then patch, re-base or kill each dependent entry. ``table`` is
        the catalog's BlockMatrix before AND after: its ``data`` is the
        donated program's output."""
        from matrel_tpu import executor as executor_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.parallel import planner
        from matrel_tpu.resilience.retry import now as _now
        sess = self.sess
        cfg, mesh, rc = sess.config, sess.mesh, sess._result_cache
        t0 = _now()
        c = int(delta.rows.shape[0])
        run = delta.start is not None
        gen = sess._delta_gen + 1
        old_prefix = delta_lib.delta_prefix(gen - 1)
        new_prefix = delta_lib.delta_prefix(gen)
        keep_stale = sess._brownout is not None
        deps = frozenset({id(table)})
        snapshot = rc.items_snapshot()
        todo = []
        for key, ent in snapshot:
            if not ent.dep_ids & deps:
                continue
            spec = None
            if cfg.delta_patch_mode != "off" and ent.expr is not None \
                    and _in_place_table(ent.result, mesh):
                spec = delta_lib.derive_rows_patch(ent.expr, table)
            todo.append((key, ent, spec))
        planned = planner.rows_delta_plan(
            table, c, [e.result for _k, e, s in todo if s is not None],
            [s.partner for _k, _e, s in todo
             if s is not None and s.partner is not None], mesh, cfg)
        vals = delta.vals
        nbytes = 0
        with trace_lib.span("delta.upload") as sp:
            if isinstance(vals, np.ndarray):
                nbytes = int(vals.nbytes)
                vals = BlockMatrix.from_numpy(vals, mesh=mesh,
                                              config=cfg).data
            at = (np.int32(delta.start) if run
                  else jnp.asarray(delta.rows, jnp.int32))
            sp.set(bytes=nbytes)
        with trace_lib.span("delta.update", rows=c, in_place=True,
                            hbm_plan_bytes=planned["hbm_plan_bytes"]):
            table.data, left = executor_lib.rows_update(run)(
                table.data, vals, at)
        counters = {"patched": 0, "killed": 0, "no_rule": 0,
                    "rebased": 0, "reused_plans": 0}
        worst = 0.0
        for key, ent, spec in todo:
            new_key = new_prefix + key[len(old_prefix):]
            bound = None
            if spec is not None:
                bound = self._patch_rows(key, new_key, ent, spec, table,
                                         delta, vals, left, at, gen,
                                         counters)
            if bound is None:
                rc.drop(key, keep_stale=keep_stale,
                        stale_max=cfg.result_cache_max_entries,
                        stale_max_bytes=cfg.result_cache_max_bytes)
                counters["killed"] += 1
                counters["no_rule"] += spec is None
            else:
                worst = max(worst, bound)
        rekeyed = 0
        for key, ent in snapshot:
            if not ent.dep_ids & deps and key.startswith(old_prefix):
                rekeyed += rc.rekey(key, new_prefix + key[len(old_prefix):])
        rc.rebuild_stale(
            lambda k: (new_prefix + k[len(old_prefix):]
                       if k.startswith(old_prefix) else k), deps)
        sess._delta_gen = gen
        self._reconcile(rc)
        record = {
            "name": name, "gen": gen, "delta_kind": "rows",
            "delta_rank": None, "delta_nnz": None,
            "rows": c, "in_place": True, "upload_bytes": nbytes,
            "examined": len(todo),
            "patched": counters["patched"],
            "killed": counters["killed"],
            "no_rule": counters["no_rule"],
            "priced_out": 0,
            "rebased": counters["rebased"],
            "table_passes": counters["rebased"],
            "reused_plans": counters["reused_plans"],
            "rekeyed": rekeyed,
            "rules": {"rows": counters["patched"]},
            "err_bound": worst,
            "hbm_plan_bytes": planned["hbm_plan_bytes"],
            "est_saved_flops": 0.0,
            "ms": round((_now() - t0) * 1e3, 3),
        }
        sess._emit_delta_event(record)
        return record

    def _patch_rows(self, key: str, new_key: str, ent: CacheEntry,
                    spec, table, delta, new_rows, old_rows, at, gen: int,
                    counters: dict) -> Optional[float]:
        """One view under a rows delta: corrected by its compiled patch
        (``matrel.delta.patch``), or — where the correction would carry
        its composed bound past ``ROWS_REBASE_BOUND`` — recomputed
        from the table as it now stands (``matrel.delta.rebase``: the
        statement's own plan, a pass over the table). Either way the
        entry's BlockMatrix stays the object it was (a plan over it
        stays the plan it was) and takes the new array. Returns the
        entry's bound, or None where it no longer fits the cache (the
        caller kills it)."""
        from matrel_tpu import executor as executor_lib
        sess = self.sess
        cfg = sess.config
        view = ent.result
        ivm_id = ent.ivm_id if ent.ivm_id is not None \
            else next(self._ivm_ids)
        state = self._rows_views.get(ivm_id)
        if state is None:
            state = self._rows_views[ivm_id] = RowsView(
                jnp.zeros_like(view.data), float(ent.err_bound))
        bound = float(ent.err_bound) + delta_lib.rows_patch_bound(
            int(delta.rows.shape[0]), table.shape[0])
        if bound > delta_lib.ROWS_REBASE_BOUND:
            with trace_lib.span("delta.rebase", rule="rows",
                                form=spec.form, table_pass=True,
                                err_bound=bound):
                sla = ent.prec[len("prec:"):-1] if ent.prec else "default"
                plan = sess._compile_entry(ent.expr, sla=sla)[0]
                view.data = sess._arbitrated_run(plan).data
                state.lo = jnp.zeros_like(view.data)
            bound = state.base_bound
            counters["rebased"] += 1
            self.stats["rebases"] += 1
        else:
            sig = (delta.signature(), spec.form, id(spec.partner))
            patch = state.programs.get(sig)
            reused = patch is not None
            with trace_lib.span("delta.patch", rule="rows",
                                form=spec.form, reused=reused,
                                table_pass=False, err_bound=bound):
                if not reused:
                    patch = state.programs[sig] = executor_lib.rows_patch(
                        spec.form, delta.start is not None, cfg)
                    self.stats["patch_compiles"] += 1
                else:
                    self.stats["patch_reuses"] += 1
                    counters["reused_plans"] += 1
                partner = (spec.partner.data if spec.partner is not None
                           else new_rows)
                view.data, state.lo = patch(
                    view.data, state.lo, new_rows, old_rows, partner, at)
        new_ent = dataclasses.replace(
            ent,
            key_hash=hashlib.sha1(new_key.encode()).hexdigest()[:16],
            nbytes=2 * result_nbytes(view),
            err_bound=bound,
            delta_gen=gen,
            delta_rule="rows",
            ivm_id=ivm_id)
        if not sess._result_cache.apply_patch(
                key, new_key, new_ent, cfg.result_cache_max_bytes,
                cfg.result_cache_max_entries):
            self._rows_views.pop(ivm_id, None)
            return None
        if sess._prov is not None:
            sess._prov.stamp_patched(new_ent, gen, "rows",
                                     bound - float(ent.err_bound))
        counters["patched"] += 1
        return bound

    # -- one entry ----------------------------------------------------------

    def _patch_entry(self, key: str, ent: CacheEntry, old, new,
                     delta, gen: int, new_prefix: str,
                     known: Dict[str, tuple],
                     rules_census: Dict[str, int],
                     counters: dict) -> Tuple[bool, float]:
        from matrel_tpu import executor as executor_lib
        sess = self.sess
        cfg = sess.config
        mesh = sess.mesh
        ck = delta_lib.core_key(ent.expr, frozenset({id(old)}))
        prog = (self._programs.get(ent.ivm_id)
                if ent.ivm_id is not None else None)
        out_bm = None
        meta: Optional[PatchProgram] = None
        if prog is not None \
                and prog.signature == (delta.signature(), ck) \
                and all(k in known for k in prog.known_keys):
            # steady state: same entry, same-shaped delta, siblings
            # available — rebind the dynamic leaves and re-run
            try:
                bindings = self._bindings(prog, ent, old, new, delta,
                                          known)
                out_bm = self._wrap(prog.plan.run(bindings=bindings))
                meta = prog
                self.stats["patch_reuses"] += 1
                counters["reused_plans"] += 1
            except (KeyError, ValueError):
                out_bm = None       # shape/sibling drift: recompile
        if out_bm is None:
            spec = delta_lib.derive_patch(ent.expr, old, new, delta,
                                          ent.result, mesh, cfg, known)
            if spec is None:
                return False, 0.0
            if not self._decide(spec, ent, cfg, mesh):
                counters["priced_out"] += 1
                return False, 0.0
            if spec.refine is not None:
                res = spec.refine(ent.result, new, delta)
                out_bm = self._wrap(res)
                meta = PatchProgram(
                    plan=None, binds=(), signature=(None,),
                    known_keys=(), rule=spec.rule, rules=spec.rules,
                    est_patch_flops=spec.est_patch_flops,
                    est_full_flops=spec.est_full_flops,
                    err_bound=spec.err_bound)
            else:
                stamp = {"rule": spec.rule, "gen": gen,
                         "est_saved_flops": spec.est_saved_flops}
                plan = executor_lib.compile_expr(
                    spec.expr.with_attrs(ivm_patch=stamp), mesh, cfg)
                # provenance for obs/explain: plan_matmul_decisions
                # threads this onto every decision record as
                # delta_est_saved_flops (the root stamp may not
                # survive the optimizer's rebuild — meta always does)
                plan.meta["ivm"] = dict(stamp)
                out_bm = self._wrap(plan.run())
                self.stats["patch_compiles"] += 1
                meta = PatchProgram(
                    plan=plan,
                    binds=tuple(
                        (l.uid, tuple(l.attrs["ivm_role"]))
                        for l in plan.leaf_order
                        if "ivm_role" in l.attrs),
                    signature=(delta.signature(), ck),
                    known_keys=spec.known_keys,
                    rule=spec.rule, rules=spec.rules,
                    est_patch_flops=spec.est_patch_flops,
                    est_full_flops=spec.est_full_flops,
                    err_bound=spec.err_bound)
        for r, n in meta.rules.items():
            rules_census[r] = rules_census.get(r, 0) + n
        rules_census[meta.rule] = rules_census.get(meta.rule, 0)
        # re-key under the new binding: the substituted expression is
        # structurally what a re-run query over the new catalog value
        # computes, so the patched entry answers it with a plain hit
        from matrel_tpu import session as session_lib
        from matrel_tpu.ir import expr as expr_mod
        from matrel_tpu.parallel import planner
        sub_expr = delta_lib.substitute(ent.expr, old, new)
        structural, pins = session_lib._plan_key(sub_expr)
        new_key = new_prefix + ent.prec + structural
        ivm_id = ent.ivm_id if ent.ivm_id is not None \
            else next(self._ivm_ids)
        new_ent = dataclasses.replace(
            ent,
            key_hash=hashlib.sha1(new_key.encode()).hexdigest()[:16],
            result=out_bm,
            pins=tuple(pins),
            dep_ids=(ent.dep_ids - {id(old)}) | {id(new)},
            layout=planner._layout_of(expr_mod.leaf(out_bm), mesh),
            dtype=str(np.dtype(out_bm.dtype)),
            nbytes=result_nbytes(out_bm),
            expr=sub_expr,
            err_bound=ent.err_bound + meta.err_bound,
            delta_gen=gen,
            delta_rule=meta.rule,
            ivm_id=ivm_id)
        ok = sess._result_cache.apply_patch(
            key, new_key, new_ent, cfg.result_cache_max_bytes,
            cfg.result_cache_max_entries)
        if not ok:
            self._programs.pop(ivm_id, None)
            return False, 0.0
        if sess._prov is not None:
            # one lineage link per applied patch: the chain (and the
            # composed err_bound a later audit replays against) lives
            # on the ledger, the stamp on the entry (sanctioned seam
            # — obs/provenance.py)
            sess._prov.stamp_patched(new_ent, gen, meta.rule,
                                     meta.err_bound)
        if meta.plan is not None:
            self._programs[ivm_id] = meta
        counters["patched"] += 1
        known[ck] = (ent.result, out_bm)
        return True, meta.est_full_flops - meta.est_patch_flops

    # -- helpers ------------------------------------------------------------

    def _wrap(self, res):
        """Refine hooks may hand back host arrays; patch plans hand
        BlockMatrices. One canonical form enters the cache."""
        from matrel_tpu.core.blockmatrix import BlockMatrix
        if isinstance(res, BlockMatrix):
            return res
        arr = np.asarray(res)
        if arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        return BlockMatrix.from_numpy(arr, mesh=self.sess.mesh,
                                      config=self.sess.config)

    def _bindings(self, prog: PatchProgram, ent: CacheEntry, old, new,
                  delta, known: Dict[str, tuple]) -> dict:
        cfg = self.sess.config
        mesh = self.sess.mesh
        fac = delta.factors(mesh, cfg)
        fixed = {
            delta_lib.ROLE_TARGET_OLD: old,
            delta_lib.ROLE_TARGET_NEW: new,
            delta_lib.ROLE_OLD_RESULT: ent.result,
        }
        out = {}
        for uid, role in prog.binds:
            head = role[0]
            if head == "factor_u":
                if fac is None:
                    raise ValueError("delta lost its factored form")
                bm = fac[0]
            elif head == "factor_v":
                if fac is None:
                    raise ValueError("delta lost its factored form")
                bm = fac[1]
            elif head == "delta_dense":
                bm = delta.materialize(mesh, cfg)
            elif head == "known_old":
                bm = known[role[1]][0]
            elif head == "known_new":
                bm = known[role[1]][1]
            else:
                bm = fixed[tuple(role)]
            out[uid] = bm
        return out

    def _decide(self, spec: delta_lib.PatchSpec, ent: CacheEntry,
                cfg, mesh) -> bool:
        """Patch-vs-recompute: the flop estimate decides, a measured
        autotune ``ivm|`` winner overrides it (the fuse| precedent).
        Measurement itself happens lazily through the bench/soak
        harnesses (autotune.lookup_or_measure_ivm with runners) — the
        hot register path only ever LOOKS UP."""
        if cfg.delta_patch_mode == "force":
            return True
        # ties favor the patch: at equal flops the patched entry still
        # amortizes compiles (the recompute arm recompiles every
        # generation — rebinding changes every plan key) and keeps the
        # cache warm
        est_win = spec.est_saved_flops >= 0.0
        if cfg.autotune:
            from matrel_tpu.parallel import autotune
            side = max(ent.result.shape[0], ent.result.shape[1],
                       *spec_shape(spec))
            winner = autotune.lookup_or_measure_ivm(
                spec.rule, side, mesh, cfg)
            if winner in ("patch", "recompute"):
                self.stats["measured_overrides"] += 1
                return winner == "patch"
        return est_win


def _in_place_table(m, mesh) -> bool:
    """What the in-place rows programs take: a dense float32
    BlockMatrix on ONE device."""
    from matrel_tpu.core.blockmatrix import BlockMatrix
    return (isinstance(m, BlockMatrix) and mesh.size == 1
            and np.dtype(m.data.dtype) == np.float32)


def spec_shape(spec: delta_lib.PatchSpec) -> tuple:
    e = spec.expr
    return tuple(e.shape) if e is not None else (1, 1)


def _expr_size(e) -> int:
    if e is None:
        return 0
    seen = set()

    def walk(n) -> int:
        if n.uid in seen:
            return 0
        seen.add(n.uid)
        return 1 + sum(walk(c) for c in n.children)

    return walk(e)
