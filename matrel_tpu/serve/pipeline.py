"""Micro-batched admission + async execution pipeline.

``session.submit(expr)`` returns a ``concurrent.futures.Future``; one
admission worker per session drains the submission queue, coalesces up
to ``config.serve_max_batch`` concurrent queries into ONE MultiPlan
(one fusion/CSE domain, shared leaf transfers — ``session.run_many``)
and dispatches it WITHOUT waiting for device completion: JAX's async
dispatch returns arrays whose values are still materialising, so the
worker immediately starts optimize/verify/trace of the next batch while
the device executes this one — the MPMD overlap-dispatch-with-execution
discipline, host-side.

The overlap is BOUNDED: past ``config.serve_max_inflight``
dispatched-but-unsynced batches the worker blocks on the oldest, so
host planning never runs unboundedly ahead of the device (an unbounded
queue would pile un-materialised results — and their HBM — without
backpressure).

Futures resolve with the BlockMatrix as soon as its batch is
DISPATCHED (the array is usable immediately; touching its values
blocks until the device delivers them — ordinary JAX semantics).

Resilience contracts (docs/RESILIENCE.md):

- **Poison-query isolation by batch bisection**: a failing MultiPlan is
  recursively SPLIT instead of failing every sibling future — only the
  poison query's own future resolves with the (typed) error, siblings
  re-admit in halves and complete normally. Depth is bounded by
  log2(batch).
- **Backpressure**: ``config.serve_queue_max`` bounds the admission
  queue; a submit against a full queue raises the typed
  ``AdmissionShed`` rather than growing the queue without bound.
- **Deadlines**: a future whose per-query deadline expires while
  queued — or whose batch finishes past it — resolves with the typed
  ``DeadlineExceeded``; expired entries never reach compilation.
- **Typed shutdown**: ``drain(timeout=...)`` raises ``DrainTimeout``
  instead of hanging on a wedged worker; ``submit`` after ``close()``
  raises ``PipelineClosed`` instead of enqueueing into a dead worker.

Overload control plane (docs/OVERLOAD.md, round 13):

- **Per-tenant admission**: the FIFO queue became the weighted-fair
  :class:`serve.admission.AdmissionQueue` — per-tenant queues,
  stride-scheduled pops (so batch formation is fair by construction),
  per-tenant quota sheds BEFORE the global bound, and deadline-expired
  entries purged at every shed decision point. ``submit`` carries
  ``tenant=`` and ``staleness_ms=``.
- **Adaptive brownout**: when the session owns a
  :class:`resilience.brownout.LoadController` the worker feeds it one
  sample per admission cycle (queue depth, waits, deadline misses);
  rung 1 downshifts default-SLA queries to the "fast" tier (stamped,
  MV112-verified, SLA-key-isolated), rung 2 serves STALE result-cache
  entries to queries declaring ``staleness_ms``, rung 3 sheds
  lowest-weight tenants typed at submit.
- **Circuit breakers**: with a session
  :class:`resilience.breaker.BreakerRegistry`, each entry's plan
  class is gated at batch formation — an OPEN class fails its future
  fast with the typed ``CircuitOpen`` (half-open probe schedule
  attached) instead of riding a batch it would poison.
- **Obs**: one ``overload`` event per admission cycle (rung, tenant
  depths/waits, shed/purge/stale deltas, breaker state) whenever the
  control plane is active.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from concurrent.futures import Future
from typing import Optional

from matrel_tpu.obs import trace as trace_lib
from matrel_tpu.resilience import breaker as breaker_lib
from matrel_tpu.resilience import brownout as brownout_lib
from matrel_tpu.resilience import faults as faults_lib
from matrel_tpu.resilience import retry as retry_lib
from matrel_tpu.resilience.errors import (AdmissionShed, CircuitOpen,
                                          DeadlineExceeded,
                                          DrainTimeout, PipelineClosed)
from matrel_tpu.resilience.retry import Deadline
from matrel_tpu.serve.admission import AdmissionQueue
from matrel_tpu.utils import lockdep

log = logging.getLogger("matrel_tpu.serve")

#: Entry layout: (expr, future, t_enqueue, sla, deadline, tenant,
#: staleness_ms). Legacy white-box callers enqueue shorter tuples;
#: the worker right-pads with these defaults.
_ENTRY_DEFAULTS = ("default", None, "", None)


class ServePipeline:
    """One session's admission queue + worker thread (daemon, started
    on first submit). Not a pool: queries of one session share its
    plan/result caches, so one worker keeps every cache consult
    race-free while the caller's thread stays free to submit."""

    def __init__(self, session):
        self.session = session
        self.max_batch = session.config.serve_max_batch
        self.max_inflight = session.config.serve_max_inflight
        self.queue_max = session.config.serve_queue_max
        # SLO plane (obs/slo.py; None when off): the queue reports
        # typed sheds / purges, this pipeline reports resolution
        # latency and deadline misses — together the full outcome
        # stream the burn-rate monitors watch
        self._slo = getattr(session, "_slo", None)
        self._q = AdmissionQueue(session.config, slo=self._slo)
        self._inflight: "collections.deque" = collections.deque()  # matlint: disable=ML011 bounded by the serve_max_inflight sync loop in _run_group
        self._worker: threading.Thread = None
        self._stop = threading.Event()
        self._closed = False
        # RLock: submit() holds it across the closed-check + enqueue +
        # _ensure_worker (which locks again) so a concurrent close()
        # can never interleave between them
        self._lock = lockdep.make_rlock("serve.pipeline")
        # overload control plane (session-owned; None when off — the
        # bit-identity contract): brownout controller + breakers, plus
        # the last counter snapshot the overload event diffs against
        self._brownout = getattr(session, "_brownout", None)
        self._breakers = getattr(session, "_breakers", None)
        self._overload_active = (
            self._brownout is not None or self._breakers is not None
            or self._slo is not None or bool(self._q.weights))
        self._overload_last: dict = {}
        self.stale_served = 0
        self.deadline_misses = 0
        # late deadline misses (batch finished past a query's SLA),
        # folded into the NEXT cycle's controller sample — one
        # observe() per admission cycle is the hysteresis contract,
        # so _run_group must not sample mid-batch. Worker-thread-only.
        self._late_misses = 0

    # -- public surface ----------------------------------------------------

    def submit(self, expr, sla: str = "default",
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               staleness_ms: Optional[float] = None) -> Future:
        """Enqueue one query; returns its future. ``sla`` is the
        query's precision SLA — the admission worker only coalesces
        same-SLA queries into one MultiPlan (one planning config per
        batch; mixed SLAs run as separate sub-batches).
        ``deadline_ms`` starts the query's deadline clock NOW (queue
        wait counts against it). ``tenant`` names the submitting
        tenant for weighted-fair admission (None = the implicit
        tenant); ``staleness_ms`` declares how old a STALE result-
        cache answer this query tolerates (consumed only at brownout
        rung >= 2 — docs/OVERLOAD.md)."""
        fut: Future = Future()
        dl = Deadline(deadline_ms) if deadline_ms is not None else None
        # enqueue timestamp, not a measurement: its delta lands in the
        # serve event record as queue_wait_ms
        entry = (expr, fut, time.perf_counter(), sla, dl, tenant or "",  # matlint: disable=ML006 queue-wait timestamp — lands in the serve event record
                 staleness_ms)
        # closed-check + enqueue + worker-ensure are ONE atomic step
        # vs close(): a submit that passes the check enqueues with the
        # worker alive BEFORE close() can flip _closed, and close()'s
        # drain then still processes the entry — no future can ever be
        # stranded in a dead queue
        with self._lock:
            if self._closed:
                raise PipelineClosed(
                    "submit after close(): the admission worker is "
                    "stopped — build a new session (or pipeline) to "
                    "serve again")
            # brownout rung 3: shed lowest-weight tenants FIRST —
            # typed, before any queue slot is consumed
            ctl = self._brownout
            if (ctl is not None
                    and ctl.rung() >= brownout_lib.SHED_RUNG
                    and self._q.lowest_weight_tenant(tenant)):
                self._q.record_shed(tenant)
                raise AdmissionShed(self._q.tenant_max
                                    or self._q.global_max,
                                    tenant=tenant, scope="brownout")
            # typed load shed (per-tenant quota first, then the global
            # bound — each after purging deadline-expired entries):
            # the bounded queue protects the queries already admitted
            self._q.put(entry, tenant or "")
            self._ensure_worker()
        return fut

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted query is dispatched AND every
        dispatched batch has materialised on device. ``timeout``
        (seconds) bounds the whole wait: a wedged worker raises the
        typed ``DrainTimeout``; queue state is untouched."""
        t_abs = (retry_lib.now() + timeout
                 if timeout is not None else None)
        # queue.Queue.join() has no timeout — wait the same condition
        # it waits, re-checking the clock on every wakeup
        with self._q.all_tasks_done:
            while self._q.unfinished_tasks:
                rem = (None if t_abs is None
                       else t_abs - retry_lib.now())
                if rem is not None and rem <= 0:
                    raise DrainTimeout(timeout,
                                       self._q.unfinished_tasks)
                self._q.all_tasks_done.wait(rem)
        while self._inflight:
            rem = None if t_abs is None else t_abs - retry_lib.now()
            if rem is not None and rem <= 0:
                raise DrainTimeout(timeout, len(self._inflight))
            try:
                outs = self._inflight.popleft()
            except IndexError:      # worker synced it concurrently
                break
            if not _sync_bounded(outs, rem):
                # a device-side wedge: block_until_ready cannot be
                # interrupted, so the sync ran on a helper thread and
                # the batch goes BACK in front (a later drain — or the
                # still-running helper — can finish it)
                self._inflight.appendleft(outs)
                raise DrainTimeout(timeout, len(self._inflight))

    def close(self, timeout: Optional[float] = None) -> None:
        """Stop the worker after the queue drains. A later ``submit``
        raises the typed ``PipelineClosed``."""
        with self._lock:
            # flip FIRST (atomic vs submit): any submit that already
            # passed the check has its entry enqueued with the worker
            # alive, and the drain below processes it; any later one
            # raises typed
            self._closed = True
        self.drain(timeout=timeout)
        self._stop.set()

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def inflight_depth(self) -> int:
        return len(self._inflight)

    # -- worker ------------------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._lock:
            if self._closed:
                return
            if self._worker is None or not self._worker.is_alive():
                self._stop.clear()
                self._worker = threading.Thread(
                    target=self._run, name="matrel-serve", daemon=True)
                self._worker.start()

    def readmit_entry(self, entry, tenant: str) -> None:
        """Fleet-failover seam (serve/fleet.py is the one caller):
        enqueue an already-built entry under the SAME closed-check +
        enqueue + worker-ensure atomicity ``submit`` enforces — a
        stolen future re-admitted into a pipeline that a concurrent
        ``close()`` just flipped would otherwise strand in a closed,
        workerless queue (``_ensure_worker`` no-ops once ``_closed``
        is set). Raises ``PipelineClosed``/``AdmissionShed`` typed;
        the fleet turns either into a typed refusal."""
        with self._lock:
            if self._closed:
                raise PipelineClosed(
                    "re-admission after close(): the admission "
                    "worker is stopped")
            self._q.put(entry, tenant)
            self._ensure_worker()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.05)
            except queue.Empty:
                if self._slo is not None:
                    # burn decays as the windows slide: a drained
                    # plane must CLEAR its alerts without waiting for
                    # the next query (obs/slo.py tick contract)
                    self._slo.tick()
                continue
            pulled = [first]
            while len(pulled) < self.max_batch:
                try:
                    pulled.append(self._q.get_nowait())
                except queue.Empty:
                    break
            # normalise legacy short entries (pre-SLA white-box
            # callers enqueue (expr, fut, t_enq); later rounds added
            # sla / deadline / tenant / staleness) to the 7-tuple
            pulled = [(*it, *_ENTRY_DEFAULTS[len(it) - 3:])
                      if len(it) < 7 else it for it in pulled]
            # transition each future to RUNNING; a future the caller
            # cancelled while queued drops out here (and can no longer
            # be cancelled mid-flight) — set_result on a cancelled
            # future would raise InvalidStateError and kill the worker,
            # stranding every sibling future of the batch
            batch = [it for it in pulled
                     if it[1].set_running_or_notify_cancel()]
            t_admit = time.perf_counter()  # matlint: disable=ML006 queue-wait timestamp — lands in the serve event record
            cycle_waits = [round((t_admit - it[2]) * 1e3, 3)
                           for it in batch]
            # deadline shed BEFORE compilation: an entry that expired
            # while queued resolves typed and never costs a compile
            live = []
            misses = 0
            for it in batch:
                dl = it[4]
                if dl is not None and dl.expired():
                    _fail(it[1], DeadlineExceeded(
                        dl.budget_ms, dl.elapsed_ms(),
                        context="queued query"))
                    misses += 1
                    if self._slo is not None:
                        self._slo.record_miss(it[5] or None)
                else:
                    live.append(it)
            self.deadline_misses += misses
            # circuit breakers: an entry whose plan class is OPEN
            # fails fast (typed, probe schedule attached) instead of
            # riding — and poisoning — a batch
            if self._breakers is not None:
                admitted = []
                for it in live:
                    try:
                        self._breakers.admit(
                            self._breakers.plan_class(it[0]))
                    except CircuitOpen as ex:
                        _fail(it[1], ex)
                        if self._slo is not None:
                            # a breaker refusal is a shed the tenant
                            # sees — availability budget burn
                            self._slo.record_shed(it[5] or None)
                    else:
                        admitted.append(it)
                live = admitted
            # per-tenant queue waits AT ADMISSION (t_admit) — both the
            # controller and the overload event read these; measuring
            # at emission time would fold compile/dispatch time into
            # a number named "queue wait"
            tenant_waits: dict = {}
            for it, w in zip(batch, cycle_waits):
                tenant_waits.setdefault(it[5] or "", []).append(w)  # matlint: disable=ML013 one admission cycle's event-record assembly — these waits land in the overload event and the controller sample, not a private stopwatch
            # brownout: ONE load sample per admission cycle (late
            # deadline misses from earlier batches fold in here), then
            # act on the (possibly new) rung
            rung = 0
            ctl = self._brownout
            if ctl is not None:
                late, self._late_misses = self._late_misses, 0
                rung = ctl.observe(depth=self._q.qsize(),
                                   waits_ms=cycle_waits,
                                   misses=misses + late,
                                   admitted=len(live))
            stale_served = 0
            if (rung >= brownout_lib.STALE_RUNG
                    and self.session._rc_enabled()):
                # rung 2: a query that DECLARED a staleness tolerance
                # may be answered by the stale ghost of a rebind-
                # invalidated entry — exact answer, slightly old
                # catalog; nothing compiles, nothing executes
                remaining = []
                for it in live:
                    ent = (self.session._rc_stale_probe(
                        it[0], it[3], it[6]) if it[6] else None)
                    if ent is not None:
                        if not it[1].done():
                            it[1].set_result(ent.result)
                        stale_served += 1
                        if self.session._prov is not None:
                            self.session._prov_capture_stale(
                                it[0], ent,
                                AdmissionQueue.entry_provenance(it))
                        if self._slo is not None:
                            self._slo.record_ok(
                                it[5] or None,
                                (time.perf_counter() - it[2]) * 1e3)  # matlint: disable=ML006 SLO resolution-latency sample — lands in the slo plane's sketches and alert records

                        # a cache hit says NOTHING about the class's
                        # execution health — release the (possibly
                        # half-open probe) slot without a transition,
                        # never close a breaker on work that never ran
                        self._breaker_done(it[0], None)
                    else:
                        remaining.append(it)
                live = remaining
                self.stale_served += stale_served
            if rung >= brownout_lib.TIER_RUNG:
                # rung 1: default-SLA queries downshift to the "fast"
                # tier, STAMPED on the expr root so MV112 can verify
                # the claim and the prec:fast| key prefix isolates the
                # browned-out plan/result from full-fidelity ones
                live = [self._downshift(it, rung) for it in live]
            # same-SLA sub-batches, admission order preserved: one
            # MultiPlan compiles under ONE planning config, so a
            # "fast" submission must never ride an "exact" query's
            # batch (precision SLAs are per query, not per batch)
            groups: "collections.OrderedDict" = collections.OrderedDict()
            for it in live:
                groups.setdefault(it[3], []).append(it)
            try:
                for sla, part in groups.items():
                    self._admit_group(sla, part, t_admit, rung)
            finally:
                for _ in pulled:
                    self._q.task_done()
                if self._overload_active:
                    self._emit_overload(rung, tenant_waits, misses,
                                        stale_served)

    @staticmethod
    def _downshift(it, rung: int):
        """Rung >= 1: rewrite one entry's expr/sla for the fast tier.
        Non-default SLAs pass through untouched — an explicit accuracy
        ask is an ask, brownout only downgrades the defaults. The
        stamp carries the AUTHORIZING rung (brownout.downshift_stamp),
        so every downshifted plan shares one cache key regardless of
        the controller's instantaneous rung."""
        if it[3] != "default":
            return it
        stamp = brownout_lib.downshift_stamp(
            it[6] if rung >= brownout_lib.STALE_RUNG else None)
        e = it[0].with_attrs(brownout=stamp)
        return (e, it[1], it[2], "fast", it[4], it[5], it[6])

    def _breaker_done(self, expr, ok, ex: BaseException = None) -> None:
        """Record one admitted entry's terminal outcome against its
        plan-class breaker (no-op when breakers are off). Outcomes
        that say nothing about the class — deadline, shed, abort —
        release the probe slot without a transition."""
        if self._breakers is None:
            return
        cls = self._breakers.plan_class(expr)
        if ok:
            self._breakers.record(cls, True)
        elif ex is not None and breaker_lib.counts_as_failure(ex):
            self._breakers.record(cls, False)
        else:
            self._breakers.record(cls, None)

    def _emit_overload(self, rung: int, tenant_waits: dict,
                       misses: int, stale_served: int) -> None:
        """One ``overload`` record per admission cycle while the
        control plane is active: instantaneous rung/depths, this
        cycle's per-tenant ADMISSION-TIME waits (the same numbers the
        controller sampled), and shed/purge/breaker-transition DELTAS
        (cumulative counters diffed against the last cycle — the
        multi-session-log discipline of the serve roll-up)."""
        sess = self.session
        if not (sess._obs_enabled() or sess._flight is not None):
            return
        try:
            counters = self._q.counters()
            last = self._overload_last
            shed_delta = {
                t: n - last.get("sheds", {}).get(t, 0)
                for t, n in counters["sheds"].items()
                if n - last.get("sheds", {}).get(t, 0)}
            admitted = {t: len(ws) for t, ws in tenant_waits.items()}
            rec = {
                "rung": rung,
                "rung_label": brownout_lib.rung_label(rung),
                "queue_depth": self._q.qsize(),
                "tenant_depths": self._q.tenant_depths(),
                "admitted": admitted,
                "tenant_waits_ms": tenant_waits,
                "sheds": shed_delta,
                "purged_expired": (counters["purged_expired"]
                                   - last.get("purged_expired", 0)),
                "deadline_misses": misses,
                "stale_served": stale_served,
            }
            if self._brownout is not None:
                rec["brownout"] = self._brownout.snapshot()
            if self._slo is not None:
                # the SLO plane's live state rides the overload
                # stream, so `top --log` (and any offline replay)
                # reconstructs burn rates/alert states without the
                # endpoint (obs/top.py snapshot_from_log)
                rec["slo"] = self._slo.snapshot()
            if self._breakers is not None:
                snap = self._breakers.snapshot()
                lt = last.get("breaker_transitions", {})
                rec["breakers"] = {
                    "open": snap["open"],
                    "half_open": snap["half_open"],
                    "transitions": {
                        k: v - lt.get(k, 0)
                        for k, v in snap["transitions"].items()},
                }
                counters["breaker_transitions"] = snap["transitions"]
            self._overload_last = counters
            sess._emit_overload_event(rec)
        except Exception:   # the never-fail obs contract
            log.warning("obs: overload event dropped", exc_info=True)

    def _admit_group(self, sla: str, batch: list, t_admit: float,
                     rung: int = 0) -> None:
        self._run_group(sla, batch, t_admit, depth=0,
                        retries=self.session.config.retry_max_attempts,
                        rung=rung)

    def _run_group(self, sla: str, batch: list, t_admit: float,
                   depth: int, retries: int = 0,
                   rung: int = 0) -> None:
        """Run one same-SLA sub-batch through session.run_many and
        resolve its futures. A failing batch BISECTS: the halves
        re-admit independently, so one poison query fails only its own
        future (typed) while every sibling completes — the worker
        survives regardless. A single-query group that fails TRANSIENT
        re-admits up to ``retries`` times (the admission-level sites
        sit outside run_many's own retry loop), so injected admission
        hiccups converge instead of failing a healthy query."""
        if not batch:
            return
        waits_ms = [round((t_admit - t_enq) * 1e3, 3)
                    for _, _, t_enq, *_ in batch]
        try:
            # fault site "serve_admit" INSIDE the try: an injected
            # admission fault exercises the same bisection/re-admission
            # path as any other batch failure (free when off)
            faults_lib.check("serve_admit", self.session.config)
            # the worker thread's entry span: the admission
            # span is the serve trail's root — run_many's
            # batch/plan/execute spans parent-link under it,
            # so a chrome export shows queue bubbles next to
            # compile/execute overlap
            with trace_lib.entry(
                    "serve.admit", getattr(self.session, "_tracer", None),
                    batch=len(batch), inflight=len(self._inflight),
                    bisect_depth=depth,
                    max_wait_ms=max(waits_ms) if waits_ms else 0.0):
                outs = self.session.run_many(
                    [it[0] for it in batch],
                    precision=sla,
                    _queue_wait_ms=waits_ms,
                    _inflight_depth=len(self._inflight),
                    _tenants=[it[5] for it in batch],
                    _brownout_rung=rung or None)
        except Exception as ex:  # noqa: BLE001 — any planning/
            # compile/execute failure either bisects (isolating the
            # poison query), re-admits a transient single, or fails
            # the lone future typed; the worker survives either way
            if depth == 0:
                dump = getattr(self.session, "_flight_auto_dump", None)
                if dump is not None:
                    # the post-mortem trail for a failed serve batch
                    # (no-op when the flight recorder is off)
                    dump(ex, reason="serve_batch_failure")
            emit = getattr(self.session, "_emit_retry_event", None)
            if len(batch) == 1:
                from matrel_tpu.resilience.errors import is_transient
                if retries > 0 and is_transient(ex):
                    if emit is not None:
                        emit(ex, attempt=depth + 1, rung=0,
                             scope="serve_readmit")
                    self._run_group(sla, batch, t_admit, depth + 1,
                                    retries=retries - 1, rung=rung)
                else:
                    # TERMINAL single-query failure: the breaker's
                    # class-health signal (retry budget already spent)
                    self._breaker_done(batch[0][0], False, ex)
                    _fail(batch[0][1], ex)
                    if self._slo is not None:
                        self._slo.record_bad(batch[0][5] or None,
                                             "error")
                return
            # POISON ISOLATION: split and re-admit each half — only
            # the failing query's own future ends up carrying the
            # error. Recursion depth is bounded by log2(batch).
            if emit is not None:
                emit(ex, attempt=depth + 1, rung=0,
                     scope="serve_bisect")
            mid = len(batch) // 2
            self._run_group(sla, batch[:mid], t_admit, depth + 1,
                            retries=retries, rung=rung)
            self._run_group(sla, batch[mid:], t_admit, depth + 1,
                            retries=retries, rung=rung)
        else:
            for it, out in zip(batch, outs):
                fut, dl = it[1], it[4]
                if dl is not None and dl.expired():
                    # the batch finished past this query's deadline:
                    # the future resolves TYPED (the result exists but
                    # the caller's SLA already failed — honoring it
                    # beats handing back a late answer marked on-time).
                    # The miss folds into the NEXT cycle's controller
                    # sample (one observe per cycle — the hysteresis
                    # dwell must not be advanced mid-batch).
                    self.deadline_misses += 1
                    self._late_misses += 1
                    self._breaker_done(it[0], None)
                    _fail(fut, DeadlineExceeded(
                        dl.budget_ms, dl.elapsed_ms(),
                        context="served query"))
                    if self._slo is not None:
                        self._slo.record_miss(it[5] or None)
                else:
                    self._breaker_done(it[0], True)
                    if not fut.done():
                        fut.set_result(out)
                    if self._slo is not None:
                        # resolution latency = enqueue → dispatch-
                        # complete, the serve plane's own SLA clock
                        # since PR 5 (what the traffic harness
                        # measures too)
                        self._slo.record_ok(
                            it[5] or None,
                            (time.perf_counter() - it[2]) * 1e3)  # matlint: disable=ML006 SLO resolution-latency sample — lands in the slo plane's sketches and alert records
            if outs:
                self._inflight.append(outs)
            while len(self._inflight) > self.max_inflight:
                # backpressure: sync the OLDEST dispatched batch
                # before admitting more host-side planning
                try:
                    _sync(self._inflight.popleft())
                except IndexError:
                    break


def _fail(fut: Future, ex: BaseException) -> None:
    if not fut.done():
        fut.set_exception(ex)


def _sync_bounded(outs, rem: Optional[float]) -> bool:
    """Sync one dispatched batch within ``rem`` seconds (None = no
    bound). ``block_until_ready`` itself cannot be interrupted, so the
    bounded form runs it on a daemon helper and gives up on it after
    the budget — returning False so the caller can raise the typed
    ``DrainTimeout`` instead of hanging (the drain contract)."""
    if rem is None:
        _sync(outs)
        return True
    t = threading.Thread(target=_sync, args=(outs,),
                         name="matrel-serve-sync", daemon=True)
    t.start()
    t.join(rem)
    return not t.is_alive()


def _sync(outs) -> None:
    # sanctioned blocking point (utils/lockdep.py): syncing a batch
    # while holding any serve/fleet lock is the PR 8 drain-wedge class
    # — with the sanitizer on, a held unsanctioned lock diagnoses as
    # HeldAcrossDispatch. One flag check when off.
    lockdep.note_dispatch("serve.sync")
    for o in outs:
        try:
            o.data.block_until_ready()
        except Exception:  # a device-side error surfaces at the
            # consumer's own touch of the array; the pipeline only
            # needed the backpressure
            log.warning("serve: in-flight batch sync failed",
                        exc_info=True)
