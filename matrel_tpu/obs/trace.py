"""Structured tracing spans + the flight recorder — obs tier 2.

PR 1's event log says WHAT each query decided (strategies, estimated
bytes, cache outcomes); this module says WHERE THE TIME WENT: one
``span()`` context threaded through sql → compute (plan → compile →
dispatch) → fetch, the serve admission path and PageRank's host side,
emitting parent-linked records that share a ``qid`` per query.

Five cost tiers, strictly ordered:

- **Inactive** (no profiler session, ``obs_level="off"``, flight
  recorder off — the bench default): :func:`span` returns a shared
  no-op singleton — no allocation, no clock reads, no stack
  bookkeeping.
- **Cold** (always on; nothing turns it off or on): the sites that run
  only where a plan is MADE — a compile on a plan-cache miss and its
  phases, a host plan build, an upload, the slab's fill — open their
  span with :func:`phase`, which always times and always leaves one
  record in a second bounded ring (:func:`cold_spans`), and while it
  is open the thread's :func:`span` calls record there too,
  parent-linked. jax's own trace / lowering / backend-compile /
  compile-cache events land in the same ring by function name
  (:func:`hear_jax`). A query answered from a warm plan meets none of
  them: it stays on the inactive tier's path.
- **Profiler session** (a ``jax.profiler`` trace is running —
  ``start_trace`` around traffic, or ``start_server`` + a capture; no
  config change): every span is also a
  ``jax.profiler.TraceAnnotation("matrel.<name>", qid=...)`` on the
  host plane of the profiler's own trace — the device's clock by
  construction — and one record ``{name, start_ns, end_ns, span_id,
  parent_id, qid, tid, attrs}`` in a process-wide bounded ring
  (:func:`profile_spans`; ``time.time_ns()``, the profiler's host
  clock). No span adds a device sync but ``fetch.wait``, where
  ``device_get`` was about to make one. A pause of Python's collector
  is a span of this tier too (``gc``, :func:`_on_gc`).
- **Flight recorder only** (``config.obs_flight_recorder > 0``,
  obs off): spans are timed and appended to a bounded in-memory ring —
  no file I/O, no event assembly — so a field failure can dump the last
  N records as a post-mortem artifact.
- **Full** (``obs_level != "off"``): span records ALSO append to the
  JSONL event log (``kind: "span"``), where ``history`` and the chrome
  exporter (``python -m matrel_tpu trace --export chrome``) read them.

Activation is per-thread and per ENTRY CALL (``entry()``): the session
opens its sql/compute/batch span with its tracer, ``to_numpy`` and
``pagerank_edges`` keep the thread's, and the serve admission worker
opens its own in its own thread, so parent links never cross threads.
The entry span asks the profiler once whether a session runs
(``TraceAnnotation.is_enabled()``) and hands the answer down the
thread with the tracer; a span below it asks nothing.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import sys
import threading
import time
from typing import List, Optional

from matrel_tpu.obs.events import SCHEMA_VERSION
from matrel_tpu.utils import lockdep

_SPAN_SEQ = itertools.count(1)
_QID_SEQ = itertools.count(1)

#: Prefix of a span's name on the profiler's host plane and in the
#: :func:`profile_spans` ring (beside the benchmark's ``bench.*``).
PROFILE_PREFIX = "matrel."

_time_ns = time.time_ns
_perf_counter = time.perf_counter
_get_ident = threading.get_ident
_KEEP = object()
_trace_annotation = None


class _State:
    """One thread's tracing state, installed by its open entry span."""

    __slots__ = ("live", "tracer", "profiled", "current", "gc", "cold",
                 "jit_cache")

    def __init__(self):
        self.live = False       # tracer is not None, profiled, or cold
        self.tracer = None      # the entry call's Tracer
        self.profiled = False   # the entry call found a profiler session
        self.current = None     # the innermost open live Span
        self.gc = None          # the open span of a collection under way
        self.cold = False       # a cold span (phase()) is open
        self.jit_cache = None   # jax's cache verdict, until its jit.backend


class _ThreadState(threading.local):
    """Holds the thread's :class:`_State` (made on the thread's first
    read): one thread-local lookup per span, plain attribute reads
    after it."""

    def __init__(self):
        self.st = _State()


_tls = _ThreadState()


def _profiler_on() -> bool:
    """Whether a ``jax.profiler`` session is running (~0.1 us). jax is
    looked up, never imported: a process that has not imported it (the
    bench parents) has no session."""
    global _trace_annotation
    ta = _trace_annotation
    if ta is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        ta = _trace_annotation = jax.profiler.TraceAnnotation
    return ta.is_enabled()


class _NoopSpan:
    """The inactive-path singleton: enters/exits without touching the
    clock or the thread's state. ``dur_ms`` stays None — callers that
    need a duration unconditionally use :func:`timed` (or, on a cold
    path, :func:`phase`) instead."""

    __slots__ = ()
    dur_ms = None
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def elapsed_ms(self):
        return None


_NOOP = _NoopSpan()


class Span:
    """One timed scope. Parent-linked through the thread's innermost
    open span; emitted at exit through the owning tracer (when there is
    one) and, under a profiler session, as a ``TraceAnnotation`` and a
    record of the :func:`profile_spans` ring. A root span draws the
    ``qid`` its descendants carry. An ENTRY span (:func:`entry`) also
    installs its tracer and the profiler's answer for the thread while
    it is open. A COLD span (:func:`phase`) always leaves a record in
    the :func:`cold_spans` ring, and so does every span opened on the
    thread while it is open."""

    __slots__ = ("name", "attrs", "tracer", "profiled", "span_id",
                 "parent_id", "qid", "t0", "start_ns", "dur_ms",
                 "_annotation", "_profile_name", "_parent", "_outer",
                 "_st", "_cold")

    def __init__(self, name: str, tracer: Optional["Tracer"],
                 attrs: dict, profiled: bool, is_entry: bool,
                 st: _State, cold: bool = False):
        self.name = name
        self.tracer = tracer
        self.profiled = profiled
        self.attrs = attrs
        self._st = st
        self.span_id = None
        self.parent_id = None
        self.qid = None
        self.dur_ms = None
        self._outer = () if is_entry else None
        # a cold span keeps here what the thread's (live, cold) were
        self._cold = () if cold else None

    def __enter__(self):
        tracer, profiled = self.tracer, self.profiled
        tls = self._st
        if (tracer is not None or profiled or tls.cold
                or self._cold is not None):
            if self._outer is not None:
                self._outer = (tls.live, tls.tracer, tls.profiled)
                tls.live, tls.tracer, tls.profiled = True, tracer, profiled
            if self._cold is not None:
                # span() below records while this is open
                self._cold = (tls.live, tls.cold)
                tls.live = tls.cold = True
            parent = self._parent = tls.current
            tls.current = self
            self.span_id = next(_SPAN_SEQ)
            if parent is None:
                self.qid = next(_QID_SEQ)
            else:
                self.parent_id = parent.span_id
                self.qid = parent.qid
            if profiled:
                name = self._profile_name = PROFILE_PREFIX + self.name
                self._annotation = _trace_annotation(name, qid=self.qid)
                self._annotation.__enter__()
        self.start_ns = _time_ns()
        self.t0 = _perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ms = (_perf_counter() - self.t0) * 1e3
        if self.span_id is None:
            return False
        tls = self._st
        tls.current = self._parent
        to_cold = tls.cold      # this span's own, or one it lies beneath
        if self._cold is not None:
            tls.live, tls.cold = self._cold
        if self._outer is not None:
            tls.live, tls.tracer, tls.profiled = self._outer
        tid = _get_ident()
        if self.profiled or to_cold:
            end_ns = _time_ns()
            if self.profiled:
                self._annotation.__exit__(exc_type, exc, tb)
                # _RECORD_KEYS' order; profile_spans() makes the dict
                _ring_append((self._profile_name, self.start_ns, end_ns,
                              self.span_id, self.parent_id, self.qid, tid,
                              self.attrs))
            if to_cold:
                _cold_append((self.name, self.start_ns, end_ns,
                              self.span_id, self.parent_id, self.qid, tid,
                              self.attrs))
        if self.tracer is not None:
            rec = {"name": self.name,
                   "span_id": self.span_id,
                   "parent_id": self.parent_id,
                   "qid": self.qid,
                   "t0": round(self.start_ns * 1e-9, 6),
                   "dur_ms": round(self.dur_ms, 3),
                   "pid": os.getpid(),
                   "tid": tid}
            if exc_type is not None:
                # the error rides the span so a flight-recorder dump
                # shows WHICH scope died, not just that something did
                rec["error"] = repr(exc)[:200]
            if self.attrs:
                rec["attrs"] = self.attrs
            self.tracer.emit_span(rec)
        return False

    @property
    def live(self) -> bool:
        """Whether the open span records anything: false for the no-op
        singleton and for a :func:`timed` that only times. A site asks
        before work done for the record's sake (``to_numpy``'s split)."""
        return self.span_id is not None

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-scope (e.g. cache hit)."""
        self.attrs.update(attrs)
        return self

    def elapsed_ms(self) -> float:
        """Wall milliseconds since enter — readable BEFORE exit (the
        serve batch reports its wall while still inside the span)."""
        return (_perf_counter() - self.t0) * 1e3


def entry(name: str, tracer=_KEEP, **attrs):
    """The span an ENTRY call (``session.sql``, ``compute``, a batch,
    the admission worker, ``to_numpy``, ``pagerank_edges``) wraps
    itself in. It asks the profiler, once, whether a session runs, and
    while it is open the thread's spans go to ``tracer`` (no argument:
    the thread's own) and to the profiler's trace accordingly; a
    :func:`span` below it asks nothing. With neither a tracer nor a
    session — the default deployment — it is the no-op singleton: no
    object, no clock read, no thread-local write."""
    profiled = _profiler_on()
    if tracer is None and not profiled:
        return _NOOP
    st = _tls.st
    if tracer is _KEEP:
        tracer = st.tracer
        if tracer is None and not profiled:
            return _NOOP
    if profiled and not _gc_watched:
        _watch_gc()
    return Span(name, tracer, attrs, profiled, True, st)


def span(name: str, **attrs):
    """A span that costs NOTHING when the entry call above it found
    neither a tracer nor a profiler session (the obs-off / recorder-off
    / profiler-off contract). Use everywhere the duration is purely
    observational."""
    st = _tls.st
    if not st.live:
        return _NOOP
    return Span(name, st.tracer, attrs, st.profiled, False, st)


def timed(name: str, **attrs) -> Span:
    """A span that ALWAYS times (``dur_ms`` readable after exit) and
    emits only under a live entry span — for a duration that feeds a
    record regardless of observability on a path every query takes
    (``query.execute``): never a cold span, so a served query leaves
    nothing in the :func:`cold_spans` ring."""
    st = _tls.st
    return Span(name, st.tracer, attrs, st.profiled, False, st)


def phase(name: str, **attrs) -> Span:
    """A COLD span: for the sites that run only where a plan is made
    (the executor's compile phases, a host plan build, an upload, the
    slab's fill). It ALWAYS times (``dur_ms`` readable after exit: the
    phases' durations feed ``plan.meta`` regardless of observability),
    emits to the tracer and the profiler's trace under a live entry
    span as :func:`span` does, and ALWAYS leaves one record in the
    :func:`cold_spans` ring; while it is open, :func:`span` on the
    thread returns recording spans too (children of it in that ring),
    whether or not a session runs. Costs a :class:`Span`, three clock
    reads and one tuple append: never put it on a warm query's path."""
    st = _tls.st
    return Span(name, st.tracer, attrs, st.profiled, False, st, True)


class _Part:
    """See :func:`part`."""

    __slots__ = ("key", "attrs", "t0")

    def __init__(self, key: str, attrs: dict):
        self.key = key
        self.attrs = attrs

    def __enter__(self):
        self.t0 = _perf_counter()
        return self

    def __exit__(self, *exc):
        self.attrs[self.key] = round(
            self.attrs.get(self.key, 0.0) + _perf_counter() - self.t0, 4)
        return False


def part(key: str):
    """A scope whose seconds are ADDED to attribute ``key`` of the
    thread's innermost open span where a cold span is open: a part of a
    build that is worth a number and no name of its own
    (``build_spmv_plan``'s ``fill_s`` and ``hub_walks_s`` on its
    caller's ``*.plan.build`` record; several builds under one record
    add up). The no-op singleton otherwise."""
    st = _tls.st
    return _Part(key, st.current.attrs) if st.cold else _NOOP


#: A pause of Python's cyclic collector, as a span of the profiler
#: tier. The name's presence tells a reader that this program records
#: them at all.
GC_SPAN = "gc"
_gc_watched = False
_gc_watch_lock = lockdep.make_lock("obs.gc_watch")


def _watch_gc() -> None:
    """Put :func:`_on_gc` into ``gc.callbacks``: the first
    :func:`entry` of a process that finds a profiler session, and the
    first after the callback took itself out. Nothing is installed at
    import or while dark."""
    global _gc_watched
    with _gc_watch_lock:
        if not _gc_watched:
            # the flag first: _on_gc clears it only after the append
            _gc_watched = True
            gc.callbacks.append(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: under a profiler session a collection is
    an entry span ``gc`` (``generation``, ``collected``) on the
    collecting thread, a child of whatever span it interrupted or,
    between two queries, a root. It goes to the profiler's trace and
    the ring alone, never through a tracer: a collection starts at any
    bytecode boundary, also inside the event log's or the flight ring's
    critical section, and a sink that took that lock again would never
    return. A collection that finds no session takes the callback out."""
    global _gc_watched
    st = _tls.st
    if phase == "start":
        sp = entry(GC_SPAN, None, generation=info["generation"])
        if sp is _NOOP:
            # the interpreter walks gc.callbacks by index: taking out
            # any but the last would skip the one after it this once,
            # so behind a later registrant this stays, at one dark
            # entry() a collection
            if gc.callbacks[-1] is _on_gc:
                gc.callbacks.pop()
                _gc_watched = False
            return
        st.gc = sp.__enter__()
    elif st.gc is not None:
        sp, st.gc = st.gc, None
        sp.set(collected=info["collected"]).__exit__(None, None, None)


class Tracer:
    """Routes finished span records to the session's emission path
    (event log when obs is on, flight-recorder ring when configured —
    the session's ``_obs_emit`` decides). Never raises: a broken sink
    must not fail the scope it was observing."""

    __slots__ = ("_emit_fn",)

    def __init__(self, emit_fn):
        self._emit_fn = emit_fn

    def emit_span(self, rec: dict) -> None:
        try:
            self._emit_fn("span", rec)
        except Exception:  # matlint: disable=ML007 never-fail obs sink — a broken emitter must not fail the observed scope (and logging here could recurse per span)
            pass


class FlightRecorder:
    """Bounded in-memory ring of the last N span/event records —
    always-cheap (a deque append under a lock; no I/O, no assembly),
    independent of ``obs_level``. Dumped to a JSON artifact on
    ``VerificationError`` / compile failure / serve-batch failure or
    an explicit ``session.dump_flight_recorder()``, so a field failure
    leaves a post-mortem trail instead of a bare error string."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._buf: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        self._lock = lockdep.make_lock("obs.flight_ring")
        self.dumps = 0

    def add(self, record: dict) -> None:
        with self._lock:
            self._buf.append(record)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._buf)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def dump(self, path: str, reason: str,
             error: Optional[str] = None) -> str:
        """Write the ring as one JSON artifact (atomic rename, same
        discipline as the autotune table). Returns the path."""
        artifact = {
            "schema": SCHEMA_VERSION,
            "kind": "flight_recorder",
            "dumped_at": round(time.time(), 3),
            "reason": reason,
            "error": error,
            "capacity": self.capacity,
            "records": self.snapshot(),
        }
        self.dumps += 1
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(artifact, f, default=repr)
        os.replace(tmp, path)
        return path


#: Spans recorded while a profiler session ran, process-wide: a traced
#: window of some thousand queries fits; an older record falls out.
PROFILE_RING_CAPACITY = 16384
_PROFILE_RING = FlightRecorder(PROFILE_RING_CAPACITY)
# A span's exit appends a tuple straight to the ring's deque (one C
# call, atomic under the interpreter's lock like the snapshot's copy):
# the recorder's own lock and the dict cost a live span a tenth of its
# time, and the reader can pay for the dict.
_ring_append = _PROFILE_RING._buf.append
_RECORD_KEYS = ("name", "start_ns", "end_ns", "span_id", "parent_id",
                "qid", "tid", "attrs")


def profile_spans() -> List[dict]:
    """Snapshot of the profiler tier's ring, oldest first: one record
    ``{name, start_ns, end_ns, span_id, parent_id, qid, tid, attrs}`` per
    span that ended while a ``jax.profiler`` session ran. ``name`` is
    the host-plane event's (``matrel.<name>``); the times are
    ``time.time_ns()``, which is the trace's host clock plus the
    session's start."""
    return [dict(zip(_RECORD_KEYS, r)) for r in _PROFILE_RING.snapshot()]


#: The cold tier's records, process-wide. A process's set-up makes 65
#: to 600 where no Pallas kernel is traced and 1,300 to 7,300 where one
#: is (the benchmark's cells on a v5e, PR 52): every function jax traces,
#: lowers, compiles or loads is three or four records, and a kernel's
#: body is some hundred jitted ``jnp`` calls, each a ``jit.trace`` of
#: its own inside ``pallas_call``'s. The OLDEST falls out: a process that
#: goes on making plans or matrices for hours keeps its newest 16,384
#: records and loses its start-up's first (and a record that outlived
#: its fallen children then reads a self time that was theirs).
COLD_RING_CAPACITY = 16384
_COLD_RING = FlightRecorder(COLD_RING_CAPACITY)
# as _ring_append: one C call, no lock, so a listener of jax's or a
# collection's callback may append from wherever it is called
_cold_append = _COLD_RING._buf.append


def cold_spans() -> List[dict]:
    """Snapshot of the cold tier's ring, oldest first: one record
    ``{name, start_ns, end_ns, span_id, parent_id, qid, tid, attrs}``
    (``time.time_ns()``; ``name`` bare, no ``matrel.`` prefix: no
    profiler's plane is shared here) per :func:`phase` span, per
    :func:`span` that ended beneath an open one, and per event of
    jax's that :func:`hear_jax` listens for — whether or not a tracer
    or a profiler session ran. What a process spent before it could
    answer its first query, from the inside."""
    return [dict(zip(_RECORD_KEYS, r)) for r in _COLD_RING.snapshot()]


# jax's own account of a compile (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py): the three time spans carry ``fun_name``, start
# and end on time.time(); the cache's verdict comes as an event and two
# durations INSIDE the backend-compile span, before that span is told.
_JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.backend",
}
_JIT_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": True,
    "/jax/compilation_cache/cache_misses": False,
}
_JIT_CACHE_SECS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
_hear_once = itertools.count()
_jax_heard = False


def hear_jax() -> None:
    """Listen, from now on and once a process however often it is
    called, to what ``jax.monitoring`` tells of a function's trace,
    lowering and backend compile (records ``jit.trace``, ``jit.lower``,
    ``jit.backend`` of the cold ring: jax's start and end,
    ``fun_name``) and of the persistent compile cache (``jit.cache``:
    ``hit``, ``retrieval_s``, ``saved_s``, ``fun_name``; zero length, a
    child of the ``jit.backend`` it fell inside). ``parent_id`` is the
    thread's innermost open span where a cold span is open, else null.
    jax calls a listener only where it traces, lowers or compiles,
    never on a cached call's path. An event name this does not know is
    ignored: a jax that renames one loses a record, not a query."""
    global _jax_heard
    if _jax_heard or next(_hear_once):
        return      # done, or another thread is about to
    from jax import monitoring
    monitoring.register_event_time_span_listener(_on_jax_span)
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_secs)
    _jax_heard = True


# The three listeners never raise (jax calls them inside a compile) and
# take no lock (the rule _on_gc follows): a thread-local read, a dict
# lookup, a deque append.

def _on_jax_span(event: str, start: float, end: float, **kw) -> None:
    try:
        name = _JIT_SPANS.get(event)
        if name is None:
            return
        st = _tls.st
        parent = st.current if st.cold else None
        parent_id, qid = ((None, None) if parent is None
                          else (parent.span_id, parent.qid))
        span_id, tid = next(_SPAN_SEQ), _get_ident()
        fun = kw.get("fun_name")
        _cold_append((name, int(start * 1e9), int(end * 1e9), span_id,
                      parent_id, qid, tid, {"fun_name": fun}))
        if name == "jit.backend":
            cache, st.jit_cache = st.jit_cache, None
            if cache is not None:
                at = cache.pop("at_ns")
                cache["fun_name"] = fun
                _cold_append(("jit.cache", at, at, next(_SPAN_SEQ),
                              span_id, qid, tid, cache))
    except Exception:  # matlint: disable=ML007 never-fail obs sink — jax calls this inside a compile, which a broken record must not fail
        pass


def _on_jax_event(event: str, **kw) -> None:
    try:
        hit = _JIT_CACHE_EVENTS.get(event)
        if hit is not None:
            _tls.st.jit_cache = {"hit": hit, "at_ns": _time_ns()}
    except Exception:  # matlint: disable=ML007 never-fail obs sink — as _on_jax_span
        pass


def _on_jax_secs(event: str, secs: float, **kw) -> None:
    try:
        key = _JIT_CACHE_SECS.get(event)
        if key is not None:
            cache = _tls.st.jit_cache
            if cache is not None:
                cache[key] = secs
    except Exception:  # matlint: disable=ML007 never-fail obs sink — as _on_jax_span
        pass


#: Default flight-recorder artifact name (cwd-relative, like the event
#: log's default).
DEFAULT_FLIGHT_PATH = ".matrel_flight.json"


# ---------------------------------------------------------------------------
# Chrome/Perfetto export — spans → trace_event JSON
# ---------------------------------------------------------------------------


def chrome_trace(events: List[dict], last: Optional[int] = None) -> dict:
    """Render span records as a Chrome ``trace_event`` JSON object
    (the "JSON Array Format" with complete "X" events) loadable in
    Perfetto / chrome://tracing. Nesting comes from per-tid timestamp
    containment — exactly how the spans nested live — and every event's
    args carry the explicit span/parent ids for cross-checking.

    ``last`` keeps only the most recent N ROOT spans (parent_id null)
    plus their descendants — "show me the last serve batch" without
    hand-filtering a long log."""
    spans = [e for e in events if e.get("kind") == "span"
             and isinstance(e.get("dur_ms"), (int, float))]
    spans.sort(key=lambda e: e.get("t0") or 0.0)
    if last is not None and last >= 0:
        # span ids are per-PROCESS sequences (a shared log mixes
        # sessions, drills and bench runs by design), so the root
        # selection and the descendant closure must key by
        # (pid, span_id) — a bare span_id would pull an unrelated
        # earlier process's spans into "the last batch"
        def sid(e):
            return (e.get("pid"), e.get("span_id"))

        roots = [sid(e) for e in spans
                 if e.get("parent_id") is None
                 and e.get("span_id") is not None]
        keep = set(roots[-last:] if last > 0 else [])
        # descend: children name their parent, so iterate to fixpoint
        # (span lists are small; the log reader already bounded them)
        grew = True
        while grew:
            grew = False
            for e in spans:
                if ((e.get("pid"), e.get("parent_id")) in keep
                        and sid(e) not in keep):
                    keep.add(sid(e))
                    grew = True
        spans = [e for e in spans if sid(e) in keep]
    trace_events = []
    for e in spans:
        t0 = e.get("t0")
        if not isinstance(t0, (int, float)):
            # older/foreign record: reconstruct start from the emission
            # timestamp (stamped at exit)
            t0 = float(e.get("ts", 0.0)) - e["dur_ms"] / 1e3
        args = {"span_id": e.get("span_id"),
                "parent_id": e.get("parent_id")}
        if e.get("attrs"):
            args.update(e["attrs"])
        if e.get("error"):
            args["error"] = e["error"]
        trace_events.append({
            "name": e.get("name", "span"),
            "cat": "matrel",
            "ph": "X",
            "ts": round(t0 * 1e6, 3),          # epoch microseconds
            "dur": round(e["dur_ms"] * 1e3, 3),
            "pid": e.get("pid", 0),
            "tid": e.get("tid", 0),
            "args": args,
        })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def main(args) -> int:
    """CLI backend for ``python -m matrel_tpu trace --export chrome``.
    Path precedence matches ``history``: --log beats
    $MATREL_OBS_EVENT_LOG beats the cwd default."""
    from matrel_tpu.obs.events import read_events, resolve_path
    if args.export != "chrome":
        print(f"unknown export format {args.export!r} "
              f"(supported: chrome)")
        return 2
    path = resolve_path(args.log or os.environ.get(
        "MATREL_OBS_EVENT_LOG"))
    events = read_events(path)
    doc = chrome_trace(events, last=args.last)
    out_path = args.out or (path + ".chrome.json")
    if out_path == "-":
        print(json.dumps(doc))
        return 0
    with open(out_path, "w") as f:
        json.dump(doc, f)
    print(json.dumps({"spans": len(doc["traceEvents"]),
                      "log": path, "out": out_path}))
    return 0
