"""Structured tracing spans + the flight recorder — obs tier 2.

PR 1's event log says WHAT each query decided (strategies, estimated
bytes, cache outcomes); this module says WHERE THE TIME WENT: one
``span()`` context threaded through sql → compute (plan → compile →
dispatch) → fetch, the serve admission path and PageRank's host side,
emitting parent-linked records that share a ``qid`` per query.

Four cost tiers, strictly ordered:

- **Inactive** (no profiler session, ``obs_level="off"``, flight
  recorder off — the bench default): :func:`span` returns a shared
  no-op singleton — no allocation, no clock reads, no stack
  bookkeeping. ``phase()`` (the executor's compile-phase form) still
  reads the clock because its durations feed ``plan.meta`` regardless
  of observability.
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

    __slots__ = ("live", "tracer", "profiled", "current", "gc")

    def __init__(self):
        self.live = False       # tracer is not None or profiled
        self.tracer = None      # the entry call's Tracer
        self.profiled = False   # the entry call found a profiler session
        self.current = None     # the innermost open live Span
        self.gc = None          # the open span of a collection under way


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
    need a duration unconditionally use :func:`phase` instead."""

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
    it is open."""

    __slots__ = ("name", "attrs", "tracer", "profiled", "span_id",
                 "parent_id", "qid", "t0", "start_ns", "dur_ms",
                 "_annotation", "_profile_name", "_parent", "_outer",
                 "_st")

    def __init__(self, name: str, tracer: Optional["Tracer"],
                 attrs: dict, profiled: bool, is_entry: bool,
                 st: _State):
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

    def __enter__(self):
        tracer, profiled = self.tracer, self.profiled
        if tracer is not None or profiled:
            tls = self._st
            if self._outer is not None:
                self._outer = (tls.live, tls.tracer, tls.profiled)
                tls.live, tls.tracer, tls.profiled = True, tracer, profiled
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
        if self._outer is not None:
            tls.live, tls.tracer, tls.profiled = self._outer
        tid = _get_ident()
        if self.profiled:
            end_ns = _time_ns()
            self._annotation.__exit__(exc_type, exc, tb)
            # _RECORD_KEYS' order; profile_spans() makes the dict
            _ring_append((self._profile_name, self.start_ns, end_ns,
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
        singleton and for a :func:`phase` that only times. A site asks
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


def phase(name: str, **attrs) -> Span:
    """A span that ALWAYS times (``dur_ms`` readable after exit) and
    emits only under a live entry span — for the executor's compile
    phases, whose durations feed ``plan.meta`` regardless of
    observability (the pre-span behaviour, one mechanism)."""
    st = _tls.st
    return Span(name, st.tracer, attrs, st.profiled, False, st)


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
