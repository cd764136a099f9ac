"""PageRank power iteration — reference workload (SURVEY.md §3.5,
BASELINE.md row 5: 1M-node adjacency, 30 matvec rounds).

Reference execution: a driver-side loop; every round is one optimized plan
execution and one Spark shuffle — the shuffle dominates. TPU rebuild: the
WHOLE loop is one jitted ``lax.fori_loop``; the matvec's psum rides ICI and
there is no host round trip between rounds (SURVEY.md §3.5 🔥 note).
"""

from __future__ import annotations

import functools
import logging
import weakref
from typing import NamedTuple, Optional

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from matrel_tpu.config import MatrelConfig, on_tpu
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.obs import trace as trace_lib

log = logging.getLogger("matrel_tpu.pagerank")


def pagerank(A: BlockMatrix, rounds: int = 30, alpha: float = 0.85,
             config: Optional[MatrelConfig] = None) -> jax.Array:
    """r ← α·Âᵀ·r + (1-α)/N, iterated ``rounds`` times inside one program.

    A is the (row-stochastic-normalisable) adjacency matrix: A[i, j] = 1 for
    an edge i→j. Dangling nodes (zero out-degree) redistribute uniformly.
    Returns the rank vector as a replicated (N, 1) array.
    """
    n = A.shape[0]
    if A.shape[0] != A.shape[1]:
        raise ValueError(f"adjacency must be square, got {A.shape}")
    mesh = A.mesh
    pn = A.padded_shape[0]
    out_sharding = NamedSharding(mesh, P())

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def run(ad):
        valid_row = (jnp.arange(pn) < n)[:, None]
        deg = jnp.sum(ad, axis=1, keepdims=True)               # out-degree
        inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1e-30), 0.0)
        dangling = (valid_row & (deg == 0)).astype(ad.dtype)
        r0 = jnp.where(valid_row, 1.0 / n, 0.0).astype(ad.dtype)
        teleport = (1.0 - alpha) / n

        def body(_, r):
            # contribution along edges: Âᵀ·r with Â = D⁻¹A (row-normalised)
            contrib = jnp.einsum("ij,ik->jk", ad, inv_deg * r,
                                 precision=jax.lax.Precision.HIGHEST)
            # dangling mass redistributes uniformly over real nodes
            dmass = jnp.sum(dangling * r)
            r_new = alpha * (contrib + dmass / n) + teleport
            return jnp.where(valid_row, r_new, 0.0)

        r = jax.lax.fori_loop(0, rounds, body, r0)
        return jax.lax.with_sharding_constraint(r, out_sharding)

    return run(A.data)[:n]


def pagerank_edges(src: jax.Array, dst: jax.Array, n: int,
                   rounds: int = 30, alpha: float = 0.85,
                   mesh=None, impl: str = "auto",
                   weights=None, passes: int = 3) -> jax.Array:
    """PageRank over an edge list — the BASELINE row-5 scale (1M nodes).

    A dense or block-sparse 1M×1M adjacency is off the table (4 TB dense;
    uniform-random graphs touch every 512² block). The TPU-idiomatic sparse
    matvec for graphs is gather/segment-sum over the edge arrays:

        contrib[j] = Σ_{(i,j)∈E} r[i] / outdeg[i]

    The whole 30-round loop is one jitted fori_loop, no host round trips.
    On the segment path the edges are device-resident int32 arrays (10M
    edges = 80 MB) and may be sharded over the mesh (segment_sum psums
    over ICI). The one-hot and compact paths build their plan on the
    host, once per graph: a later call on the same graph finds it by
    comparing the edge arrays, every element, with the copy the plan
    keeps (so an in-place edit of one edge is seen), or by identity
    when it is handed the same ``jax.Array`` objects (no pull to the
    host). Arrays that agree with a cached plan in their first chunk
    are launched on that plan FIRST and compared in full while the
    device iterates (~8 ms at 10M edges, ~98 ms at 128M, under runs of
    0.6 and 1.3 s); ranks launched for a graph that then differs are
    dropped, never returned, and the call builds the plan of the arrays
    it was handed (:func:`recognition_counts`). On one device the
    compact executor takes a skewed
    graph too (PR 33): its plan lies in fixed chunks of slots, a hub
    block owning many (``build_spmv_plan`` ``layout="auto"``: no
    overflow COO, ~1.04 slots an edge on a Graph500 Kronecker graph),
    the matvec runs in panels that fit the device, and the plan gate
    and cache reckon its real bytes; where the sources are skewed as
    well, the edges from the sources of largest out-degree take their
    rank from a table in VMEM inside the scatter kernel (PR 36: the
    plan's hub chunks, chosen by the build from the degrees; PR 42: a
    register of slots walks only the table rows it names);
    :func:`last_plan` says what ran.
    """
    if impl not in ("auto", "segment", "onehot"):
        raise ValueError(f"unknown impl {impl!r}")
    trace_lib.hear_jax()    # no session here to have asked; once a process
    with trace_lib.entry("pagerank") as sp:
        _LAST_PLAN.clear()
        out, path = _pagerank_edges(src, dst, n, rounds, alpha, mesh,
                                    impl, weights, passes)
        _PATH_COUNTS[path] += 1
        _LAST_PLAN["impl"] = path
        sp.set(impl=path)
        return out


# How many pagerank_edges calls each executor answered, process-wide.
_PATH_COUNTS = dict.fromkeys(
    ("compact", "compact_sharded", "onehot", "onehot_sharded", "segment"),
    0)


# What the newest pagerank_edges call ran on: ``impl`` and, where a
# prepared plan answered, what its ``matrel.pagerank.plan`` span carries.
_LAST_PLAN: dict = {}


def last_plan() -> dict:
    """The executor (``impl``) and the prepared plan's layout
    (:func:`_plan_attrs`: ``layout``, ``edges``, ``slots``, ``chunks``,
    ``chunk``, ``overflow_edges``, ``row_values``, ``panels``,
    ``plan_bytes``, ``hubs``, ``hub_slots``, ``hub_chunks``,
    ``hub_walk_rows``, ``hit``, and ``recognised``: the call's key of
    :func:`recognition_counts`; on
    a build by the one-device path also
    ``build_s`` and ``upload_s``) of the newest :func:`pagerank_edges` call
    — what its ``matrel.pagerank`` / ``matrel.pagerank.plan`` spans say
    under a profiler session, for a caller outside one. A copy."""
    return dict(_LAST_PLAN)


def path_counts() -> dict:
    """Calls of :func:`pagerank_edges` by the executor that answered:
    ``compact`` / ``compact_sharded`` (compact tables through the Pallas
    SpMV), ``onehot`` / ``onehot_sharded`` (expanded tables, XLA),
    ``segment`` (gather + segment-sum) — the ``impl`` the
    ``matrel.pagerank`` span carries. A copy."""
    return dict(_PATH_COUNTS)


# How each pagerank_edges call that went to the prepared-plan cache knew
# its graph, process-wide.
_RECOGNISED = dict.fromkeys(
    ("confirmed", "discarded", "compared_first", "identity"), 0)


def recognition_counts() -> dict:
    """Calls of :func:`pagerank_edges` by how the prepared-plan cache
    knew their graph: ``confirmed`` (launched on a cached plan whose
    first chunks agreed, found equal in full while the device ran:
    the comparison cost the caller no time), ``discarded`` (launched
    so, found edited: the launched ranks were dropped unseen and the
    call went on to the plan of the arrays it was handed),
    ``compared_first`` (nothing cached agreed in its first chunk: a
    new graph, built before any launch) and ``identity`` (the very
    ``jax.Array`` objects a plan was built from: nothing compared).
    The segment-sum path has no plan and counts nowhere. A copy."""
    return dict(_RECOGNISED)


def _pagerank_edges(src, dst, n, rounds, alpha, mesh, impl, weights,
                    passes):
    """(ranks, the executor that ran) — pagerank_edges under its span."""
    if impl == "onehot":
        # explicit choice: any backend; with mesh= the sharded variant
        # (plan tables row-decomposed over every device)
        if not (_host_fetchable(src) and _host_fetchable(dst)):
            raise ValueError(
                "impl='onehot' builds its plan on the host; edge arrays "
                "sharded across non-addressable devices need "
                "impl='segment'")
        if mesh is not None:
            from matrel_tpu.config import pallas_enabled
            if pallas_enabled():
                path = "compact_sharded"
                out = _pagerank_compact_sharded(
                    src, dst, n, rounds, alpha, mesh, max_slots=None,
                    weights=weights, passes=passes)
            else:
                path = "onehot_sharded"
                out = _pagerank_onehot_sharded(src, dst, n, rounds,
                                               alpha, mesh,
                                               max_slots=None,
                                               weights=weights)
        else:
            path = _single_device_path()
            out = _pagerank_onehot(src, dst, n, rounds, alpha,
                                   weights=weights, passes=passes)
        if out is None:
            raise ValueError(
                "impl='onehot' requested but the graph's degree "
                "distribution is too heavy-tailed for the one-hot plan "
                "(build_spmv_plan refused); use impl='segment' or 'auto'")
        return out, path
    if impl == "auto":
        # The one-hot MXU matvec (ops/spmv.py) beats segment_sum ~5× on
        # TPU; on CPU the extra one-hot FLOPs lose, so auto keeps the
        # segment path there. The plan build is host-side numpy, so
        # edge arrays sharded across non-addressable (multi-host) devices
        # stay on the segment path. Falls back when the degree
        # distribution is too heavy-tailed to pad, or when the expanded
        # tables would exceed the per-device HBM budget (~224 B/slot
        # expanded, ~17 B/slot compact — _auto_max_slots picks;
        # the cap keeps auto from OOMing on huge graphs that the
        # 8 B/edge segment path handles fine).
        if on_tpu() and _host_fetchable(src) and _host_fetchable(dst):
            why = []        # what build_spmv_plan refused the graph for
            if mesh is not None:
                from matrel_tpu.config import pallas_enabled
                if pallas_enabled():
                    path = "compact_sharded"
                    out = _pagerank_compact_sharded(
                        src, dst, n, rounds, alpha, mesh,
                        max_slots=_auto_max_slots() * mesh.size,
                        weights=weights, passes=passes, refusals=why)
                else:
                    path = "onehot_sharded"
                    out = _pagerank_onehot_sharded(
                        src, dst, n, rounds, alpha, mesh,
                        max_slots=_PLAN_CACHE_MAX_SLOTS * mesh.size,
                        weights=weights, refusals=why)
            else:
                path = _single_device_path()
                out = _pagerank_onehot(src, dst, n, rounds, alpha,
                                       max_slots=_auto_max_slots(),
                                       weights=weights, passes=passes,
                                       refusals=why)
            if out is not None:
                return out, path
            log.warning(
                "pagerank_edges: the one-hot plan refused this graph (%s); "
                "running the segment-sum path", "; ".join(why) or
                "build_spmv_plan returned None")
    src = jnp.asarray(src, dtype=jnp.int32)
    dst = jnp.asarray(dst, dtype=jnp.int32)
    w = (jnp.ones_like(src, dtype=jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    prepare, run = _edges_runner(int(n), int(rounds), float(alpha))
    src, dst, w = prepare(src, dst, w)
    return _dispatch(run, src, dst, w), "segment"


def _single_device_path() -> str:
    """What _pagerank_onehot runs: the compact tables through the
    Pallas SpMV where Pallas is on, the expanded tables otherwise."""
    from matrel_tpu.config import pallas_enabled
    return "compact" if pallas_enabled() else "onehot"


def _dispatch(run, *args):
    """The call of a jitted round loop, until it returns (the device
    runs on)."""
    with trace_lib.span("pagerank.dispatch"):
        return run(*args)


def prepare_pagerank_onehot(src, dst, n: int, max_slots: int = None,
                            weights=None, layout: str = "blocks",
                            refusals: Optional[list] = None):
    """Build the one-hot SpMV plan for a graph (ops/spmv.py), reusable
    across pagerank runs — plan construction is the expensive, per-graph
    step (host sort + pad, one device table expansion).

    The contribution matvec is contrib = Âᵀ·r with Â[i,j] = w_ij/outdeg_w
    [i] for each edge i→j (w ≡ 1 unweighted) — so the plan is rows=dst,
    cols=src, vals=w/outdeg_w[src]; the normalisation rides the
    gather-select table for free. Returns (plan, dangling_mask), or None
    when the plan refuses the graph (its padding, or ``max_slots``: the
    reason is appended to ``refusals``). ``layout`` is
    ``build_spmv_plan``'s: ``"auto"`` only for the compact executor on
    one device, which alone walks chunks.
    """
    from matrel_tpu.ops import spmv as spmv_lib

    src_np = np.asarray(src, dtype=np.int64)
    dst_np = np.asarray(dst, dtype=np.int64)
    if weights is None:
        w = np.ones(src_np.shape, np.float32)
    else:
        w = np.asarray(weights, dtype=np.float32)
    outdeg = np.bincount(src_np, weights=w,
                         minlength=n).astype(np.float32)
    # epsilon (not 1.0) floor: weighted out-masses below 1 must not be
    # clamped or the ranks skew
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1e-30), 0.0)
    plan = spmv_lib.build_spmv_plan(dst_np, src_np,
                                    vals=w * inv[src_np],
                                    n_rows=n, n_cols=n,
                                    max_slots=max_slots, layout=layout,
                                    refusals=refusals)
    if plan is None:
        return None
    dangling = jnp.asarray((outdeg == 0).astype(np.float32))
    return plan, dangling


def run_pagerank_onehot(prepared, rounds: int = 30,
                        alpha: float = 0.85) -> jax.Array:
    """Execute PageRank rounds over a prepared one-hot plan."""
    if prepared is None:
        raise ValueError(
            "prepare_pagerank_onehot returned None for this graph "
            "(degree distribution too heavy-tailed for the one-hot "
            "plan); use the segment-sum path instead")
    plan, dangling = prepared
    run = _onehot_runner(plan.n_rows, int(rounds), float(alpha),
                         (plan.n_rows, plan.n_cols, plan.block),
                         len(plan.arrays()))
    return _dispatch(run, plan.arrays(), dangling)


def run_pagerank_compact(prepared, rounds: int = 30, alpha: float = 0.85,
                         passes: int = 2,
                         interpret=None) -> jax.Array:
    """PageRank rounds over the compact-table Pallas SpMV
    (ops/pallas_spmv.py): ~14× smaller device tables than the expanded
    plan and faster on real TPU (measured 18.8 ms vs 29.4 per matvec at
    BASELINE row-5 scale). ``passes`` trades round fidelity for speed:
    2 → ~2^-16 relative error per matvec (ranking-grade), 3 → ~f32."""
    if prepared is None:
        raise ValueError(
            "prepare_pagerank_onehot returned None for this graph; "
            "use the segment-sum path instead")
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib
    plan, dangling = prepared
    from matrel_tpu.config import resolve_interpret
    interpret = resolve_interpret(interpret)
    tables = pc.compact_tables(plan)
    ov = plan.overflow
    run = _compact_runner_loop(plan.n_rows, int(rounds), float(alpha),
                               (plan.n_rows, plan.n_cols, plan.block,
                                spmv_lib.LO),
                               len(ov), int(passes), bool(interpret))
    return _dispatch(run, tables, ov, dangling)


# Prepared-plan cache for the auto path: repeated pagerank_edges calls on
# the same graph (alpha/round sweeps) must not repay the host sort + table
# transfer. An entry recognises its graph by COMPARISON with a private
# int32 (weights: float32) copy of the arrays it was built from, taken
# once, when the plan is cached. Equality, not a digest or a sample: a
# graph edited in one edge, in place or in a new array, is rebuilt, and
# an equal graph in another array or index dtype hits. The comparison
# runs in chunks, with an early exit and no temporary beyond a chunk
# whatever the dtype or strides: a repeated 10M-edge call reads 80 MB
# against 80 MB (7.9 ms on the v5e's host, PERF.md §6 PR 26; no copy, no
# hash), a Graph500 scale-22 call 1.03 GB (98 ms). A numpy array is
# mutable and is always compared; a jax.Array is not, so the very object
# an entry was built from (held weakly) hits by identity with no
# device→host pull — any other jax.Array is pulled to be compared.
# WHEN it is compared (PR 53; _cached_plan): the comparison needs nothing
# of the device and, where the graph is the cached one, the device needs
# nothing of it, so they overlap. A PROBE walks the entries of the same
# (n, sizes, weighted, mesh…) key and compares the first chunk of each
# array (~0.1 ms an entry: a different graph of the same size fails
# here, and nothing is launched for it); the first entry that passes is
# LAUNCHED at once (jax's dispatch returns in ~1 ms, the device starts
# its rounds) and the rest of every array is compared on the calling
# thread meanwhile. Confirmed, the launched ranks are the call's answer,
# and the comparison cost it max(0, compare − run). Not confirmed (an
# edit past the first chunk), they are dropped — never returned, counted
# nowhere but recognition_counts()["discarded"] — and the call goes on
# as if it had compared first: the later entries in full, before any
# second launch (one speculation a call), else a build. A dropped run
# occupies the device for max(0, run − build) longer than the call
# would have: it executes while the host builds the new plan (16.9 s for
# the Graph500 plan, 0.9 s for the 1M one, against runs of 1.26 and
# 0.63 s), and its output is n floats. What a call returns is always the
# ranks of the arrays it was handed, compared in full.
# Host memory: the copies are 8 B an edge (12 with weights) per
# cached graph, beside the ~13 B a slot of compact host tables its plan
# already keeps; a plan has about a slot an edge or more, so the budget
# below holds all single-device entries' copies to ~192 MB (288
# weighted), and a sharded plan's grow with the mesh as its tables do.
# Eviction is by PER-DEVICE bytes, each plan at what its executor really
# holds: the expanded one-hot tables ~224 B per padded slot, the compact
# executor's 13 B a slot of tables and 4 of slot weights (PR 33: until
# then every plan was counted at the expanded price, and a 133M-slot
# compact plan, 2.3 GB, ran uncached — rebuilt in every call); sharded
# plans spread theirs over mesh.size devices. Pinning several multi-GB
# plans would OOM a 16 GB chip, and plans above the budget run uncached.
_PLAN_CACHE: list = []               # _CachedPlan, oldest first
_PLAN_CACHE_MAX_SLOTS = 24_000_000   # the expanded path's gate: slots
_EXPANDED_BYTES_A_SLOT = 224         # compact: pc.RESIDENT_BYTES_A_SLOT
_PLAN_CACHE_MAX_BYTES = _PLAN_CACHE_MAX_SLOTS * _EXPANDED_BYTES_A_SLOT
# the share of a device's memory a compact plan may take while it runs
# (tables, slot weights and a panel's temporaries: pallas_spmv.plan_bytes)
_PLAN_SHARE = 0.5
# elements a comparison step, and what the probe reads of an array: its
# temporary (a 64 KB mask) stays in the heap and the cache, a
# first-chunk miss costs ~0.1 ms, and from 64K elements up the whole
# compare runs at memory speed
_PROBE_CHUNK = 1 << 16


class _CachedPlan(NamedTuple):
    key: tuple          # (n, sizes, weighted) + the caller's tail
    kept: tuple         # the canonical copies: src, dst[, weights]
    refs: tuple         # per array: a weakref to the jax.Array, or None
    prepared: tuple
    cost: int           # per-device bytes
    attrs: Optional[dict] = None    # _plan_attrs, reckoned once a plan


def _host_fetchable(a) -> bool:
    """True when np.asarray(a) is safe — numpy/lists always; jax arrays
    only when every shard is addressable from this process."""
    if isinstance(a, jax.Array):
        return a.is_fully_addressable
    return True


def _same_contents(a, kept, start: int = 0, stop: int = None) -> tuple:
    """(whether ``a`` holds what ``kept`` holds over the elements
    [``start``, ``stop``), the bytes of ``kept`` read to find out).
    Indices compare under numpy's promotion, so an id beyond int32
    equals nothing; weights compare as the float32 the plan is built
    from."""
    a = np.asarray(a)
    seen = 0
    stop = kept.shape[0] if stop is None else min(stop, kept.shape[0])
    for i in range(start, stop, _PROBE_CHUNK):
        j = min(i + _PROBE_CHUNK, stop)
        ours, theirs = kept[i:j], a[i:j]
        if ours.dtype.kind == "f":
            theirs = theirs.astype(ours.dtype, copy=False)
        seen += ours.nbytes
        if not np.array_equal(ours, theirs):
            return False, seen
    return True, seen


def _known(arrays, entry, start: int = 0, stop: int = None) -> tuple:
    """(how ``arrays`` are ``entry``'s graph over the elements
    [``start``, ``stop``): ``identity`` (every array is the jax.Array
    the entry was built from), ``compare`` (by content), None where
    one differs; the bytes read)."""
    how, examined = "identity", 0
    for a, kept, ref in zip(arrays, entry.kept, entry.refs):
        if ref is not None and ref() is a:
            continue
        how = "compare"
        same, seen = _same_contents(a, kept, start, stop)
        examined += seen
        if not same:
            return None, examined
    return how, examined


def _recognise(arrays, entries, stop: int = None) -> tuple:
    """(the first of ``entries`` whose graph ``arrays`` are in their
    first ``stop`` elements (None: in all), how it was known — or
    (None, ``new``) — and the bytes read). ``entries`` is an iterator,
    left behind the entry returned."""
    examined = 0
    for entry in entries:
        how, seen = _known(arrays, entry, 0, stop)
        examined += seen
        if how is not None:
            return entry, how, examined
    return None, "new", examined


def _cached_plan(src, dst, n: int, weights, tail: tuple, build, launch,
                 compact: bool, devices: int = 1):
    """``launch(prepared)``, the ranks as the device will have them, on
    the prepared plan of this graph for the caller ``tail`` names: the
    cached one, or ``build()``'s (None = refused, and None is what
    comes back), cached with its cost in per-device bytes (``compact``:
    the compact tables' price, else the expanded ones', over
    ``devices``) unless that exceeds the budget. A cached plan that is
    known by content is launched after the probe and confirmed under
    the launch (the comment over ``_PLAN_CACHE``)."""
    arrays = tuple(a if isinstance(a, (jax.Array, np.ndarray))
                   else np.asarray(a)
                   for a in (src, dst, weights) if a is not None)
    key = (n, tuple(a.shape[0] for a in arrays[:2]),
           weights is not None) + tail
    entries = iter([e for e in _PLAN_CACHE if e.key == key])
    entry, how, examined = _recognise(arrays, entries, _PROBE_CHUNK)
    outcome = "identity" if how == "identity" else "compared_first"
    under_launch = how == "compare"
    out = _launch(entry, launch) if under_launch else None
    with trace_lib.span("pagerank.fingerprint") as sp:
        if under_launch:
            # the device iterates; the calling thread has nothing else
            rest, seen = _known(arrays, entry, _PROBE_CHUNK)
            examined += seen
            confirmed = rest is not None
            outcome = "confirmed" if confirmed else "discarded"
            sp.set(confirmed=confirmed)
            if not confirmed:
                # edited past the probe: those ranks are another
                # graph's. One speculation a call: the later entries
                # are compared in full before anything is launched
                out = None
                entry, how, seen = _recognise(arrays, entries)
                examined += seen
        sp.set(bytes=examined, how=how, under_launch=under_launch)
    _RECOGNISED[outcome] += 1
    hit = entry is not None
    if not hit:
        entry = _build_entry(arrays, key, build, compact, devices)
        if entry is None:
            return None
        out = launch(entry.prepared)
    elif out is None:
        out = _launch(entry, launch)
    _LAST_PLAN.update(entry.attrs, hit=hit, recognised=outcome)
    return out


def _launch(entry, launch):
    """The caller's launch on a cached plan, ``matrel.pagerank.plan``
    saying ``hit`` and the plan's layout ahead of it."""
    with trace_lib.span("pagerank.plan") as sp:
        sp.set(hit=True, **entry.attrs)
    return launch(entry.prepared)


def _build_entry(arrays, key, build, compact: bool, devices: int):
    """``build()``'s plan as a cache entry (None = refused), appended
    to ``_PLAN_CACHE`` with its copies of ``arrays`` where its cost
    fits the budget, the oldest entries making room; above it the
    entry is handed back alone and keeps no copy."""
    with trace_lib.span("pagerank.plan") as sp:
        sp.set(hit=False)
        prepared = build()
        if prepared is None:
            return None
        attrs = _plan_attrs(prepared[0], arrays[0].shape[0], compact,
                            devices)
        sp.set(**attrs)
        from matrel_tpu.ops.pallas_spmv import resident_bytes
        hub_slots = attrs["hub_slots"]      # 0 off the compact path
        own = attrs["slots"] - hub_slots
        cost = -(-(resident_bytes(own, hub_slots) if compact
                   else own * _EXPANDED_BYTES_A_SLOT) // devices)
        if cost > _PLAN_CACHE_MAX_BYTES:
            return _CachedPlan(key, (), (), prepared, cost, attrs)
        total = sum(e.cost for e in _PLAN_CACHE)
        while _PLAN_CACHE and total + cost > _PLAN_CACHE_MAX_BYTES:
            total -= _PLAN_CACHE.pop(0).cost
        # the build accepted the ids (all in [0, n)), so int32 holds
        kept = tuple(np.array(a, dtype=t) for a, t in zip(
            arrays, (np.int32, np.int32, np.float32)))
        refs = tuple(weakref.ref(a) if isinstance(a, jax.Array)
                     else None for a in arrays)
        entry = _CachedPlan(key, kept, refs, prepared, cost, attrs)
        _PLAN_CACHE.append(entry)
        return entry


def _plan_attrs(plan, edges: int, compact: bool, devices: int = 1) -> dict:
    """What ``matrel.pagerank.plan`` and :func:`last_plan` say of a
    prepared plan, on a hit as on a build: the layout and its padding
    (``slots``, the hub chunks' among them, over ``edges``), the table
    rows (``chunks``: chunks or blocks) of ``chunk`` slots that go
    through the row gather, the edges left to the scalar overflow
    path, the values a gathered byte row holds, what the compact
    executor reckons of the device (``panels`` a matvec, ``plan_bytes``)
    — the expanded executor gathers in one piece and holds ~224 B a
    slot; both a device, of a plan sharded over ``devices`` — and the
    hub table (``hubs`` sources, 0 where the build chose none) with the
    ``hub_chunks`` of ``hub_slots`` whose edges come from it and the
    table rows the hub kernel walks a matvec (``hub_walk_rows``: the
    rows every register of 1,024 hub slots names, summed; walking the
    whole table for each, as until PR 42, would be ``hub_chunks`` x
    ``chunk`` / 1,024 x ``hubs`` / 128)."""
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib
    rows, cap = plan.src8.shape
    mine = -(-rows // devices)          # table rows a device
    per = pc.panel_rows(mine, cap) if compact else mine
    hub = plan.hubs
    hub_chunks = 0 if hub is None else int(hub.idx.shape[0])
    return {"layout": "blocks" if plan.chunk_block is None else "chunks",
            "edges": int(edges), "slots": int((rows + hub_chunks) * cap),
            "chunks": int(rows), "chunk": int(cap),
            "overflow_edges": (0 if plan.ov_rows is None
                               else int(plan.ov_rows.shape[0])),
            "row_values": spmv_lib._row_values(plan.n_cols),
            "panels": -(-mine // per),
            "plan_bytes": int(pc.plan_bytes(mine, cap, hub_chunks * cap)
                              if compact else
                              mine * cap * _EXPANDED_BYTES_A_SLOT),
            "hubs": 0 if hub is None else int(hub.ids.shape[0]),
            "hub_slots": hub_chunks * cap, "hub_chunks": hub_chunks,
            "hub_walk_rows": 0 if hub is None else int(hub.rows.sum())}




def _auto_max_slots() -> int:
    """Plan-size gate for the auto path. Where the compact executor will
    run, the slots whose tables, slot weights (17 B a slot) and one
    panel of gather temporaries stay under ``_PLAN_SHARE`` of what the
    device hands out (245M slots on a v5e; PR 33 — until then a fixed
    192M; a slot of a hub chunk holds 12 B and is counted as any
    other: the gate errs to the safe side). Must consult the SAME gate
    as the executor choice — with use_pallas=False the expanded tables
    run (~224 B/slot), and they keep their own gate."""
    from matrel_tpu.config import pallas_enabled
    if pallas_enabled():
        from matrel_tpu.ops import pallas_spmv as pc
        return int((_PLAN_SHARE - pc._PANEL_SHARE) * pc._hbm_limit()
                   // pc.RESIDENT_BYTES_A_SLOT)
    return _PLAN_CACHE_MAX_SLOTS


def _pagerank_onehot(src, dst, n: int, rounds: int, alpha: float,
                     max_slots: int = None, weights=None,
                     passes: int = 3, refusals: Optional[list] = None):
    from matrel_tpu.config import pallas_enabled
    compact = pallas_enabled()

    def build():
        # the compact executor alone walks a plan laid out in chunks
        with trace_lib.phase("pagerank.plan.build") as built:
            prepared = prepare_pagerank_onehot(
                src, dst, n, max_slots=max_slots, weights=weights,
                layout="auto" if compact else "blocks", refusals=refusals)
        if prepared is None:
            return None
        with trace_lib.phase("pagerank.plan.upload") as placed:
            if compact:
                from matrel_tpu.ops import pallas_spmv as pc
                jax.block_until_ready(           # upload now, memoised
                    pc.compact_tables(prepared[0]))
        # what a first call spends before it compiles: last_plan says
        _LAST_PLAN.update(build_s=round(built.dur_ms / 1e3, 3),
                          upload_s=round(placed.dur_ms / 1e3, 3))
        return prepared

    def launch(prepared):
        if compact:
            # compact-table Pallas executor: faster and ~17× less HBM
            # than the expanded tables (BASELINE row 5). passes=3
            # (default) is f32-faithful like the expanded path; callers
            # may pass 2 for ranking-grade (~2^-16 per matvec) at
            # higher speed
            return run_pagerank_compact(prepared, rounds, alpha,
                                        passes=passes)
        return run_pagerank_onehot(prepared, rounds, alpha)

    # keyed by the executor: a plan built for the compact one may lie
    # in chunks, which the expanded tables cannot be made from
    return _cached_plan(src, dst, n, weights,
                        ("compact",) if compact else (), build, launch,
                        compact)


def _pagerank_compact_sharded(src, dst, n: int, rounds: int, alpha: float,
                              mesh, max_slots: int = None, weights=None,
                              passes: int = 3, interpret=None,
                              refusals: Optional[list] = None):
    """Multi-chip PageRank over mesh-sharded COMPACT tables: each device
    holds ~13 B/slot / P and generates its scatter one-hots in VMEM
    (ops/pallas_spmv.py); the whole power iteration is one shard_map'd
    program with a tiled all_gather of r per round."""
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib

    def build():
        prepared = prepare_pagerank_onehot(src, dst, n,
                                           max_slots=max_slots,
                                           weights=weights,
                                           refusals=refusals)
        if prepared is None:
            return None
        pc.shard_compact_tables(prepared[0], mesh)   # place now
        return prepared

    def launch(prepared):
        plan, dangling = prepared
        from matrel_tpu.config import resolve_interpret
        tables = pc.shard_compact_tables(plan, mesh)
        ov = plan.overflow
        run = _compact_sharded_loop(
            int(n), int(rounds), float(alpha),
            (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO),
            len(ov), int(passes), bool(resolve_interpret(interpret)), mesh)
        return _dispatch(run, *tables, jnp.asarray(dangling), *ov)

    return _cached_plan(src, dst, n, weights, (mesh, "compact"), build,
                        launch, True, mesh.size)


@functools.lru_cache(maxsize=32)
def _compact_sharded_loop(n: int, rounds: int, alpha: float, plan_static,
                          n_ov: int, passes: int, interpret: bool, mesh):
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib

    axes = tuple(mesh.axis_names)
    in_specs = pc.compact_sharded_specs(axes, n_ov)

    def matrel_pagerank_compact_sharded(src8, lane, off, val, dangling,
                                        *ov):
        def matvec(r):
            return pc.compact_sharded_apply(
                plan_static, (src8, lane, off, val), ov, r, axes,
                passes, interpret)

        body = _power_body(matvec, n, alpha, dangling)
        r0 = _r0(n)
        r0 = compat.pvary(r0, axes)
        return jax.lax.fori_loop(0, rounds, body, r0)

    return jax.jit(shard_map(matrel_pagerank_compact_sharded, mesh=mesh,  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
                             in_specs=in_specs, out_specs=P(),
                             check_vma=False))


def _pagerank_onehot_sharded(src, dst, n: int, rounds: int, alpha: float,
                             mesh, max_slots: int = None, weights=None,
                             refusals: Optional[list] = None):
    """Multi-chip one-hot PageRank: the whole power iteration runs inside
    ONE shard_map'd jitted program; each device owns a slice of
    destination blocks and the round ends in a tiled all_gather of r."""
    from matrel_tpu.ops import spmv as spmv_lib

    p = mesh.size

    def build():
        prepared = prepare_pagerank_onehot(src, dst, n,
                                           max_slots=max_slots,
                                           weights=weights,
                                           refusals=refusals)
        if prepared is None:
            return None
        return (spmv_lib.shard_plan(prepared[0], mesh), prepared[1])

    # Mesh compares identity-precise: same-shaped meshes over different
    # devices must not share cached (device-committed) plans
    def launch(prepared):
        plan, dangling = prepared
        run = _onehot_sharded_runner(int(n), int(rounds), float(alpha),
                                     (plan.n_rows, plan.n_cols, plan.block),
                                     len(plan.arrays()), mesh)
        return _dispatch(run, *plan.arrays(), dangling)

    return _cached_plan(src, dst, n, weights, (mesh,), build, launch,
                        False, p)


@functools.lru_cache(maxsize=32)
def _onehot_sharded_runner(n: int, rounds: int, alpha: float, plan_static,
                           n_arrays: int, mesh):
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P
    from matrel_tpu.ops import spmv as spmv_lib

    axes = tuple(mesh.axis_names)
    in_specs = spmv_lib.sharded_table_specs(axes, n_arrays)
    in_specs = in_specs + (P(),)          # dangling, replicated

    def matrel_pagerank_onehot_sharded(src8, sel, oh_hi, oh_lo, *rest):
        ov, dangling = rest[:-1], rest[-1]
        arrays = (src8, sel, oh_hi, oh_lo) + ov

        body = _power_body(
            lambda r: spmv_lib.spmv_sharded_apply(plan_static, arrays,
                                                  r, mesh),
            n, alpha, dangling)
        r0 = _r0(n)
        r0 = compat.pvary(r0, axes)
        return jax.lax.fori_loop(0, rounds, body, r0)

    # check_vma=False: see _sharded_spmv_runner — the all_gathered carry
    # is value-identical per device but typed varying
    return jax.jit(shard_map(matrel_pagerank_onehot_sharded, mesh=mesh,  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
                             in_specs=in_specs, out_specs=P(),
                             check_vma=False))


def _power_body(matvec, n: int, alpha: float, dangling):
    """The shared PageRank update: one body for every edge-based impl so
    the teleport/dangling semantics (and precision) cannot drift apart."""
    teleport = (1.0 - alpha) / n

    def body(_, r):
        contrib = matvec(r)
        dmass = jnp.sum(dangling * r)
        return alpha * (contrib + dmass / n) + teleport

    return body


def _r0(n: int):
    return jnp.full((n,), 1.0 / n, dtype=jnp.float32)


@functools.lru_cache(maxsize=32)
def _onehot_runner(n: int, rounds: int, alpha: float, plan_static,
                   n_arrays: int):
    from matrel_tpu.ops import spmv as spmv_lib

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def matrel_pagerank_onehot(arrays, dangling):
        body = _power_body(
            lambda r: spmv_lib.spmv_apply(plan_static, arrays, r),
            n, alpha, dangling)
        return jax.lax.fori_loop(0, rounds, body, _r0(n))

    return matrel_pagerank_onehot


@functools.lru_cache(maxsize=32)
def _compact_runner_loop(n: int, rounds: int, alpha: float, plan_static,
                         n_ov: int, passes: int, interpret: bool):
    from matrel_tpu.ops import pallas_spmv as pc

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def matrel_pagerank_compact(tables, ov, dangling):
        body = _power_body(
            lambda r: pc.compact_apply(plan_static, tables, ov, r,
                                       passes, interpret),
            n, alpha, dangling)
        return jax.lax.fori_loop(0, rounds, body, _r0(n))

    return matrel_pagerank_compact


@functools.lru_cache(maxsize=32)
def _edges_runner(n: int, rounds: int, alpha: float):
    """Jitted programs cached per (n, rounds, alpha) — fresh closures per
    call would recompile on every invocation."""

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def prepare(s, d, w):
        # sort edges by destination once so the per-round scatter-add runs
        # with indices_are_sorted (much cheaper on TPU)
        order = jnp.argsort(d)
        return s[order], d[order], w[order]

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def matrel_pagerank_segment(s, d, w):
        outdeg = jax.ops.segment_sum(w, s, num_segments=n)
        inv_deg = jnp.where(outdeg > 0,
                            1.0 / jnp.maximum(outdeg, 1e-30), 0.0)
        dangling = (outdeg == 0).astype(jnp.float32)

        def matvec(r):
            rn = r * inv_deg
            return jax.ops.segment_sum(rn[s] * w, d, num_segments=n,
                                       indices_are_sorted=True)

        body = _power_body(matvec, n, alpha, dangling)
        return jax.lax.fori_loop(0, rounds, body, _r0(n))

    return prepare, matrel_pagerank_segment


def pagerank_reference_edges(src, dst, n: int, rounds: int = 30,
                             alpha: float = 0.85) -> np.ndarray:
    """The plain reference of :func:`pagerank_edges`, float64 on the
    host: LDBC Graphalytics' PageRank over an edge list,

        PR_0(v) = 1/n
        PR_i(v) = (1 - d)/n + d * (sum_{u -> v} PR_{i-1}(u) / outdeg(u)
                                   + (1/n) * sum_{w dangling} PR_{i-1}(w))

    for ``rounds`` iterations, d = ``alpha``; a duplicate edge counts
    twice, a dangling vertex (no out-edge) spreads its rank over all."""
    import scipy.sparse as sp
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1e-30), 0.0)
    at = sp.csr_matrix((inv[src], (dst, src)), shape=(n, n))
    dangling = outdeg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(rounds):
        r = alpha * (at @ r + r[dangling].sum() / n) + (1 - alpha) / n
    return r


def pagerank_numpy_oracle(a, rounds=30, alpha=0.85):
    """Naive host oracle for tests."""
    n = a.shape[0]
    deg = a.sum(1, keepdims=True)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1e-30), 0.0)
    r = np.full((n, 1), 1.0 / n, dtype=np.float64)
    for _ in range(rounds):
        contrib = (a * inv).T @ r
        dmass = r[(deg == 0).ravel()].sum()
        r = alpha * (contrib + dmass / n) + (1 - alpha) / n
    return r
