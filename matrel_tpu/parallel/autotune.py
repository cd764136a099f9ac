"""Strategy autotuning — empirical answer to SURVEY.md §7's hard part:
"proving the explicit SUMMA/psum_scatter paths beat XLA's choice (and
detecting when not)".

The cost model (planner.py) is an estimate; this module MEASURES. For a
given (n, k, m, mesh) it times every admissible strategy on-device
(marginal timing: chained dependent runs with a forced fetch, cancelling
dispatch latency) and caches the winner.

The loop is CLOSED via ``config.autotune``: with the flag on, the
planner consults ``lookup_or_measure`` before trusting its byte model —
a recurring shape class is measured once, the winner overrides the
model's pick, and the table persists as JSON (config.autotune_table_path)
so later sessions inherit the measurement. ``config.strategy_override``
still wins over both.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_log = logging.getLogger("matrel_tpu.autotune")


def _log_dropped(variant, err) -> None:
    """A candidate that fails to build, compile or run drops out of the
    measured table — said out loud (a refused Pallas kernel used to
    vanish without a word)."""
    _log.warning("autotune: %s dropped from the measurement: %s: %s",
                 variant, type(err).__name__, str(err)[:300])

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib, padding
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.parallel import planner, strategies

# (best, times) per shape class; best is None when the measured winner was
# within TIE_REL of the runner-up — a tie is recorded as a tie and the
# planner's byte model decides (VERDICT r3: noise must not become winners).
_CACHE: Dict[tuple, Tuple[Optional[str], Dict[str, float]]] = {}

TIE_REL = 0.10

_DEFAULT_TABLE = ".matrel_autotune.json"


def _table_path(config: Optional[MatrelConfig] = None) -> str:
    cfg = config or default_config()
    return cfg.autotune_table_path or _DEFAULT_TABLE


def _table_key(side: int, gx: int, gy: int, dtype: str,
               weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    # backend is part of the key, mirroring _spmv_key's rationale
    # (advisor r4): a shared table must never serve one backend's
    # winner to the other — a persisted CPU-mesh winner has nothing to
    # say about Mosaic. Old un-suffixed entries simply never hit; they
    # linger in the JSON (persist rewrites the whole table) but are
    # inert — delete the file to reclaim the bytes.
    #
    # Non-uniform topology weights (core/mesh.MeshTopology) suffix the
    # key too: a winner measured on (or planned for) a hierarchical
    # ICI/DCN mesh must never collide with the homogeneous mesh's row
    # for the same grid shape. Uniform weights keep the historical
    # 4-field format, so existing tables stay live.
    key = f"{side}|{gx}x{gy}|{dtype}|{jax.default_backend()}"
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def load_table(path: str) -> Dict[str, dict]:
    """Persisted {key: {"best": strategy, "times": {...}}} or {}.
    A corrupt/absent file is an empty table, never an error.

    Tables written BEFORE the backend key suffix landed are migrated on
    load by PRUNING their un-suffixed entries (advisor r5 low): those
    keys can never hit again — `_table_key`/`_spmv_key` always emit the
    suffixed form — so left in place they would ride every whole-table
    rewrite forever as dead bytes. Dropping them here means the next
    `_persist` rewrites a clean table; the one-time re-measure cost of
    the orphaned winners is the accepted price of backend-safe keys."""
    try:
        with open(path) as f:
            t = json.load(f)
    except OSError:
        return {}            # absent table: the normal first-run case
    except ValueError as e:
        # corrupt/truncated table: WARN and rebuild from empty — the
        # session must survive a torn write (a crash mid-_persist, a
        # disk hiccup); the next _persist rewrites a clean file
        # (docs/RESILIENCE.md robust-reader contract)
        _log.warning("autotune table %s is corrupt (%s); "
                     "rebuilding from empty", path, e)
        return {}
    if not isinstance(t, dict):
        _log.warning("autotune table %s has unexpected shape (%s); "
                     "rebuilding from empty", path, type(t).__name__)
        return {}
    return {k: v for k, v in t.items() if _current_key_format(k)}


def _current_key_format(key: str) -> bool:
    """Does a persisted key match the CURRENT (backend-suffixed) key
    formats? Matmul keys are ``side|gxXgy|dtype|backend`` (4 fields);
    SpMV keys ``spmv|backend|rows x cols|nb|cap|blk|grid`` (7 fields);
    reshard keys ``reshard|src>dst|side|grid|backend`` (5 fields);
    SpGEMM kernel keys ``spgemm|<=side|structure|bs|grid|backend``
    (6 fields — the structure class must be in the CURRENT classifier
    vocabulary, so keys from a retired taxonomy are pruned too).
    Any may carry one extra trailing ``w<wx>x<wy>`` field — the
    topology-weight suffix of a non-uniform mesh. Legacy un-suffixed
    entries (one field short) and anything unknown read as stale."""
    if not isinstance(key, str):
        return False
    fields = key.split("|")
    n = len(fields)
    if key.startswith("spmv|"):
        base = 7
    elif key.startswith("reshard|"):
        base = 5
    elif key.startswith("spgemm|"):
        from matrel_tpu.ir import stats
        base = 6
        if n >= 3 and fields[2] not in stats.STRUCTURE_CLASSES:
            return False
    elif key.startswith("fuse|"):
        base = 5         # fuse|<sig>|<=side|grid|backend (round 12)
    elif key.startswith("ivm|"):
        base = 5         # ivm|<rule>|<=side|grid|backend (round 14);
        # rules from a retired vocabulary prune like spgemm structures
        from matrel_tpu.ir import delta as delta_lib
        if n >= 2 and fields[1] not in delta_lib.DELTA_RULES:
            return False
    else:
        base = 4
    if n == base:
        return True
    return n == base + 1 and fields[-1].startswith("w")


_TABLE_CACHE: Dict[str, Tuple[float, Dict[str, dict]]] = {}


def _load_table_cached(path: str) -> Dict[str, dict]:
    """load_table memoised on (path, mtime): the planner consults the
    table on EVERY matmul when config.autotune is on, and un-measured
    shapes (including everything above autotune_max_dim) would
    otherwise re-open and re-parse the JSON on each compile."""
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        mtime = -1.0
    hit = _TABLE_CACHE.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    table = load_table(path)
    _TABLE_CACHE[path] = (mtime, table)
    return table


def _persist(path: str, key: str, best: Optional[str],
             times: Dict[str, float]) -> None:
    """Merge one measurement into the JSON table (atomic rename).

    A best-effort O_CREAT|O_EXCL lock file guards the read-merge-replace
    window (advisor r3: two concurrent processes could interleave
    load/merge/replace and silently drop each other's measurements).
    On contention the persist is SKIPPED — losing one merge is benign
    (the in-process cache still holds it and a later call re-persists),
    and rename atomicity already rules out corruption. A lock older
    than 60 s is presumed dead and broken; after the break the breaker
    re-stats the lock path and proceeds only when the inode matches its
    own freshly-created fd (advisor r4: two processes can both observe
    the stale lock, both unlink-and-recreate — one unlinking the
    other's fresh lock — and both enter the merge window; the st_ino
    check makes exactly one of them win)."""
    lock = f"{path}.lock"
    fd = None
    try:
        fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
    except FileExistsError:
        try:
            st0 = os.stat(lock)
            if time.time() - st0.st_mtime <= 60.0:
                return
            # re-stat immediately before the unlink: if the inode
            # changed since the staleness check, another breaker got
            # here first — never unlink ITS fresh lock (review r5; the
            # remaining stat→unlink window is unavoidable without
            # flock, but every exit below re-checks ownership so a
            # lost race costs one skipped persist, never two writers)
            if os.stat(lock).st_ino != st0.st_ino:
                return
            os.unlink(lock)
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            if os.stat(lock).st_ino != os.fstat(fd).st_ino:
                os.close(fd)   # a racing breaker re-created over ours;
                return         # it owns the window — skip, don't unlink
        except OSError:
            if fd is not None:
                os.close(fd)
            return
    except OSError:
        fd = None    # lock unsupported (read-only FS): try unguarded
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        # (re-)load under the lock so a concurrent writer's just-merged
        # entries survive into this replace
        table = load_table(path)
        table[key] = {"best": best, "times": times}
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:      # read-only FS etc.: in-process cache still holds it
        try:
            os.unlink(tmp)
        except OSError:
            pass
    finally:
        if fd is not None:
            try:
                # release ONLY a lock we still own: a racing breaker
                # may have replaced ours mid-merge (review r5) — its
                # inode differs and must not be unlinked
                if os.stat(lock).st_ino == os.fstat(fd).st_ino:
                    os.unlink(lock)
            except OSError:
                pass
            os.close(fd)


def measure_strategy(strategy: str, A: BlockMatrix, B: BlockMatrix,
                     config: MatrelConfig, reps: Tuple[int, int] = (2, 8),
                     n_estimates: int = 3, min_window_s: float = 0.05
                     ) -> float:
    """Marginal seconds per multiply for one strategy: the MEDIAN of
    ``n_estimates`` independent marginal estimates (a single marginal
    on a shared chip records noise as winners, VERDICT r3). The chained-reps budget is floored: when the
    long chain completes under ``min_window_s`` the reps are scaled up
    so the marginal rises above dispatch jitter. May return a
    NON-POSITIVE value on a hopelessly noisy host — callers must treat
    that as "no measurement", never clamp it into a fake winner."""
    mesh = A.mesh
    f = jax.jit(lambda x, y: strategies.run_matmul(strategy, x, y, mesh,  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path
                                                   config))
    fetch = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path

    def chained(n: int):
        cur = A.data
        for i in range(n):
            cur = f(cur, B.data).astype(A.dtype)
            if (i + 1) % 8 == 0:
                # bound in-flight programs: the CPU in-process
                # communicator's rendezvous starves (fatal abort) with
                # tens of queued collective executions; a sync every 8
                # reps costs the same per rep for every strategy, so
                # the ranking is unaffected
                cur.block_until_ready()
        float(fetch(cur))

    def marginal(lo: int, hi: int) -> Tuple[float, float]:
        t0 = time.perf_counter()
        chained(lo)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        chained(hi)
        t_hi = time.perf_counter() - t0
        return (t_hi - t_lo) / (hi - lo), t_hi

    chained(2)  # compile + warm
    lo, hi = reps
    est, t_hi = marginal(lo, hi)
    if t_hi < min_window_s:
        # bounded: the whole re-measure must stay cheap even on a slow
        # host (a CPU-mesh run pays ~ms dispatch per chained call), so
        # the chain never exceeds 48 multiplies however short the window
        scale = min(max(2, round(min_window_s / max(t_hi, 1e-4))),
                    max(48 // hi, 1))
        if scale > 1:
            lo, hi = lo * scale, hi * scale
            est, t_hi = marginal(lo, hi)
    ests = [est]
    for _ in range(max(n_estimates, 1) - 1):
        ests.append(marginal(lo, hi)[0])
    ests.sort()
    return ests[len(ests) // 2]


def autotune_matmul(n: int, k: int, m: int,
                    mesh=None, dtype="float32",
                    config: Optional[MatrelConfig] = None
                    ) -> Tuple[str, Dict[str, float]]:
    """Times every admissible strategy for an (n×k)·(k×m) multiply on this
    mesh; returns (best_strategy, {strategy: seconds}). Results cached per
    (dims, mesh shape, dtype). Chained timing needs n == m == k for the
    feedback loop, so non-square requests are measured square at
    max(n, k, m) — the MXU/collective behaviour is shape-dominated."""
    cfg = config or default_config()
    mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
    side = max(n, k, m)
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    wts = mesh_lib.axis_weights(mesh, cfg)
    key = (side, gx, gy, str(dtype), jax.default_backend(), wts)
    if key in _CACHE:
        _maybe_persist_cached(cfg, key)
        return _CACHE[key]
    A = BlockMatrix.random((side, side), mesh=mesh, seed=0, dtype=dtype)
    B = BlockMatrix.random((side, side), mesh=mesh, seed=1, dtype=dtype)
    pn, pk = padding.padded_shape((side, side), mesh)
    results: Dict[str, float] = {}
    for s in strategies.STRATEGIES:
        if s == "summa" and gx != gy:
            continue
        if not planner.admissible(s, pn, pk, pn, gx, gy):
            continue
        try:
            t = measure_strategy(s, A, B, cfg)
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a strategy failing to compile
            _log_dropped(s, e)       # on this backend just drops out of the table
            continue
        if t > 0.0:        # non-positive median = noise, not a time
            results[s] = t
    # _pick_winner owns the one-variant and tie gates (advisor r4):
    # a compile-failure-reduced lone survivor records best=None —
    # times still persist for observability, the model decides
    best = _pick_winner(results)
    _CACHE[key] = (best, results)
    if results and (cfg.autotune or cfg.autotune_table_path):
        # an EMPTY result set (every strategy failed or measured pure
        # noise) is never persisted — a persisted empty entry would read
        # as "measured: no winner" and permanently disable re-measurement
        # of the shape class on later, healthy processes
        # persist only when the closed loop is on or the caller named a
        # table explicitly — a one-off measurement call (the original
        # API contract, also the CLI) must not drop a hidden JSON file
        # into the working directory as a side effect
        _persist(_table_path(cfg),
                 _table_key(side, gx, gy, str(dtype), wts),
                 best, results)
    return best, results


def _pick_winner(results: Dict[str, float]) -> Optional[str]:
    """argmin with two guards, BOTH owned here (review r5 — one policy,
    not copies at each call site): a one-variant "comparison" proves
    nothing (None — the lone survivor of compile failures/noise must
    not become a measured preference), and a winner within TIE_REL of
    the runner-up is recorded as None ("no measured winner") so the
    byte model decides — on meshes where strategies compile identically
    (e.g. 1 device) every marginal is pure noise."""
    if len(results) < 2:
        return None
    order = sorted(results, key=results.get)
    best, runner = order[0], order[1]
    if results[runner] <= results[best] * (1.0 + TIE_REL):
        return None
    return best


def _maybe_persist_cached(config: Optional[MatrelConfig],
                          key: tuple) -> None:
    """A shape first measured with persistence OFF (one-off call) must
    still reach the table when a later caller enables the closed loop —
    both cache-hit early-returns route through here."""
    cfg = config or default_config()
    if not (cfg.autotune or cfg.autotune_table_path):
        return
    side, gx, gy, dtype, _backend, wts = key
    best, results = _CACHE[key]
    if not results:
        return
    path = _table_path(cfg)
    tkey = _table_key(side, gx, gy, dtype, wts)
    if tkey not in _load_table_cached(path):
        _persist(path, tkey, best, results)


def lookup_or_measure(n: int, k: int, m: int, mesh,
                      dtype: str = "float32",
                      config: Optional[MatrelConfig] = None
                      ) -> Optional[str]:
    """The planner's entry point (config.autotune=True): the measured
    winner for this shape class, or None when the cost model should
    decide. Order: in-process cache → persisted table → measure once
    (small shapes only — measuring allocates two side² operands, so
    shapes above config.autotune_max_dim are never measured inline)."""
    cfg = config or default_config()
    side = max(n, k, m)
    # strongly rectangular shapes are gated out (advisor r3): the table
    # keys and measures SQUARE side-sized operands, so a 64x8192 matvec
    # chain would both allocate two side-squared probes at compile time
    # and inherit a square-dense winner that can mispick for it — the
    # byte model (which sees the true dims) decides instead
    if min(n, k, m) * 4 < side:
        return None
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    wts = mesh_lib.axis_weights(mesh, cfg)
    key = (side, gx, gy, str(dtype), jax.default_backend(), wts)
    if key in _CACHE:
        _maybe_persist_cached(cfg, key)
        return _CACHE[key][0]
    entry = _load_table_cached(_table_path(cfg)).get(
        _table_key(side, gx, gy, str(dtype), wts))
    if isinstance(entry, dict) and entry.get("times"):
        # a persisted TIE (best null) is a measurement too: cache it and
        # let the model decide — do NOT re-measure every compile
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _CACHE[key] = (best, dict(entry.get("times", {})))
        return best
    if side > cfg.autotune_max_dim:
        return None
    best, _ = autotune_matmul(n, k, m, mesh=mesh, dtype=dtype, config=cfg)
    return best


# -- SpMV executor autotuning -------------------------------------------------
# The largest hand-pinned constants in the codebase are the COO SpMV
# executor choices (SURVEY.md §7 "detecting when XLA's choice is
# beaten"): compact-table Pallas scatter vs expanded-table XLA one-hots.
# The hand default (compact wherever Pallas is available — measured
# 18.8 ms vs 29.4 per matvec at BASELINE row-5 scale on v5e) stays the
# fallback; with config.autotune on, the choice is measured once per
# plan shape class and persisted in the same JSON table.

_SPMV_CACHE: Dict[str, Optional[str]] = {}

# expanded tables cost ~224 B per padded slot; refuse to even MEASURE
# the expanded variant past this budget (a 10x-graph table would blow
# the chip's HBM just to lose the comparison)
SPMV_EXPANDED_BUDGET_BYTES = 2 * 1024 ** 3

SPMV_VARIANTS = ("compact", "expanded")


def _spmv_key(plan, gx: int, gy: int,
              weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    # backend is part of the key: the compact/expanded trade-off FLIPS
    # between real Mosaic (compact wins, BASELINE row 5) and CPU
    # interpret mode (expanded wins ~20x) — a shared table must never
    # serve one backend's winner to the other. Non-uniform topology
    # weights suffix the key like _table_key's matmul rows: the sharded
    # executors' gather bills differ on a hierarchical mesh.
    nb, cap = plan.src8.shape if hasattr(plan.src8, "shape") else (0, 0)
    key = (f"spmv|{jax.default_backend()}|{plan.n_rows}x{plan.n_cols}"
           f"|nb{nb}|cap{cap}|blk{plan.block}|{gx}x{gy}")
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def measure_spmv_variant(variant: str, plan, mesh,
                         config: Optional[MatrelConfig] = None,
                         n_times: int = 5) -> float:
    """Median seconds per matvec for one executor variant, timed through
    the REAL lowering path (Lowerer._coo_spmv_stack with the choice
    forced). Sync timing with a forced scalar fetch — both variants pay
    the identical fetch, so the ranking is unaffected."""
    import numpy as np
    from matrel_tpu import executor as executor_lib
    cfg = config or default_config()
    low = executor_lib.Lowerer(mesh, cfg)
    low.spmv_choice = {id(plan): (plan, variant)}
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(plan.n_cols).astype(np.float32))
    # snapshot the plan's expanded-table caches: the expanded probe
    # calls plan.arrays(), which eagerly expands and CACHES the ~224
    # B/slot one-hot tables on the plan — left in place they would pin
    # up to the measurement budget of HBM for the whole session even
    # when compact wins (review r4). The winner re-expands on first
    # real use (one fused program).
    saved = (plan._tables, plan._spmm_tables)
    try:
        f = jax.jit(lambda v: jnp.sum(low._coo_spmv_stack(plan, [v])))  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path
        float(f(x))    # compile + warm (also table upload/expansion)
        ts = []
        for _ in range(max(n_times, 1)):
            t0 = time.perf_counter()
            float(f(x))
            ts.append(time.perf_counter() - t0)
    finally:
        if variant == "expanded":
            plan._tables, plan._spmm_tables = saved
    ts.sort()
    return ts[len(ts) // 2]


def _spmv_admissible(variant: str, plan, config: MatrelConfig) -> bool:
    from matrel_tpu.config import pallas_enabled
    if variant == "compact":
        return pallas_enabled(config)
    # expanded: gate on the materialised-table budget
    nb, cap = plan.src8.shape
    return nb * cap * 224 <= SPMV_EXPANDED_BUDGET_BYTES


def lookup_or_measure_spmv(plan, mesh,
                           config: Optional[MatrelConfig] = None
                           ) -> Optional[str]:
    """The compile-time entry point (config.autotune=True): the measured
    executor variant for this plan shape class, or None when the hand
    default should stand. Same table discipline as the matmul loop:
    in-process cache → persisted table → measure once; ties and empty
    result sets resolve to None and are never fake winners."""
    cfg = config or default_config()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    key = _spmv_key(plan, gx, gy, mesh_lib.axis_weights(mesh, cfg))
    if key in _SPMV_CACHE:
        return _SPMV_CACHE[key]
    entry = _load_table_cached(_table_path(cfg)).get(key)
    if isinstance(entry, dict) and entry.get("times"):
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _SPMV_CACHE[key] = best
        return best
    results: Dict[str, float] = {}
    for v in SPMV_VARIANTS:
        if not _spmv_admissible(v, plan, cfg):
            continue
        try:
            t = measure_spmv_variant(v, plan, mesh, cfg)
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a variant failing to compile
            _log_dropped(v, e)       # on this backend drops out of the table
            continue
        if t > 0.0:
            results[v] = t
    # a one-variant "comparison" proves nothing, and which variants are
    # admissible depends on CONFIG state (use_pallas, the expanded
    # budget) that the table key does not encode — persisting it would
    # poison shared tables across configs (review r4). Hand default
    # stands; nothing is written.
    if len(results) < 2:
        _SPMV_CACHE[key] = None
        return None
    best = _pick_winner(results)
    _SPMV_CACHE[key] = best
    if cfg.autotune or cfg.autotune_table_path:
        _persist(_table_path(cfg), key, best, results)
    return best


# ---------------------------------------------------------------------------
# SpGEMM kernel measurement (round 11) — the closed loop for the sparse
# kernel registry (ops/kernel_registry.py): per (shape class, structure
# class, backend) the registered variants are timed over a synthetic
# operand pair EXHIBITING that structure (the same generator the bench
# and soak batteries draw from), and the winner persists exactly like
# matmul strategies. ``kernel_registry.select_kernel`` consults this
# before trusting its cost model (the "measured" stamp source).
# ---------------------------------------------------------------------------

_SPGEMM_CACHE: Dict[str, Optional[str]] = {}

#: Probe block-density seed for the synthetic structure pair — fixed so
#: the measured population is reproducible per key.
SPGEMM_PROBE_SEEDS = (0, 1)


def _spgemm_side_class(side: int) -> int:
    """Power-of-two side bucket — the drift auditor's shape-class
    granularity, so a 3800² and a 4096² S×S share a row."""
    return 1 << max(0, math.ceil(math.log2(max(int(side), 1))))


def _spgemm_key(side: int, structure: str, bs: int, gx: int, gy: int,
                weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    """``spgemm|<=side|structure|bs|grid|backend[|w..]`` — the issue'd
    key format: side bucketed, structure class explicit, backend (and
    non-uniform weights) suffixed like every other table row. Keys in
    any OTHER spgemm format (including a retired structure taxonomy)
    are legacy and pruned on load (_current_key_format)."""
    key = (f"spgemm|<={_spgemm_side_class(side)}|{structure}|bs{bs}"
           f"|{gx}x{gy}|{jax.default_backend()}")
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def measure_spgemm_kernel(kernel_id: str, A, B,
                          config: Optional[MatrelConfig] = None,
                          n_times: int = 5) -> float:
    """Median seconds for one forced-kernel SpGEMM over the probe pair,
    through the REAL ops path (spgemm_tiles with the registry choice
    pinned). Sync timing with a forced scalar fetch — every kernel
    pays the identical fetch, so the ranking is unaffected."""
    from matrel_tpu.ops import spgemm as spgemm_lib
    cfg = config or default_config()
    fetch = jax.jit(lambda t: jnp.sum(t.astype(jnp.float32)))  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path

    def go():
        tiles, _, _ = spgemm_lib.spgemm_tiles(A, B, cfg,
                                              kernel=kernel_id)
        float(fetch(tiles))

    go()                        # compile + warm (runner cache fill)
    ts = []
    for _ in range(max(n_times, 1)):
        t0 = time.perf_counter()
        go()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def lookup_or_measure_spgemm(side: int, structure: str, bs: int, mesh,
                             config: Optional[MatrelConfig] = None
                             ) -> Optional[str]:
    """The registry's compile-time entry point (config.autotune=True):
    the measured kernel id for this (shape class, structure class,
    backend), or None when the cost model should decide. Same table
    discipline as the matmul/SpMV/reshard loops: in-process cache →
    persisted table → measure once (bounded probe — shapes above
    autotune_max_dim are never measured inline); ties and
    single-variant result sets resolve to None and are never fake
    winners."""
    from matrel_tpu.ops import kernel_registry as kr
    cfg = config or default_config()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    wts = mesh_lib.axis_weights(mesh, cfg)
    key = _spgemm_key(side, structure, bs, gx, gy, wts)
    if key in _SPGEMM_CACHE:
        return _SPGEMM_CACHE[key]
    entry = _load_table_cached(_table_path(cfg)).get(key)
    if isinstance(entry, dict) and entry.get("times"):
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _SPGEMM_CACHE[key] = best
        return best
    if side > cfg.autotune_max_dim:
        _SPGEMM_CACHE[key] = None
        return None
    probe_n = int(side)
    A = kr.synthesize_structure(structure, probe_n, bs, mesh,
                                seed=SPGEMM_PROBE_SEEDS[0])
    B = kr.synthesize_structure(structure, probe_n, bs, mesh,
                                seed=SPGEMM_PROBE_SEEDS[1])
    npairs = 1              # admissibility probe: eligibility, not size
    results: Dict[str, float] = {}
    for kid in kr.kernel_ids():
        spec = kr.get_kernel(kid)
        if not (spec.universal or structure in spec.structures):
            continue        # foreign specializations aren't candidates
        if not kr.admissible(kid, bs, npairs, cfg):
            continue
        try:
            t = measure_spgemm_kernel(kid, A, B, cfg)
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a kernel failing to compile on this backend drops out of the table
            _log_dropped(kid, e)
            continue
        if t > 0.0:
            results[kid] = t
    # which kernels are admissible depends on CONFIG state (use_pallas,
    # interpret) the key does not encode — a single-variant "result"
    # proves nothing and is never persisted (the SpMV-loop precedent)
    if len(results) < 2:
        _SPGEMM_CACHE[key] = None
        return None
    best = _pick_winner(results)
    _SPGEMM_CACHE[key] = best
    if cfg.autotune or cfg.autotune_table_path:
        _persist(_table_path(cfg), key, best, results)
    return best


# ---------------------------------------------------------------------------
# Fused-vs-staged region measurement (round 12) — the closed loop for
# the whole-plan fusion pass (ir/fusion.py; docs/FUSION.md): per
# (region signature, shape class, backend), the region is emitted BOTH
# ways through the executor's unit-program seam — one jitted program
# for the whole segment vs one per member op — over synthetic padded
# probes, and the winner persists under the ``fuse|`` key family.
# ``fusion.annotate_fusion`` consults this before stamping: a measured
# "staged" winner SUPPRESSES the region (fusion boundaries are planner
# decisions, and the closed measurement loop overrules the model).
# ---------------------------------------------------------------------------

_FUSION_CACHE: Dict[str, Optional[str]] = {}

FUSION_VARIANTS = ("fused", "staged")


def _fusion_key(sig: str, side: int, gx: int, gy: int,
                weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    """``fuse|<sig>|<=side|grid|backend[|w..]`` — the region signature
    is '|'-free by construction (ir/fusion.region_sig); side bucketed
    to the drift auditor's power-of-two class like every other row."""
    cls = 1 << max(0, math.ceil(math.log2(max(int(side), 1))))
    key = (f"fuse|{sig}|<={cls}|{gx}x{gy}"
           f"|{jax.default_backend()}")
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def measure_fusion_region(region, root_tree, mesh,
                          config: Optional[MatrelConfig] = None,
                          n_times: int = 5) -> Dict[str, float]:
    """{'fused': s, 'staged': s} medians for ONE region, both lowered
    through the executor's unit-program seam over synthetic padded
    probes (region_probe_programs). Empty dict when the region is not
    probeable (sparse-payload inputs) or a variant fails to build."""
    from matrel_tpu import executor as executor_lib
    cfg = config or default_config()
    node = _find_region_root(root_tree, region.root_uid)
    if node is None:
        return {}
    probe = executor_lib.region_probe_programs(
        node, region.member_uids, mesh, cfg)
    if probe is None:
        return {}
    fused, staged, input_uids, arrays, root_uid = probe

    def run_fused():
        jax.block_until_ready(fused(*(arrays[u] for u in input_uids)))

    def run_staged():
        env = dict(arrays)
        for n, fn, ins in staged:
            env[n.uid] = fn(*(env[u] for u in ins))
        jax.block_until_ready(env[root_uid])

    results: Dict[str, float] = {}
    for name, go in (("fused", run_fused), ("staged", run_staged)):
        try:
            go()                      # compile + warm every unit
            ts = []
            for _ in range(max(n_times, 1)):
                t0 = time.perf_counter()
                go()
                ts.append(time.perf_counter() - t0)
            ts.sort()
            t = ts[len(ts) // 2]
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a region variant failing to build/compile drops out of the table
            _log_dropped(name, e)
            continue
        if t > 0.0:
            results[name] = t
    return results


def _find_region_root(root_tree, uid: int):
    from matrel_tpu.ir import fusion as fusion_lib
    return fusion_lib._find_uid(root_tree, uid)


def lookup_or_measure_fusion(region, root_tree, mesh,
                             config: Optional[MatrelConfig] = None
                             ) -> Optional[str]:
    """The fusion pass's boundary consult (config.autotune on):
    "fused" / "staged" / None (no measured preference — the region
    stamps by default, the model's pick). Same table discipline as the
    matmul/SpMV/SpGEMM/reshard loops: in-process cache → persisted
    table → measure once (bounded probe side); ties and one-variant
    result sets resolve to None and are never fake winners."""
    cfg = config or default_config()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    node = _find_region_root(root_tree, region.root_uid)
    side = max([1] + [d for u in (region.member_uids
                                  + (region.root_uid,))
                      for d in _member_dims(root_tree, u)])
    key = _fusion_key(region.sig, side, gx, gy,
                      mesh_lib.axis_weights(mesh, cfg))
    if key in _FUSION_CACHE:
        return _FUSION_CACHE[key]
    entry = _load_table_cached(_table_path(cfg)).get(key)
    if isinstance(entry, dict) and entry.get("times"):
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _FUSION_CACHE[key] = best
        return best
    if node is None or side > cfg.autotune_max_dim:
        _FUSION_CACHE[key] = None
        return None
    results = measure_fusion_region(region, root_tree, mesh, cfg)
    if len(results) < 2:
        _FUSION_CACHE[key] = None
        return None
    best = _pick_winner(results)
    _FUSION_CACHE[key] = best
    if cfg.autotune or cfg.autotune_table_path:
        _persist(_table_path(cfg), key, best, results)
    return best


def _member_dims(root_tree, uid: int):
    n = _find_region_root(root_tree, uid)
    return tuple(n.shape) if n is not None else ()


# ---------------------------------------------------------------------------
# Reshard plan-vs-naive measurement (round 10) — the closed loop for the
# staged redistribution planner (parallel/reshard.py): per
# (src->dst, side class, grid, backend) shape class, time the compiled
# step sequence against the legacy one-shot sharding constraint and
# persist the winner like matmul strategies, so a backend where XLA's
# own one-shot move beats the staged chain keeps it (the executor's
# staged lowering consults this before applying steps).
# ---------------------------------------------------------------------------

_RESHARD_CACHE: Dict[str, Optional[str]] = {}

RESHARD_VARIANTS = ("staged", "naive")


def _reshard_key(plan, gx: int, gy: int,
                 weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    """``reshard|src>dst|<=side|gxXgy|backend[|w..]`` — side bucketed
    to the power of two above sqrt(nbytes/4), the drift auditor's
    shape-class granularity, so a 3800² and a 4096² move share a row.
    Backend (and non-uniform weights) key like every other table row:
    a CPU winner has nothing to say about Mosaic."""
    side = math.sqrt(max(plan.nbytes / 4.0, 1.0))
    cls = 1 << max(0, math.ceil(math.log2(max(side, 1.0))))
    key = (f"reshard|{plan.src}>{plan.dst}|{cls}|{gx}x{gy}"
           f"|{jax.default_backend()}")
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def measure_reshard_variant(variant: str, plan, mesh,
                            config: Optional[MatrelConfig] = None,
                            n_times: int = 5) -> float:
    """Median seconds for one lowering of the plan's move at its shape
    class, on a square f32 probe padded to the mesh (the matmul-probe
    discipline). "naive" is a single constraint to the destination
    sharding (XLA's own collective choice); "staged" applies the
    compiled step sequence."""
    import numpy as np
    from jax.sharding import NamedSharding
    from matrel_tpu.core import padding
    from matrel_tpu.parallel import reshard as reshard_lib
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    p = max(gx * gy, 1)
    side = int(round(math.sqrt(max(plan.nbytes / 4.0, 1.0))))
    side = max(p, -(-side // p) * p)            # divisible probe
    probe = reshard_lib.compile_reshard(
        plan.src, plan.dst, float(side) * side * 4, gx, gy,
        plan.weights, peak_budget=plan.peak_bytes or 0.0)
    src_sh = NamedSharding(mesh, reshard_lib._state_spec(plan.src,
                                                         mesh))
    dst_sh = NamedSharding(mesh, reshard_lib._state_spec(plan.dst,
                                                         mesh))
    x = jax.device_put(  # matlint: disable=ML008 measurement-probe input placement — the harness's own array, not a lowering re-lay
        np.random.default_rng(0).standard_normal(
            (side, side)).astype(np.float32), src_sh)
    if variant == "naive":
        f = jax.jit(lambda v: jax.lax.with_sharding_constraint(v,  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path
                                                               dst_sh))
    else:
        f = jax.jit(lambda v: reshard_lib.apply_staged(v, probe, mesh))  # matlint: disable=ML010 measurement probe — the autotune loop times candidates outside the plan path
    f(x).block_until_ready()                    # compile + warm
    ts = []
    for _ in range(max(n_times, 1)):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def lookup_or_measure_reshard(plan, mesh,
                              config: Optional[MatrelConfig] = None
                              ) -> Optional[str]:
    """Measured lowering for this reshard's shape class ("staged" /
    "naive"), or None when the model's pick should stand (ties, shapes
    above autotune_max_dim — measuring would allocate the probe —
    single-step plans, or a variant failing to compile). Same table
    discipline as the matmul/SpMV loops."""
    cfg = config or default_config()
    if len(plan.steps) < 2:
        return None          # staged == naive: nothing to compare
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    key = _reshard_key(plan, gx, gy, mesh_lib.axis_weights(mesh, cfg))
    if key in _RESHARD_CACHE:
        return _RESHARD_CACHE[key]
    entry = _load_table_cached(_table_path(cfg)).get(key)
    if isinstance(entry, dict) and entry.get("times"):
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _RESHARD_CACHE[key] = best
        return best
    if math.sqrt(max(plan.nbytes / 4.0, 1.0)) > cfg.autotune_max_dim:
        _RESHARD_CACHE[key] = None
        return None
    results: Dict[str, float] = {}
    for v in RESHARD_VARIANTS:
        try:
            t = measure_reshard_variant(v, plan, mesh, cfg)
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a variant failing to compile on this backend drops out of the table
            _log_dropped(v, e)
            continue
        if t > 0.0:
            results[v] = t
    if len(results) < 2:
        _RESHARD_CACHE[key] = None
        return None
    best = _pick_winner(results)
    _RESHARD_CACHE[key] = best
    if cfg.autotune or cfg.autotune_table_path:
        _persist(_table_path(cfg), key, best, results)
    return best


# ---------------------------------------------------------------------------
# IVM patch-vs-recompute measurement (round 14) — the closed loop for the
# delta plane (serve/ivm.py; docs/IVM.md): per (delta rule, shape class,
# grid, backend), time the compiled patch plan's steady-state run against
# a fresh full-recompute plan's run and persist the winner like every
# other table family, so a backend where recompute beats the algebraic
# patch (tiny shapes, fat deltas) KILLS the entry instead of patching at
# a loss — the measured winner overrides the flop estimate, the `fuse|`
# precedent.
# ---------------------------------------------------------------------------

_IVM_CACHE: Dict[str, Optional[str]] = {}

IVM_VARIANTS = ("patch", "recompute")


def _ivm_key(rule: str, side: int, gx: int, gy: int,
             weights: Tuple[float, float] = (1.0, 1.0)) -> str:
    """``ivm|<rule>|<=side|gxXgy|backend[|w..]`` — side bucketed to the
    power of two at or above it (the drift auditor's shape-class
    granularity), rule from ir/delta.DELTA_RULES."""
    cls = 1 << max(0, math.ceil(math.log2(max(side, 1))))
    key = f"ivm|{rule}|{cls}|{gx}x{gy}|{jax.default_backend()}"
    if weights != (1.0, 1.0):
        key += f"|w{weights[0]:g}x{weights[1]:g}"
    return key


def lookup_or_measure_ivm(rule: str, side: int, mesh,
                          config: Optional[MatrelConfig] = None,
                          patch_s=None, full_s=None) -> Optional[str]:
    """Measured patch-vs-recompute winner for one (rule, shape class):
    "patch" / "recompute" / None (no measured preference — the flop
    estimate decides). ``patch_s``/``full_s`` are zero-arg callables
    returning median steady-state seconds for the two forms, invoked
    at most once each (the delta plane passes timed runs of plans it
    holds anyway); lookups without runners never measure. Ties and
    one-variant sets resolve to None and are never fake winners —
    the fusion loop's discipline verbatim."""
    cfg = config or default_config()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    key = _ivm_key(rule, side, gx, gy, mesh_lib.axis_weights(mesh, cfg))
    if key in _IVM_CACHE:
        return _IVM_CACHE[key]
    entry = _load_table_cached(_table_path(cfg)).get(key)
    if isinstance(entry, dict) and entry.get("times"):
        best = entry.get("best")
        best = best if isinstance(best, str) else None
        _IVM_CACHE[key] = best
        return best
    if patch_s is None or full_s is None or side > cfg.autotune_max_dim:
        # no negative caching without a measurement: a later call that
        # CAN measure (runners in hand) must still get its chance
        return None
    results: Dict[str, float] = {}
    for name, fn in (("patch", patch_s), ("recompute", full_s)):
        try:
            t = float(fn())
        except Exception as e:  # noqa: BLE001  # matlint: disable=ML007 measurement loop — a variant failing on this backend drops out of the table
            _log_dropped(name, e)
            continue
        if t > 0.0:
            results[name] = t
    if len(results) < 2:
        _IVM_CACHE[key] = None
        return None
    best = _pick_winner(results)
    _IVM_CACHE[key] = best
    if cfg.autotune or cfg.autotune_table_path:
        _persist(_table_path(cfg), key, best, results)
    return best
