"""ctypes bridge to the native optimizer core (native/chain_dp.cc).

Builds libmatrel_opt.so on first use if g++ is available (no pybind11 in
this image — plain C ABI + ctypes per the environment constraints), caches
the handle, and degrades silently to the pure-Python DP when the toolchain
or library is unavailable.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np
from matrel_tpu.utils import lockdep

log = logging.getLogger("matrel_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libmatrel_opt.so")

_lock = lockdep.make_lock("native.build")
_lib: Optional[ctypes.CDLL] = None
_tried = False


_SOURCES = ("chain_dp.cc", "mtx_reader.cc", "spmv_plan.cc")


def _stale() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    lib_mtime = os.path.getmtime(_LIB_PATH)
    return any(
        os.path.exists(s) and os.path.getmtime(s) > lib_mtime
        for s in (os.path.join(_NATIVE_DIR, name) for name in _SOURCES)
    )


def _build() -> bool:
    srcs = [os.path.join(_NATIVE_DIR, s) for s in _SOURCES]
    srcs = [s for s in srcs if os.path.exists(s)]
    if not srcs:
        return False
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    cmd = ["g++", "-O3", "-fPIC", "-std=c++17", "-pthread", "-shared",
           "-o", _LIB_PATH] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native build failed (numpy fallbacks run): %s", e)
        return False


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _stale() and not _build():
            if not os.path.exists(_LIB_PATH):
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            log.debug("native load failed: %s", e)
            return None
        try:
            lib.matrel_chain_dp.restype = ctypes.c_int
            lib.matrel_chain_dp.argtypes = [
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.POINTER(ctypes.c_double),
            ]
            _has_dp = True
        except AttributeError as e:
            log.debug("native chain-dp symbols unavailable: %s", e)
            _has_dp = False
        lib._matrel_has_dp = _has_dp
        try:
            # comm-aware DP binds separately so a stale prebuilt lib
            # still serves the FLOPs-only DP
            lib.matrel_chain_dp_comm.restype = ctypes.c_int
            lib.matrel_chain_dp_comm.argtypes = [
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                ctypes.c_int32,
                ctypes.c_int32,
                ctypes.c_double,
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.POINTER(ctypes.c_double),
            ]
            lib._matrel_has_dp_comm = True
        except AttributeError as e:
            log.debug("native comm-aware chain-dp unavailable: %s", e)
            lib._matrel_has_dp_comm = False
        try:
            # layout-aware DP binds separately for the same stale-lib
            # tolerance reason
            lib.matrel_chain_dp_layout.restype = ctypes.c_int
            lib.matrel_chain_dp_layout.argtypes = [
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                ctypes.c_int32,
                ctypes.c_int32,
                ctypes.c_double,
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.POINTER(ctypes.c_double),
            ]
            lib._matrel_has_dp_layout = True
        except AttributeError as e:
            log.debug("native layout-aware chain-dp unavailable: %s", e)
            lib._matrel_has_dp_layout = False
        try:
            # topology-weighted DP binds separately for the same
            # stale-lib tolerance reason
            lib.matrel_chain_dp_topo.restype = ctypes.c_int
            lib.matrel_chain_dp_topo.argtypes = [
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                ctypes.c_int32,
                ctypes.c_int32,
                ctypes.c_double,
                ctypes.c_int32,
                ctypes.c_double,
                ctypes.c_double,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.POINTER(ctypes.c_double),
            ]
            lib._matrel_has_dp_topo = True
        except AttributeError as e:
            log.debug("native topology-weighted chain-dp unavailable: %s",
                      e)
            lib._matrel_has_dp_topo = False
        _lib = lib
        try:
            # Ingestion symbols bind separately so a stale prebuilt lib
            # (pre-mtx_reader) still serves the chain DP.
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
            lib.matrel_mtx_open.restype = ctypes.c_void_p
            lib.matrel_mtx_open.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
            ]
            lib.matrel_coo_csv_open.restype = ctypes.c_void_p
            lib.matrel_coo_csv_open.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
            lib.matrel_parse_fill.restype = ctypes.c_int64
            lib.matrel_parse_fill.argtypes = [
                ctypes.c_void_p, i64p, i64p, f64p, ctypes.c_int64]
            lib.matrel_parse_close.restype = None
            lib.matrel_parse_close.argtypes = [ctypes.c_void_p]
            _has_ingest = True
        except AttributeError as e:
            log.debug("native ingestion symbols unavailable: %s", e)
            _has_ingest = False
        lib._matrel_has_ingest = _has_ingest
        try:
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.matrel_spmv_counts.restype = ctypes.c_int
            lib.matrel_spmv_counts.argtypes = [
                i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i64p]
            lib.matrel_spmv_fill.restype = ctypes.c_int64
            lib.matrel_spmv_fill.argtypes = [
                i64p, i64p, ctypes.c_void_p,          # rows, cols, vals|NULL
                ctypes.c_int64, ctypes.c_int64,        # m, n_cols
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # block,nb,cap
                ctypes.c_int32,                        # width
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                i64p, i64p,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                ctypes.c_int64,                        # ov_cap
            ]
            lib.matrel_spmv_fill_ragged.restype = ctypes.c_int
            lib.matrel_spmv_fill_ragged.argtypes = [
                i64p, i64p, ctypes.c_void_p,          # rows, cols, vals|NULL
                ctypes.c_int64, ctypes.c_int64,        # m, n_cols
                ctypes.c_int64, ctypes.c_int64,        # block, nb
                i64p, ctypes.c_int32,                  # first, width
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ]
            _has_spmv = True
        except AttributeError as e:
            log.debug("native spmv-plan symbols unavailable: %s", e)
            _has_spmv = False
        lib._matrel_has_spmv = _has_spmv
        try:
            # the hub-chunk passes bind separately so a stale prebuilt
            # lib still serves the plain fills
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
            f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
            lib.matrel_spmv_counts_hubs.restype = ctypes.c_int
            lib.matrel_spmv_counts_hubs.argtypes = [
                i64p, i64p, ctypes.c_int64, ctypes.c_int64,   # rows,cols,m,n
                ctypes.c_int64, ctypes.c_int64, i32p, i64p]   # block,nb,rank
            lib.matrel_spmv_fill_ragged_hubs.restype = ctypes.c_int
            lib.matrel_spmv_fill_ragged_hubs.argtypes = [
                i64p, i64p, ctypes.c_void_p,          # rows, cols, vals|NULL
                ctypes.c_int64, ctypes.c_int64,        # m, n_cols
                ctypes.c_int64, ctypes.c_int64,        # block, nb
                i32p, ctypes.c_int32,                  # hub_rank, n_hubs
                i64p, i64p, ctypes.c_int32,            # first, hub_first, width
                i32p, np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                i32p, f32p,                            # src8, lane, off, val
                i32p, i32p, f32p,                      # hub idx, off, val
            ]
            lib._matrel_has_spmv_hubs = _has_spmv
        except AttributeError as e:
            log.debug("native hub-chunk symbols unavailable: %s", e)
            lib._matrel_has_spmv_hubs = False
        return _lib


def chain_dp(dims: Sequence[int], densities: Sequence[float],
             grid: Tuple[int, int] = (1, 1),
             comm_weight: Optional[float] = None,
             itemsize: int = 4,
             layouts: Optional[Sequence[int]] = None,
             weights: Optional[Tuple[float, float]] = None
             ) -> Optional[Tuple[np.ndarray, float]]:
    """Run the native interval DP. dims has n+1 entries; densities n.
    With grid != (1,1) the step cost adds the comm term (ir/stats.py::
    chain_step_cost semantics); non-trivial ``layouts`` (int codes,
    ir/stats.py::LAYOUT_CODES) make it layout-aware, and non-uniform
    per-axis ``weights`` (core/mesh.MeshTopology) make it
    topology-aware. Returns (split table [n,n] int32, total cost) or
    None if the native path is unavailable — including a stale prebuilt
    lib lacking the needed symbol (the caller's pure-Python DP then
    decides)."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_dp", False):
        return None
    n = len(densities)
    if len(dims) != n + 1:
        raise ValueError("dims must have len(densities)+1 entries")
    dims_arr = np.ascontiguousarray(dims, dtype=np.int64)
    dens_arr = np.ascontiguousarray(densities, dtype=np.float64)
    splits = np.zeros((n, n), dtype=np.int32)
    cost = ctypes.c_double(0.0)
    gx, gy = grid
    weighted = weights is not None and tuple(weights) != (1.0, 1.0)
    if gx * gy > 1:
        if comm_weight is None:
            from matrel_tpu.ir.stats import COMM_FLOPS_PER_BYTE
            comm_weight = COMM_FLOPS_PER_BYTE
        if layouts is not None and len(layouts) != n:
            raise ValueError("layouts must have one entry per operand")
        if weighted:
            # topology weights change the comm term for EVERY layout
            # (including all-2d), so the topo symbol is required — a
            # stale lib degrades to the pure-Python weighted DP rather
            # than silently pricing a flat fabric
            if not getattr(lib, "_matrel_has_dp_topo", False):
                return None
            lays_arr = np.ascontiguousarray(
                layouts if layouts is not None else [0] * n,
                dtype=np.int8)
            rc = lib.matrel_chain_dp_topo(
                n, dims_arr, dens_arr, lays_arr, int(gx), int(gy),
                float(comm_weight), int(itemsize), float(weights[0]),
                float(weights[1]), splits.reshape(-1),
                ctypes.byref(cost))
        elif layouts is not None and any(layouts):
            if not getattr(lib, "_matrel_has_dp_layout", False):
                return None
            lays_arr = np.ascontiguousarray(layouts, dtype=np.int8)
            rc = lib.matrel_chain_dp_layout(
                n, dims_arr, dens_arr, lays_arr, int(gx), int(gy),
                float(comm_weight), int(itemsize), splits.reshape(-1),
                ctypes.byref(cost))
        else:
            if not getattr(lib, "_matrel_has_dp_comm", False):
                return None
            rc = lib.matrel_chain_dp_comm(
                n, dims_arr, dens_arr, int(gx), int(gy),
                float(comm_weight), int(itemsize), splits.reshape(-1),
                ctypes.byref(cost))
    else:
        rc = lib.matrel_chain_dp(n, dims_arr, dens_arr,
                                 splits.reshape(-1), ctypes.byref(cost))
    if rc != 0:
        return None
    return splits, float(cost.value)


# -- native text ingestion (mtx_reader.cc) ----------------------------------

_MTX_SYMMETRIC = 1
_MTX_PATTERN = 2
_MTX_SKEW = 4
_MTX_COMPLEX = 8
_MTX_ARRAY = 16


def mtx_read(path: str) -> Optional[Tuple[Tuple[int, int], np.ndarray,
                                          np.ndarray, np.ndarray]]:
    """Parse a MatrixMarket file natively.

    Returns ((rows, cols), row_idx, col_idx, values) with symmetry already
    expanded (mirror/negated-mirror of off-diagonal entries), or None when
    the native library is unavailable or the file needs the scipy fallback
    (complex field, parse error).
    """
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_ingest", False):
        return None
    r = ctypes.c_int64(0)
    c = ctypes.c_int64(0)
    nnz = ctypes.c_int64(0)
    flags = ctypes.c_int32(0)
    h = lib.matrel_mtx_open(path.encode(), ctypes.byref(r), ctypes.byref(c),
                            ctypes.byref(nnz), ctypes.byref(flags))
    if not h:
        return None
    try:
        if flags.value & _MTX_COMPLEX:
            return None
        cap = max(1, nnz.value)
        ri = np.empty(cap, dtype=np.int64)
        ci = np.empty(cap, dtype=np.int64)
        vals = np.empty(cap, dtype=np.float64)
        got = lib.matrel_parse_fill(h, ri, ci, vals, cap)
    finally:
        lib.matrel_parse_close(h)
    if got < 0:
        return None
    ri, ci, vals = ri[:got], ci[:got], vals[:got]
    if flags.value & _MTX_SYMMETRIC:
        off = ri != ci
        mr, mc = ci[off], ri[off]
        mv = -vals[off] if flags.value & _MTX_SKEW else vals[off]
        ri = np.concatenate([ri, mr])
        ci = np.concatenate([ci, mc])
        vals = np.concatenate([vals, mv])
    return (r.value, c.value), ri, ci, vals


def coo_csv_read(path: str) -> Optional[Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]]:
    """Parse 'i,j,value' coordinate text natively (0-based indices as
    stored). Returns (row_idx, col_idx, values) or None if unavailable."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_ingest", False):
        return None
    n = ctypes.c_int64(0)
    h = lib.matrel_coo_csv_open(path.encode(), ctypes.byref(n))
    if not h:
        return None
    try:
        cap = max(1, int(n.value))
        ri = np.empty(cap, dtype=np.int64)
        ci = np.empty(cap, dtype=np.int64)
        vals = np.empty(cap, dtype=np.float64)
        got = lib.matrel_parse_fill(h, ri, ci, vals, cap)
    finally:
        lib.matrel_parse_close(h)
    if got < 0:
        return None
    return ri[:got], ci[:got], vals[:got]


# -- native SpMV plan layout (spmv_plan.cc) ---------------------------------


def spmv_counts(rows: np.ndarray, block: int, nb: int
                ) -> Optional[np.ndarray]:
    """Per-block edge counts (pass 1 of the plan build); None if the
    native path is unavailable."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_spmv", False):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    counts = np.zeros(nb, dtype=np.int64)
    rc = lib.matrel_spmv_counts(rows, rows.shape[0], block, nb, counts)
    return counts if rc == 0 else None


def spmv_fill(rows: np.ndarray, cols: np.ndarray,
              vals: Optional[np.ndarray], n_cols: int, block: int,
              nb: int, cap: int, width: int, n_overflow: int):
    """Pass 2: scatter edges into the padded plan tables. Returns
    (src8, lane, off, val, ov_rows, ov_cols, ov_vals) or None."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_spmv", False):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    m = rows.shape[0]
    src8 = np.empty((nb, cap), dtype=np.int32)
    lane = np.empty((nb, cap), dtype=np.int8)
    off = np.empty((nb, cap), dtype=np.int32)
    val = np.empty((nb, cap), dtype=np.float32)
    ov_cap = max(1, n_overflow)
    ov_r = np.empty(ov_cap, dtype=np.int64)
    ov_c = np.empty(ov_cap, dtype=np.int64)
    ov_v = np.empty(ov_cap, dtype=np.float32)
    if vals is not None:
        vals = np.ascontiguousarray(vals, dtype=np.float32)
        vptr = vals.ctypes.data_as(ctypes.c_void_p)
    else:
        vptr = None
    got = lib.matrel_spmv_fill(rows, cols, vptr, m, n_cols, block, nb,
                               cap, width, src8.reshape(-1),
                               lane.reshape(-1), off.reshape(-1),
                               val.reshape(-1), ov_r, ov_c, ov_v, ov_cap)
    if got < 0 or got != n_overflow:
        return None
    return (src8, lane, off, val, ov_r[:got], ov_c[:got], ov_v[:got])


def spmv_fill_ragged(rows: np.ndarray, cols: np.ndarray,
                     vals: Optional[np.ndarray], n_cols: int, block: int,
                     first: np.ndarray, width: int):
    """The chunks layout's pass 2: block b owns the flat slots
    ``first[b]:first[b + 1]``. Returns flat (src8, lane, off, val) and
    three empty overflow arrays (the shape ``spmv_fill`` answers in),
    or None."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_spmv", False):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    first = np.ascontiguousarray(first, dtype=np.int64)
    slots = int(first[-1])
    src8 = np.empty(slots, dtype=np.int32)
    lane = np.empty(slots, dtype=np.int8)
    off = np.empty(slots, dtype=np.int32)
    val = np.empty(slots, dtype=np.float32)
    if vals is not None:
        vals = np.ascontiguousarray(vals, dtype=np.float32)
        vptr = vals.ctypes.data_as(ctypes.c_void_p)
    else:
        vptr = None
    rc = lib.matrel_spmv_fill_ragged(rows, cols, vptr, rows.shape[0],
                                     n_cols, block, first.shape[0] - 1,
                                     first, width, src8, lane, off, val)
    if rc != 0:
        return None
    none = np.empty(0, dtype=np.int64)
    return (src8, lane, off, val, none, none, np.empty(0, np.float32))


def spmv_counts_hubs(rows: np.ndarray, cols: np.ndarray,
                     hub_rank: np.ndarray, block: int, nb: int
                     ) -> Optional[np.ndarray]:
    """Per-block counts of the edges whose source is a hub
    (``hub_rank[col] >= 0``); None if the native path is unavailable."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_spmv_hubs", False):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    hub_rank = np.ascontiguousarray(hub_rank, dtype=np.int32)
    counts = np.zeros(nb, dtype=np.int64)
    rc = lib.matrel_spmv_counts_hubs(rows, cols, rows.shape[0],
                                     hub_rank.shape[0], block, nb, hub_rank,
                                     counts)
    return counts if rc == 0 else None


def spmv_fill_ragged_hubs(rows: np.ndarray, cols: np.ndarray,
                          vals: Optional[np.ndarray], hub_rank: np.ndarray,
                          n_hubs: int, block: int, first: np.ndarray,
                          hub_first: np.ndarray, width: int):
    """The chunks layout's pass 2 with hub chunks, one walk over the
    edges: an edge whose source has a rank goes to the hub tables, every
    other to the main ones. Returns (the main tables in the shape
    ``spmv_fill_ragged`` answers in, flat (hub_idx, hub_off, hub_val)),
    or None."""
    lib = load()
    if lib is None or not getattr(lib, "_matrel_has_spmv_hubs", False):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    hub_rank = np.ascontiguousarray(hub_rank, dtype=np.int32)
    first = np.ascontiguousarray(first, dtype=np.int64)
    hub_first = np.ascontiguousarray(hub_first, dtype=np.int64)
    slots, hub_slots = int(first[-1]), int(hub_first[-1])
    main = (np.empty(slots, np.int32), np.empty(slots, np.int8),
            np.empty(slots, np.int32), np.empty(slots, np.float32))
    hub = (np.empty(hub_slots, np.int32), np.empty(hub_slots, np.int32),
           np.empty(hub_slots, np.float32))
    if vals is not None:
        vals = np.ascontiguousarray(vals, dtype=np.float32)
        vptr = vals.ctypes.data_as(ctypes.c_void_p)
    else:
        vptr = None
    rc = lib.matrel_spmv_fill_ragged_hubs(
        rows, cols, vptr, rows.shape[0], hub_rank.shape[0], block,
        first.shape[0] - 1, hub_rank, n_hubs, first, hub_first, width,
        *main, *hub)
    if rc != 0:
        return None
    none = np.empty(0, dtype=np.int64)
    return main + (none, none, np.empty(0, np.float32)), hub
