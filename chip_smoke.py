#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip: the driver's configurations 1, 2, 4 and 5 (BASELINE.json
``configs``; the third, the regression, is the benchmark's cells
``linreg_10m_1c`` and ``linreg_10m_2x2``) plus one S x S product,
answered on a real TPU through the normal entry points (the bridge
server, ``session.submit``/``compute``, the workloads), each answer
compared with numpy/scipy on the same seeded data.

    python chip_smoke.py                # one chip, one process (the driver)
    python chip_smoke.py --chips 4      # ONLY the 2x2-mesh phase (builder)
    python chip_smoke.py --rehearse --scale 0.02   # CPU, interpret mode

One JSON object per query on stdout; the LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only when the device is a TPU and every query passed. Without a TPU the
script exits non-zero before running anything. ``--rehearse`` relaxes
exactly that device check (CPU, Pallas in interpret mode) so the control
flow can be tried without a chip; a rehearsal never prints ``"ok": true``.
Where a size is cut (``--scale`` < 1) rows are cut, never widths, and the
cut is printed. Timing is the host clock around ``block_until_ready``.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

_failures: list = []


def emit(**rec) -> None:
    """One query line. ``pass`` False marks the run failed."""
    if rec.get("pass") is False:
        _failures.append(rec.get("query"))
    print(json.dumps(rec, default=str), flush=True)


def timed(fn):
    """(result, seconds) with the result materialised on the host clock."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def first_and_warm(fn):
    """(result, first-call seconds, warm seconds)."""
    out, first = timed(fn)
    out, warm = timed(fn)
    return out, round(first, 4), round(warm, 6)


def rel_err(got, want) -> float:
    """max |got - want| over max |want| — float64 on the host."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def require_kernel(hlo_text: str, what: str) -> bool:
    """The compiled program of a Pallas query must hold the kernel. (A
    rehearsal's interpret mode lowers the kernel to plain HLO: there the
    answer is False and reported, and only there it is not a failure.)"""
    ok = "tpu_custom_call" in hlo_text
    if not ok and _on_tpu():
        print(f"chip_smoke: {what}: compiled program has no "
              f"tpu_custom_call — the XLA fallback ran", file=sys.stderr)
    return ok


def kernel_ok(has_kernel: bool) -> bool:
    return has_kernel or not _on_tpu()


def chain_parenthesization(plan_text: str, names) -> str:
    """The optimized plan's matmul tree as ``(A·(B·C))``: explain prints
    an indented tree whose leaves keep chain order."""
    lines = plan_text.split("== Optimized plan ==")[1].splitlines()
    nodes = []
    for ln in lines:
        if ln.startswith("=="):
            break
        if ln.strip():
            nodes.append(((len(ln) - len(ln.lstrip())) // 2,
                          ln.split()[0]))
    it = iter(names)

    def build(i):
        depth, kind = nodes[i]
        kids, j = [], i + 1
        while j < len(nodes) and nodes[j][0] > depth:
            if nodes[j][0] == depth + 1:
                kids.append(build(j))
            j += 1
        if kind == "leaf":
            return next(it)
        if kind == "matmul" and len(kids) == 2:
            return f"({kids[0]}·{kids[1]})"
        return f"{kind}({','.join(kids)})"

    return build(0)


def round_to(x: float, mult: int, floor: int) -> int:
    return max(floor, int(round(x / mult)) * mult)


# -- phases (one chip) --------------------------------------------------------


def phase_bridge(sess, sizes, seed, tol):
    """Configurations 1 and 2 through BridgeServer/BridgeClient over
    loopback (what ``python -m matrel_tpu serve`` runs). Oracle operands
    are read from the server's catalog in-process (a 4096^2 ``fetch``
    would serialise 16M numbers through JSON); the ANSWERS travel the
    wire."""
    import numpy as np
    from matrel_tpu.bridge import BridgeClient, BridgeServer

    srv = BridgeServer(session=sess)
    thread = srv.serve_background()
    cli = BridgeClient("127.0.0.1", srv.port)
    n, big, small = sizes["dense_n"], sizes["chain_big"], sizes["chain_small"]
    try:
        cli.call("create_random", name="M", shape=[n, n], seed=seed)
        cli.call("create_random", name="N", shape=[n, n], seed=seed + 1)
        cli.call("create_random", name="A", shape=[big, small], seed=seed + 2)
        cli.call("create_random", name="B", shape=[small, big], seed=seed + 3)
        cli.call("create_random", name="C", shape=[big, small], seed=seed + 4)
        host = {k: sess.table(k).to_numpy().astype(np.float64)
                for k in ("M", "N", "A", "B", "C")}
        queries = [
            ("dense_multiply", "rowsum(M * N)",
             (host["M"] @ host["N"]).sum(1, keepdims=True),
             {"M": [n, n], "N": [n, n]}),
            ("chain_reorder", "rowsum(A * B * C)",
             (host["A"] @ (host["B"] @ host["C"])).sum(1, keepdims=True),
             {"A": [big, small], "B": [small, big], "C": [big, small]}),
            ("relational_select_aggregate",
             'SELECT rowcount(select(M, "v > 0.9")) FROM M',
             (host["M"] > 0.9).sum(1, keepdims=True),
             {"M": [n, n]}),
        ]
        del host
        for name, q, want, shapes in queries:
            t0 = time.perf_counter()
            r1 = cli.call("sql", query=q)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            r2 = cli.call("sql", query=q)
            warm = time.perf_counter() - t0
            err = max(rel_err(r1["data"], want), rel_err(r2["data"], want))
            emit(query=f"bridge.{name}", sql=q, shapes=shapes,
                 dtype="float32", executor="session.compute via bridge "
                 "sql (one jitted XLA program)",
                 first_call_s=round(first, 4), warm_s=round(warm, 6),
                 result_shape=r1["shape"], max_err=err,
                 **{"pass": err <= tol})
        plan = cli.call("explain", query="A * B * C")["plan"]
        shape = chain_parenthesization(plan, "ABC")
        emit(query="bridge.explain_chain", sql="A * B * C",
             executor="session.explain via bridge",
             plan=[ln for ln in plan.splitlines() if ln.strip()][:12],
             optimized_order=shape, **{"pass": shape == "(A·(B·C))"})
        cli.call("shutdown")
    finally:
        cli.close()
        srv.server_close()
        thread.join(timeout=10)
    return [q for _, q, _, _ in queries], [w for _, _, w, _ in queries]


def phase_submit(sess, queries, refs, tol):
    """The same queries through ``session.submit`` twice with the result
    cache on: the second round is answered from the cache."""
    import numpy as np

    def one_round():
        t0 = time.perf_counter()
        futs = [sess.submit(sess.sql(q)) for q in queries]
        outs = [f.result(timeout=600).to_numpy() for f in futs]
        sess.serve_drain(timeout=600)
        return outs, time.perf_counter() - t0

    before = sess.result_cache_info()
    outs1, t1 = one_round()
    mid = sess.result_cache_info()
    outs2, t2 = one_round()
    after = sess.result_cache_info()
    same = all(np.array_equal(a, b) for a, b in zip(outs1, outs2))
    hits2 = after["hits"] - mid["hits"]
    miss2 = after["misses"] - mid["misses"]
    err = max(rel_err(o, r) for o, r in zip(outs2, refs))
    ok = (same and hits2 == len(queries) and miss2 == 0
          and mid["misses"] - before["misses"] == len(queries)
          and err <= tol)
    emit(query="session.submit_twice", n_queries=len(queries),
         executor="serve pipeline (micro-batched admission) + result cache",
         first_call_s=round(t1, 4), warm_s=round(t2, 6),
         round2_hits=hits2, round2_misses=miss2, round2_bit_equal=same,
         counters={"before": before, "after_round1": mid,
                   "after_round2": after},
         max_err=err, **{"pass": ok})
    sess.serve_close(timeout=60)


def phase_spmm(sizes, seed, mesh, cfg):
    """Configuration 4: block-sparse x dense, f32 and bf16."""
    import jax.numpy as jnp
    import numpy as np
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.ops import spmm as spmm_lib

    n, bs, width = sizes["spmm_n"], 512, 512
    for dtype, tol in (("float32", 1e-4), ("bfloat16", 2e-2)):
        S = BlockSparseMatrix.random((n, n), block_density=0.01,
                                     block_size=bs, mesh=mesh, seed=seed,
                                     dtype=dtype, config=cfg)
        D = BlockMatrix.random((n, width), mesh=mesh, seed=seed + 1,
                               dtype=dtype, config=cfg)
        out, first, warm = first_and_warm(
            lambda: spmm_lib.spmm(S, D, cfg).data)
        # which runner the cache built for THIS matrix
        runners = [r for k, r in spmm_lib._RUNNER_CACHE.items()
                   if k[0] == id(S)]
        executor = sorted({f"{r.__module__}.{r.__qualname__}"
                           for r in runners})
        pallas = all("pallas_spmm" in e for e in executor) and executor
        has_kernel = bool(pallas) and all(require_kernel(
            r.jitted.lower(*r.baked_args, D.data).compile().as_text(),
            f"spmm {dtype}") for r in runners)
        # sampled block rows against float64 on the host
        br = np.asarray(S.block_rows)
        bc = np.asarray(S.block_cols)
        rng = np.random.default_rng(seed)
        rows = rng.choice(np.unique(br), size=min(16, np.unique(br).size),
                          replace=False)
        Dh = np.asarray(D.data[:n].astype(jnp.float32), np.float64)
        err = 0.0
        for i in rows:
            want = np.zeros((bs, width), np.float64)
            for t in np.nonzero(br == i)[0]:
                tile = np.asarray(S.blocks[int(t)].astype(jnp.float32),
                                  np.float64)
                want += tile @ Dh[bc[t] * bs:(bc[t] + 1) * bs, :width]
            got = np.asarray(
                out[i * bs:(i + 1) * bs, :width].astype(jnp.float32))
            err = max(err, rel_err(got, want))
        # an empty block row must be exact zeros
        empty = np.setdiff1d(np.arange(S.grid[0]), br)
        if empty.size:
            i = int(empty[0])
            z = np.asarray(out[i * bs:(i + 1) * bs].astype(jnp.float32))
            if np.any(z != 0):
                err = float("inf")
        emit(query=f"spmm.block_sparse_x_dense.{dtype}",
             shapes={"S": [n, n], "D": [n, width]}, dtype=dtype,
             tiles=int(S.nnzb), block_size=bs,
             executor=executor, kernel="pallas_spmm" if pallas else "xla",
             tpu_custom_call=has_kernel, first_call_s=first, warm_s=warm,
             max_err=err, check=f"{rows.size} sampled block rows in "
             "float64 (a full host product is 100+ GFLOP)",
             **{"pass": bool(pallas) and kernel_ok(has_kernel)
                and err <= tol})


def pagerank_oracle(src, dst, n, rounds=30, alpha=0.85):
    """scipy float64 power iteration with the workload's semantics."""
    import numpy as np
    import scipy.sparse as sp
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1e-30), 0.0)
    At = sp.csr_matrix((inv[src], (dst, src)), shape=(n, n))
    dangling = outdeg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(rounds):
        r = alpha * (At @ r + r[dangling].sum() / n) + (1 - alpha) / n
    return r


def pagerank_graph(sizes, seed):
    import numpy as np
    n, m = sizes["pr_nodes"], sizes["pr_edges"]
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n, m).astype(np.int32),
            rng.integers(0, n, m).astype(np.int32), n, m)


def phase_pagerank(sizes, seed, mesh=None):
    """Configuration 5 through the edge-list entry. ``mesh`` set runs
    the sharded compact executor (the --chips 4 phase)."""
    import jax
    import numpy as np
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib
    from matrel_tpu.workloads import pagerank as pr

    src, dst, n, m = pagerank_graph(sizes, seed)
    rounds = 30
    # "auto" is what a user gets; off the TPU auto is the segment-sum
    # path by design, so a rehearsal names the executor explicitly
    impl = "auto" if _on_tpu() else "onehot"
    before = pr.path_counts()
    t0 = time.perf_counter()
    r = jax.block_until_ready(
        pr.pagerank_edges(src, dst, n, rounds=rounds, mesh=mesh, impl=impl))
    first = time.perf_counter() - t0
    r, warm = timed(lambda: pr.pagerank_edges(src, dst, n, rounds=rounds,
                                              mesh=mesh, impl=impl))
    # which executor answered, from the workload's public counts
    ran = [k for k, v in pr.path_counts().items() if v > before[k]]
    want_runner = "compact_sharded" if mesh is not None else "compact"
    has_kernel, plan_info, resident = False, None, None
    if ran == [want_runner]:
        (cached,) = pr._PLAN_CACHE
        plan, dangling = cached.prepared
        nb, cap = np.asarray(plan.src8).shape
        plan_info = {"blocks": int(nb), "slots_per_block": int(cap),
                     "block_rows": int(plan.block), "lo": spmv_lib.LO,
                     "overflow": len(plan.overflow),
                     "max_slots_gate": pr._auto_max_slots()
                     * (mesh.size if mesh is not None else 1)}
        static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
        passes = 3
        if mesh is None:
            tables = pc.compact_tables(plan)
            hlo = pr._compact_runner_loop(
                plan.n_rows, rounds, 0.85, static, len(plan.overflow),
                passes, not _on_tpu()) \
                .lower(tables, plan.overflow, dangling).compile().as_text()
        else:
            tables = pc.shard_compact_tables(plan, mesh)
            resident = sorted({len(t.sharding.device_set) for t in tables})
            hlo = pr._compact_sharded_loop(
                int(n), rounds, 0.85, static, len(plan.overflow), passes,
                not _on_tpu(), mesh) \
                .lower(*tables, dangling, *plan.overflow) \
                .compile().as_text()
        has_kernel = require_kernel(hlo, "pagerank")
    want = pagerank_oracle(src, dst, n, rounds)
    got = np.asarray(r)
    err = rel_err(got, want)
    ok = (ran == [want_runner] and kernel_ok(has_kernel) and err <= 1e-4
          and (mesh is None or resident == [mesh.size]))
    emit(query="pagerank.edges" + (".sharded" if mesh is not None else ""),
         shapes={"nodes": n, "edges": m}, rounds=rounds, dtype="float32",
         impl=impl, executor=ran, kernel="compact-table Pallas SpMV (passes=3)"
         if ran == [want_runner] else "FALLBACK",
         plan=plan_info, tables_resident_on_devices=resident,
         tpu_custom_call=has_kernel, first_call_s=round(first, 4),
         warm_s=round(warm, 6), ms_per_round=round(1e3 * warm / rounds, 4),
         rank_sum=float(got.sum()), max_err=err,
         check="scipy float64 power iteration, all nodes",
         **{"pass": ok})


def _on_tpu() -> bool:
    from matrel_tpu.config import on_tpu
    return on_tpu()


def phase_spgemm(sess, sizes, seed):
    """One S x S product through ``session.compute``: routed by
    executor._spgemm_dispatch, kernel from the plan's stamp."""
    import jax.numpy as jnp
    import numpy as np
    from matrel_tpu import executor as executor_lib
    from matrel_tpu.core.sparse import BlockSparseMatrix

    n, bs = sizes["spgemm_n"], 512
    g = n // bs
    density = math.sqrt(0.1 / g)
    A = BlockSparseMatrix.random((n, n), block_density=density,
                                 block_size=bs, mesh=sess.mesh, seed=seed,
                                 config=sess.config)
    B = BlockSparseMatrix.random((n, n), block_density=density,
                                 block_size=bs, mesh=sess.mesh,
                                 seed=seed + 1, config=sess.config)
    e = A.multiply(B)
    dispatched = executor_lib._spgemm_dispatch(e, sess.config)
    out, first, warm = first_and_warm(lambda: sess.compute(e).data)
    plan = sess.compile(e)
    stamps = []

    def walk(node):
        if "spgemm_kernel" in node.attrs:
            stamps.append((node.attrs["spgemm_kernel"],
                           node.attrs.get("spgemm_kernel_source"),
                           node.attrs.get("spgemm_structure")))
        for c in node.children:
            walk(c)

    walk(plan.optimized)
    has_kernel = require_kernel(plan.hlo(), "spgemm")
    # reference: float64 tile products on the host, block row by block row
    ar, ac = np.asarray(A.block_rows), np.asarray(A.block_cols)
    brr, bcc = np.asarray(B.block_rows), np.asarray(B.block_cols)
    Ab = np.asarray(A.blocks, np.float64)
    Bb = np.asarray(B.blocks, np.float64)
    err, pairs = 0.0, 0
    for i in range(g):
        want = np.zeros((bs, n), np.float64)
        for ta in np.nonzero(ar == i)[0]:
            for tb in np.nonzero(brr == ac[ta])[0]:
                j = bcc[tb]
                want[:, j * bs:(j + 1) * bs] += Ab[ta] @ Bb[tb]
                pairs += 1
        got = np.asarray(out[i * bs:(i + 1) * bs, :n].astype(jnp.float32))
        if want.any() or got.any():
            err = max(err, float(np.max(np.abs(got - want))))
    err = err / max(float(bs) * 0.25, 1e-30)    # scale: E[sum of bs u*u]
    pallas = bool(stamps) and all(s[0].startswith("pallas") for s in stamps)
    emit(query="spgemm.session_compute", shapes={"A": [n, n], "B": [n, n]},
         dtype="float32", block_size=bs, tiles=[int(A.nnzb), int(B.nnzb)],
         tile_pairs=pairs, dispatched_spgemm=bool(dispatched),
         executor=[list(s) for s in stamps],
         kernel=stamps[0][0] if stamps else None,
         tpu_custom_call=has_kernel, first_call_s=first, warm_s=warm,
         max_err=err, check="every block row against float64 tile "
         "products on the host",
         **{"pass": bool(dispatched) and pallas and kernel_ok(has_kernel)
            and pairs > 0 and err <= 1e-4})


# -- the four-chip phase ------------------------------------------------------


def phase_mesh_strategies(sizes, seed, mesh, cfg):
    """A bf16 product under each forced strategy on the 2x2 mesh against
    the one-device product, compared on the device."""
    import jax
    import jax.numpy as jnp
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession

    n = sizes["mesh_n"]
    A = BlockMatrix.random((n, n), mesh=mesh, seed=seed, dtype="bfloat16",
                           config=cfg)
    B = BlockMatrix.random((n, n), mesh=mesh, seed=seed + 1,
                           dtype="bfloat16", config=cfg)
    dev0 = mesh.devices.flat[0]
    a0 = jax.device_put(A.data, dev0)
    b0 = jax.device_put(B.data, dev0)
    ref = jax.block_until_ready(jax.jit(
        lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32))(
            a0, b0))
    ref_max = float(jnp.max(jnp.abs(ref)))
    diff = jax.jit(lambda o, r: jnp.max(jnp.abs(
        o.astype(jnp.float32) - r)))
    for strategy in ("bmm_right", "cpmm", "rmm", "summa", "xla"):
        scfg = cfg.replace(strategy_override=strategy)
        sess = MatrelSession(mesh=mesh, config=scfg)
        e = A.multiply(B)
        out, first, warm = first_and_warm(lambda: sess.compute(e).data)
        plan = sess.compile(e)
        chosen = sorted(plan.meta["executors"])
        spans = len(out.sharding.device_set)
        err = float(diff(jax.device_put(out, dev0), ref)) / ref_max
        emit(query=f"mesh.matmul.{strategy}", shapes={"A": [n, n],
                                                      "B": [n, n]},
             dtype="bfloat16", executor=chosen,
             collectives=plan.collectives(),
             output_sharding=str(out.sharding.spec),
             output_devices=spans, first_call_s=first, warm_s=warm,
             max_err=err, check="one-device product, compared on device",
             **{"pass": chosen == [strategy] and spans == mesh.size
                and err <= 2e-2})


def phase_fleet(sizes, seed, mesh, cfg):
    """fleet_slices=4 answering a repeated batch: queries placed on more
    than one slice, the repeat answered through the directory."""
    import numpy as np
    from matrel_tpu.session import MatrelSession

    # serving-sized operands: the placement model (serve/placement.py)
    # keeps a query slice-local while its FLOPs are small against the
    # cross-slice bytes (crossover near n = 356 at the analytic
    # coefficients: rowsum of a 356^2 product) and SPANS the whole mesh
    # above — one big query rides along to show that side too
    n, big = sizes["fleet_n"], sizes["fleet_big_n"]
    fcfg = cfg.replace(fleet_slices=4,
                       result_cache_max_bytes=256 * 1024 * 1024)
    sess = MatrelSession(mesh=mesh, config=fcfg)
    names = []
    for i in range(4):
        sess.register(f"F{i}", sess.random((n, n), seed=seed + 10 + i))
        names.append(f"F{i}")
    sess.register("G", sess.random((big, big), seed=seed + 20))
    host = {k: sess.table(k).to_numpy().astype(np.float64)
            for k in names + ["G"]}
    queries = [(f"rowsum({a} * {b})",
                (host[a] @ host[b]).sum(1, keepdims=True))
               for a in names for b in names if a < b]
    n_small = len(queries)
    queries.append(("rowsum(G * G)",
                    (host["G"] @ host["G"]).sum(1, keepdims=True)))

    def one_round():
        t0 = time.perf_counter()
        futs = [sess.submit(sess.sql(q)) for q, _ in queries]
        outs = [f.result(timeout=600).to_numpy() for f in futs]
        sess.serve_drain(timeout=600)
        return outs, time.perf_counter() - t0

    outs1, t1 = one_round()
    info1 = sess.fleet_info()
    outs2, t2 = one_round()
    info2 = sess.fleet_info()
    err = max(rel_err(o, w) for o, (_, w) in zip(outs1 + outs2,
                                                 queries + queries))
    used = [s["id"] for s in info2["slices"] if s["submitted"] > 0]
    hits = info2["directory"]["hits"] - info1["directory"]["hits"]
    emit(query="fleet.repeated_batch", n_queries=len(queries),
         shapes={"F0..F3": [n, n], "G": [big, big]}, dtype="float32",
         executor="serve.fleet.FleetController (4 slices of 1 device)",
         first_call_s=round(t1, 4), warm_s=round(t2, 6),
         slices_used=used, directory_hits_on_repeat=hits,
         fleet_info={"placed": info2["placed"],
                     "directory": info2["directory"],
                     "slices": [{k: s[k] for k in ("id", "alive", "devices",
                                                   "submitted")}
                                for s in info2["slices"]]},
         max_err=err,
         **{"pass": len(used) > 1 and hits >= n_small and err <= 1e-4})
    sess.serve_close(timeout=60)


# -- main ---------------------------------------------------------------------


def sizes_for(scale: float) -> dict:
    """README-scale sizes; ``scale`` < 1 cuts ROWS (and the square sizes
    that are rows x rows), never block or panel widths."""
    return {
        "dense_n": round_to(4096 * scale, 128, 256),
        "chain_big": round_to(10_000 * scale, 8, 200),
        "chain_small": 100,
        "spmm_n": round_to(100_352 * scale, 512, 2048),
        "pr_nodes": max(int(1_000_000 * scale), 4096),
        "pr_edges": max(int(10_000_000 * scale), 40_960),
        "spgemm_n": round_to(16_384 * scale, 512, 4096),
        "mesh_n": round_to(16_384 * scale, 512, 512),
        "fleet_n": 256,
        "fleet_big_n": round_to(2048 * scale, 128, 256),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the 2x2-mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="cut rows (never widths) for a rehearsal")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU + Pallas interpret mode; relaxes the device "
                    "check only and never prints \"ok\": true")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (found {device}); refusing to run",
              file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{device['count']} devices", file=sys.stderr)
        return 2

    from matrel_tpu.config import (MatrelConfig, configure_compile_cache,
                                   set_default_config)
    cache_dir = configure_compile_cache()

    # the native library is built HERE from native/*.cc: a stale or
    # foreign native/build/ (git-ignored, picked up by mtime) must not
    # stand in for it
    from matrel_tpu.utils import native
    shutil.rmtree(os.path.join(HERE, "native", "build"), ignore_errors=True)
    t0 = time.perf_counter()
    lib = native.load()
    plan_fill = "native (built from native/*.cc)" if lib is not None \
        else "numpy fallback (native build failed)"

    # git-ignored tables stay off: no autotune table, drift table or
    # event log is read or written by this run
    cfg = MatrelConfig(pallas_interpret=args.rehearse, autotune=False,
                       obs_level="off",
                       drift_table_path=os.path.join(OUT_DIR, "drift.json"))
    set_default_config(cfg)
    sizes = sizes_for(args.scale)
    mem0 = dev.memory_stats() or {}
    emit(query="setup", device=device, rehearsal=args.rehearse,
         jax=jax.__version__, compile_cache_dir=cache_dir,
         plan_fill=plan_fill, native_build_s=round(
             time.perf_counter() - t0, 3),
         pallas_interpret=cfg.pallas_interpret, seed=args.seed,
         scale=args.scale, sizes=sizes,
         cut=None if args.scale == 1.0 else
         f"rows cut by --scale {args.scale}; widths kept",
         hbm_budget_bytes_config=cfg.hbm_budget_bytes,
         memory_stats_bytes_limit=mem0.get("bytes_limit"),
         **{"pass": lib is not None})

    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.session import MatrelSession
    if args.chips == 1:
        mesh = mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        sess = MatrelSession(mesh=mesh, config=cfg)
        tol = 1e-4
        queries, refs = phase_bridge(sess, sizes, args.seed, tol)
        # a second session with the result cache ON over the same
        # catalog (the bridge's stays off so its warm call recomputes)
        cached = MatrelSession(mesh=mesh, config=cfg.replace(
            result_cache_max_bytes=256 * 1024 * 1024))
        for name in ("M", "N", "A", "B", "C"):
            cached.register(name, sess.table(name))
        phase_submit(cached, queries, refs, tol)
        phase_spmm(sizes, args.seed, mesh, cfg)
        phase_pagerank(sizes, args.seed)
        phase_spgemm(sess, sizes, args.seed)
    else:
        mesh = mesh_lib.make_mesh((2, 2), devices=jax.devices())
        phase_mesh_strategies(sizes, args.seed, mesh, cfg)
        phase_pagerank(sizes, args.seed, mesh=mesh)
        phase_fleet(sizes, args.seed, mesh, cfg)

    stats = {str(d): (d.memory_stats() or {}) for d in jax.devices()}
    in_use = {k: v.get("bytes_in_use") for k, v in stats.items()}
    mem_ok = True
    if args.chips == 4 and not args.rehearse:
        mem_ok = all((v or 0) > 0 for v in in_use.values())
    emit(query="memory", peak_bytes_in_use={
        k: v.get("peak_bytes_in_use") for k, v in stats.items()},
        bytes_in_use=in_use, bytes_limit={
            k: v.get("bytes_limit") for k, v in stats.items()},
        **{"pass": mem_ok})

    if _failures:
        print(f"chip_smoke: FAILED: {_failures}", file=sys.stderr)
        return 1
    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
