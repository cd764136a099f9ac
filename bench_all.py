"""Full BASELINE.md benchmark suite — one JSON line per config.

Rows (BASELINE.json configs):
  1. 4k×4k dense BlockMatrix multiply            → TFLOPS/chip
  2. chain A·B·C, 10k dims, skewed, DP reorder   → wall-clock + plan
  3. tall-skinny linreg 10M×1k (streaming Gram)  → wall-clock
  4. block-sparse × dense, 1% blocks, 100k×100k  → wall-clock + eff. TFLOPS
  4b. block-sparse × block-sparse SpGEMM, same S → wall-clock + crossover
  5. PageRank 1M nodes / 10M edges, 30 rounds    → wall-clock/round
  5b. PageRank 10M nodes / 100M edges (10×)      → wall-clock/round
  x1. conjugate gradient, implicit SPD 8k system → wall-clock + iters
  x2. power iteration, dense 8k, 50 rounds       → wall-clock
  x3. triangle count, dense 8k adjacency         → wall-clock + count
  6. north star 65k chain A·B·C                  → TFLOPS/chip
  (x-rows track the round-3 workload families — not BASELINE.json
  configs, but captured in the same batch so they get on-chip numbers)

Methodology notes: JAX returns before the device finishes, so every timing
forces a scalar fetch; fast ops use marginal timing over two repeat counts
(see bench.py). Run on the real chip: `python bench_all.py`; every row line
is stamped with the platform, device kind and device count it ran on, and
a failed row makes the exit code non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _timed(fn, warm: int = 1, reps: int = 3) -> float:
    """Median wall-clock of fn() (fn must block/fetch internally)."""
    for _ in range(warm):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def bench_dense_4k(mesh, cfg):
    import bench
    payload = bench.measure_tpu()      # {"tflops": ..., "phases": ...}
    return {"metric": "dense_blockmatmul_tflops_per_chip",
            "value": round(payload["tflops"], 2),
            "unit": "TFLOPS", "config": "4096x4096 bf16, f32 accumulate"}


def bench_spgemm(mesh, cfg):
    """S×S tile-intersection SpGEMM (ops/spgemm.py) at BASELINE row-4
    scale + the executor-dispatch crossover comparison vs the densify
    fallback at a reduced scale (see bench.measure_spgemm)."""
    import bench
    payload = bench.measure_spgemm()
    return {"metric": "blocksparse_spgemm_100k_1pct", **payload}


def bench_sparse_kernels(mesh, cfg):
    """Structure-specialized SpGEMM kernel sweep (ops/kernel_registry):
    per structure class, every relevant registered kernel vs the fixed
    pre-registry Pallas baseline, plus the autotune persist/replay
    proof (see bench.measure_sparse_kernels)."""
    import bench
    payload = bench.measure_sparse_kernels()
    return {"metric": "sparse_kernel_sweep", **payload}


def bench_fusion(mesh, cfg):
    """Whole-plan fusion sweep: the PageRank-step and linreg-epilogue
    chains as one jitted program per fused region vs one per physical
    op, ms + dispatch counts both ways (see bench.measure_fusion)."""
    import bench
    payload = bench.measure_fusion()
    return {"metric": "fusion_region_sweep", **payload}


def bench_serve(mesh, cfg):
    """Repeated-traffic serving QPS (matrel_tpu/serve/): mixed query
    stream, {result cache off/on} x {sequential/micro-batched} — the
    cross-query amortization row (see bench.measure_serve)."""
    import bench
    payload = bench.measure_serve()
    return {"metric": "serve_repeated_traffic_qps", **payload}


def bench_cse(mesh, cfg):
    """Shared-interior batch + plan-template row (serve/mqo.py;
    docs/SERVING.md): k dashboard variants over one Gram-polynomial
    interior, cse_enable off vs on at first contact, plus the
    rebound-leaf template replay (see bench.measure_cse)."""
    import bench
    payload = bench.measure_cse()
    return {"metric": "cse_shared_interior_batch", **payload}


def bench_traffic(mesh, cfg):
    """Open-loop overload traffic harness (tools/traffic.py;
    docs/OVERLOAD.md): seeded Poisson arrivals at 2x measured
    closed-loop capacity over 3 weighted tenants — per-tenant
    percentiles, goodput ratio, typed-shed counts, Jain fairness,
    brownout enter/exit. Run as a subprocess: the harness forces the
    CPU backend (it drills the control plane, not the chip) and must
    not re-initialise this process's backend."""
    import subprocess
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "traffic.py")],
        capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    if not lines:
        raise RuntimeError(
            f"traffic harness emitted no artifact (rc {proc.returncode}): "
            f"{proc.stderr[-400:]}")
    return json.loads(lines[-1])


def bench_fleet(mesh, cfg):
    """Multi-slice serving-fleet scale-out row (serve/fleet.py;
    docs/FLEET.md): aggregate QPS going 1 -> 2 virtual slices on the
    repeated-traffic stream whose working set only fits the fleet's
    AGGREGATE cache, plus the mid-stream slice-kill drill (see
    bench.measure_fleet)."""
    import bench
    payload = bench.measure_fleet()
    return {"metric": "fleet_scaleout_qps", **payload}


def bench_stream(mesh, cfg):
    """Streaming IVM row: the sliding-window graph dashboard's
    steady-state per-update latency, delta-patch vs full recompute
    (see bench.measure_stream; docs/IVM.md)."""
    import bench
    payload = bench.measure_stream()
    return {"metric": "stream_update_latency", **payload}


def bench_reshard(mesh, cfg):
    """Reshard-planner sweep: planned staged step sequences vs the
    naive one-shot constraint per src→dst layout move, {ms, bytes
    moved, peak bytes} each (see bench.measure_reshard)."""
    import bench
    payload = bench.measure_reshard()
    return {"metric": "reshard_sweep", **payload}


def bench_precision(mesh, cfg):
    """Precision-tier sweep: f32 vs bf16x1 vs bf16x3 vs int32 on the
    dense flagship multiply, TFLOPS + measured max-abs-error vs an f64
    oracle per tier (see bench.measure_precision)."""
    import bench
    payload = bench.measure_precision()
    return {"metric": "precision_tier_sweep", **payload}


def bench_chain(mesh, cfg):
    import jax.numpy as jnp
    import jax
    from matrel_tpu.workloads import chain_bench
    mats = chain_bench.skewed_abc(mesh, n=10_000, mid=100, dtype="bfloat16")
    plan, paren, est = chain_bench.compile_chain(mats)
    a_leaf = plan.leaf_order[0]
    fetch = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

    def chained(reps):
        # result shape == A's shape: rebind so every rep depends on the last
        cur = plan.run()
        for _ in range(reps - 1):
            cur = plan.run(bindings={a_leaf.uid: cur})
        np.asarray(fetch(cur.data))

    chained(2)
    # latency-bound op on a shared chip: median of 3 marginal estimates
    # (the single-estimate round-1 methodology showed a 0.5-2.3 ms
    # run-to-run band; same treatment as bench_spmm)
    lo, hi = 3, 43
    ests = []
    for _ in range(3):
        t0 = time.perf_counter(); chained(lo); t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); chained(hi); t_hi = time.perf_counter() - t0
        ests.append(max((t_hi - t_lo) / (hi - lo), 1e-9))
    dt = sorted(ests)[1]
    # optimal order A·(B·C): 2*(100*10000*100) + 2*(10000*100*100) FLOPs
    fl = 2 * (100 * 10_000 * 100) + 2 * (10_000 * 100 * 100)
    return {"metric": "chain_abc_10k_skewed_wallclock", "value": round(dt * 1e3, 3),
            "unit": "ms", "plan": paren,
            "effective_tflops": round(fl / dt / 1e12, 3)}


def bench_linreg(mesh, cfg):
    import jax
    import jax.numpy as jnp
    from matrel_tpu.workloads.linreg import fit_streaming
    n, k, panel = 10_000_000, 1000, 250_000

    def panel_fn(p):
        # cheap deterministic on-device generator (integer-hash mixing):
        # the benchmark measures the Gram pipeline, not RNG throughput.
        # NOTE a sin(r*a + c*b) generator would be RANK 2 (sum formula)
        # and make the normal equations singular — the hash keeps X
        # full-rank and well-conditioned.
        r = jnp.arange(panel, dtype=jnp.int32)[:, None]
        c = jnp.arange(k, dtype=jnp.int32)[None, :]
        s = r * 1664525 + c * 1013904223 + p * 69069 + 12345
        s = s * 1664525 + 1013904223          # one more LCG round to mix
        xp = (s >> 8).astype(jnp.float32) * (2.0 ** -23)
        yp = xp @ jnp.ones((k, 1), jnp.float32)
        return xp, yp

    def run():
        theta = fit_streaming(n, k, panel_fn, panel_rows=panel, mesh=mesh,
                              precision="high")
        np.asarray(theta)

    dt = _timed(run, warm=1, reps=2)
    fl = 2.0 * n * k * k + 2.0 * n * k  # gram + rhs
    return {"metric": "linreg_normal_eq_10Mx1k_wallclock", "value": round(dt, 3),
            "unit": "s", "effective_tflops": round(fl / dt / 1e12, 2),
            "precision": "high (3-pass bf16 Gram)"}


def bench_spmm(mesh, cfg):
    import jax
    import jax.numpy as jnp
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.ops import spmm as spmm_lib
    n = 100_352  # 196 blocks of 512
    bs = 512
    # bf16 payloads, f32 accumulation — same dtype policy as the dense
    # row-1 bench (f32 payloads: ~6.1 ms / 16.9 eff TFLOPS)
    S = BlockSparseMatrix.random((n, n), block_density=0.01, block_size=bs,
                                 mesh=mesh, seed=0, dtype="bfloat16")
    D = BlockMatrix.random((n, 512), mesh=mesh, seed=1, dtype="bfloat16")
    fetch = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

    def chained(reps):
        cur = D  # C has D's shape (square S): feed the output back in
        for _ in range(reps):
            cur = spmm_lib.spmm(S, cur, cfg)
        np.asarray(fetch(cur.data))

    chained(2)
    # sub-ms op on a shared chip: median of several marginal estimates
    # over long chains, or dispatch jitter swamps the signal
    lo, hi = 5, 45
    ests = []
    for _ in range(3):
        t0 = time.perf_counter(); chained(lo); t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); chained(hi); t_hi = time.perf_counter() - t0
        ests.append(max((t_hi - t_lo) / (hi - lo), 1e-9))
    dt = sorted(ests)[1]
    fl = 2.0 * S.nnzb * bs * bs * 512
    return {"metric": "blocksparse_spmm_100k_1pct_wallclock",
            "value": round(dt * 1e3, 2), "unit": "ms", "nnzb": S.nnzb,
            "effective_tflops": round(fl / dt / 1e12, 3)}


def bench_pagerank(mesh, cfg):
    """Compact-table Pallas SpMV path (ops/pallas_spmv.py): plan built
    once per graph (host fill only — no table expansion; device tables
    are the 13 B/slot compact layout), 30 rounds in one fori_loop at
    f32 fidelity (passes=3; the expanded-table path at the same
    fidelity measured 32.4 ms/round)."""
    n, n_edges, rounds = 1_000_000, 10_000_000, 30
    from matrel_tpu.workloads.pagerank import (
        prepare_pagerank_onehot, run_pagerank_compact)
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, n_edges, dtype=np.int32)
    dst = rng.integers(0, n, n_edges, dtype=np.int32)
    prepared = prepare_pagerank_onehot(src, dst, n)

    def run(r=rounds):
        out = run_pagerank_compact(prepared, rounds=r, passes=3)
        np.asarray(out[:1])

    run(1)          # table upload + compile of the small program
    run(rounds)     # warm the 30-round program
    dt = _timed(run, warm=0, reps=2)
    return {"metric": "pagerank_1M_30rounds_wallclock_per_round",
            "value": round(dt / rounds * 1e3, 2), "unit": "ms/round",
            "total_s": round(dt, 3), "impl": "compact-pallas-spmv"}


def bench_pagerank_10x(mesh, cfg):
    """10×-scale PageRank: 10M nodes / 100M edges, single chip. The
    compact 13 B/slot tables are what make this FIT at all — the
    expanded tables (~23.5 GB) exceed the chip's 16 GB HBM entirely —
    so this row tracks the HBM-capacity win as a re-runnable benchmark
    (round-2 VERDICT: it was prose in BASELINE.md row-5 notes). Fewer
    rounds than row 5: the per-round cost is what's tracked."""
    n, n_edges, rounds = 10_000_000, 100_000_000, 5
    from matrel_tpu.workloads.pagerank import (
        prepare_pagerank_onehot, run_pagerank_compact)
    rng = np.random.default_rng(0)
    src = rng.integers(0, n, n_edges, dtype=np.int32)
    dst = rng.integers(0, n, n_edges, dtype=np.int32)
    prepared = prepare_pagerank_onehot(src, dst, n)

    def run(r=rounds):
        out = run_pagerank_compact(prepared, rounds=r, passes=3)
        np.asarray(out[:1])

    run(1)
    run(rounds)
    dt = _timed(run, warm=0, reps=2)
    return {"metric": "pagerank_10M_100Medges_wallclock_per_round",
            "value": round(dt / rounds * 1e3, 1), "unit": "ms/round",
            "rounds_timed": rounds, "impl": "compact-pallas-spmv",
            "note": "expanded tables (~23.5 GB) cannot fit 16 GB HBM"}


def bench_cg(mesh, cfg):
    """Conjugate gradient on an implicit SPD 8k system: two MXU matmuls
    per iteration inside one jitted while_loop (tracked extra row —
    round-3 workload family, first on-chip number wanted round 4)."""
    import jax.numpy as jnp

    from matrel_tpu.workloads.cg import cg_runner
    n = 8192
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32)
                    / np.sqrt(n))
    b = jnp.asarray(rng.standard_normal(n).astype(np.float32))

    def matvec(p):
        # A = M·Mᵀ/1 + I — SPD, well-conditioned, never materialised
        return m @ (m.T @ p) + p

    run = cg_runner(matvec, tol=1e-5, maxiter=100)

    def go():
        x, it = run(b)
        float(x[0])            # forced fetch (dispatch is async)
        return int(it)

    iters = go()               # compile + warm
    dt = _timed(go, warm=0)
    fl = 4.0 * n * n * iters   # 2 matmuls x 2nk flops per iteration
    return {"metric": "cg_8k_spd_wallclock", "value": round(dt, 3),
            "unit": "s", "iters": iters,
            "effective_tflops": round(fl / dt / 1e12, 2)}


def bench_eigen(mesh, cfg):
    """Power iteration, 50 rounds on a dense 8k matrix in one jitted
    fori_loop (tracked extra row — round-3 workload family)."""
    import jax.numpy as jnp

    from matrel_tpu.workloads.eigen import power_runner
    n, rounds = 8192, 50
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32)
                    / np.sqrt(n))
    run = power_runner(rounds, 0)

    def go():
        lam, v = run(a)
        return float(lam)

    lam = go()                 # compile + warm
    dt = _timed(go, warm=0)
    fl = 2.0 * n * n * (rounds + 1)   # rounds matvecs + the final A.v
    return {"metric": "power_iteration_8k_50rounds_wallclock",
            "value": round(dt, 3), "unit": "s",
            "dominant_eig": round(lam, 4),
            "effective_tflops": round(fl / dt / 1e12, 2)}


def bench_triangles(mesh, cfg):
    """Triangle counting on a dense 8k 0/1 adjacency through the FULL
    query stack: trace(A·A·A) — chain DP ties, R3 pushes the diagonal
    aggregate into the final multiply, so the compiled plan does one
    full matmul plus a diagonal-only contraction (tracked extra row)."""
    import jax
    import jax.numpy as jnp

    from matrel_tpu import executor as executor_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.workloads.triangles import triangle_count_expr
    n = 8192
    rng = np.random.default_rng(2)
    a = (rng.random((n, n)) < 0.01).astype(np.float32)
    a = np.triu(a, 1)
    a = a + a.T
    A = BlockMatrix.from_numpy(a, mesh=mesh)
    plan = executor_lib.compile_expr(triangle_count_expr(A), mesh, cfg)
    fetch = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))

    def go():
        out = plan.run()
        return float(np.asarray(fetch(out.data)))

    tri6 = go()                # compile + warm
    dt = _timed(go, warm=0)
    fl = 2.0 * n * n * n + 2.0 * n * n   # post-R3: one matmul + diag
    return {"metric": "triangles_8k_dense_wallclock",
            "value": round(dt, 3), "unit": "s",
            "triangles": int(round(tri6 / 6.0)),
            "effective_tflops": round(fl / dt / 1e12, 2)}


def bench_north_star(mesh, cfg):
    from matrel_tpu.workloads.big_chain import (
        streaming_chain_slab, cheap_gen, north_star_flops)
    n, tile, panel = 65_536, 8192, 16_384
    gens = tuple(cheap_gen(s, tile) for s in (1, 2, 3))
    def run():
        float(streaming_chain_slab(n, *gens, tile=tile, panel=panel))
    dt = _timed(run, warm=1, reps=2)
    return {"metric": "north_star_65k_chain_wallclock", "value": round(dt, 2),
            "unit": "s", "tflops_per_chip": round(north_star_flops(n) / dt / 1e12, 1),
            "note": "slab-scheduled, streamed on ONE v5e chip "
                    "(spec target: v5e-64)"}


def main():
    # probe FIRST, in a child under a hard timeout (bench.py's probe:
    # it refuses a non-TPU device outside the dry drill and returns the
    # device stamp every row line carries). The child has exited before
    # this process touches jax, so the chip is free for it.
    import bench
    ok, payload = bench._run_child("probe", bench.PROBE_TIMEOUT_S)
    if not ok:
        print(json.dumps({"metric": "bench_all",
                          "error": str(payload)[-800:]}), flush=True)
        sys.exit(2)
    stamp = {k: payload.get(k) for k in bench.STAMP_KEYS}
    from matrel_tpu.config import configure_compile_cache
    configure_compile_cache()
    from matrel_tpu.config import MatrelConfig, set_default_config
    from matrel_tpu.core import mesh as mesh_lib
    cfg = MatrelConfig()
    set_default_config(cfg)
    mesh = mesh_lib.make_mesh()
    # MATREL_DRY (tools/tpu_batch.sh --dry): run the rows whose fixed
    # configs are CPU-feasible, emit an explicit parseable skip record
    # for each row whose hard-coded full scale is not (10M-row linreg,
    # 100k SpMM, the 65k north star, …) — the fire-drill proves the
    # step order, the JSON contract and the harness glue, not the
    # numbers.
    dry = bool(os.environ.get("MATREL_DRY"))
    failed = []
    dry_rows = (bench_dense_4k, bench_chain, bench_spgemm,
                bench_sparse_kernels, bench_fusion, bench_serve,
                bench_cse, bench_fleet, bench_stream, bench_precision,
                bench_reshard, bench_traffic)
    for fn in (bench_dense_4k, bench_chain, bench_linreg, bench_spmm,
               bench_spgemm, bench_sparse_kernels, bench_fusion,
               bench_serve, bench_cse, bench_fleet, bench_stream,
               bench_precision, bench_reshard, bench_traffic,
               bench_pagerank, bench_pagerank_10x, bench_cg,
               bench_eigen, bench_triangles, bench_north_star):
        if dry and fn not in dry_rows:
            print(json.dumps({"metric": fn.__name__, "skipped": "dry",
                              **stamp}), flush=True)
            continue
        try:
            print(json.dumps({**fn(mesh, cfg), **stamp}), flush=True)
        except Exception as e:  # keep the suite running; fail at the end
            failed.append(fn.__name__)
            print(json.dumps({"metric": fn.__name__, "error": repr(e),
                              **stamp}), flush=True)
    if failed:
        print(f"# bench_all: {len(failed)} row(s) failed: {failed}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
