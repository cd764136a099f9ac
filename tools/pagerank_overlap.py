"""Gather/scatter pipelining experiment for the PageRank SpMV
(VERDICT r3 #6): can chunking the block axis — so chunk i+1's gather can
interleave with chunk i's MXU scatter — close any of the ~6 ms/round gap
between the measured 27.1 ms round and the ~21 ms gather-engine floor
(BASELINE.md row 5)?

STOP RULE (encoded): if the best chunked variant improves the baseline
matvec by <10%, print the negative result; BASELINE.md row 5 then
records that the schedule family is exhausted and the gather engine
floor stands.

Run on chip: ``python tools/pagerank_overlap.py``.
"""
import json
import sys
import time

import jax.numpy as jnp
import numpy as np


def measure(apply_fn, x0, reps=(2, 8)):
    """Marginal seconds per matvec: chained y->x dependencies + scalar
    fetch (bench.py methodology — dispatch is asynchronous)."""
    import jax
    f = jax.jit(apply_fn)
    fetch = jax.jit(lambda v: jnp.sum(v))

    def chained(k):
        cur = x0
        for _ in range(k):
            cur = f(cur)
        float(fetch(cur))

    chained(2)
    ts = []
    for _ in range(3):
        lo, hi = reps
        t0 = time.perf_counter()
        chained(lo)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        chained(hi)
        t_hi = time.perf_counter() - t0
        ts.append((t_hi - t_lo) / (hi - lo))
    ts.sort()
    return ts[1]


def main(n=1_000_000, n_edges=10_000_000):
    from matrel_tpu.ops import pallas_spmv as pc
    from matrel_tpu.ops import spmv as spmv_lib

    rng = np.random.default_rng(0)
    src = rng.integers(0, n, n_edges, dtype=np.int32)
    dst = rng.integers(0, n, n_edges, dtype=np.int32)
    plan = spmv_lib.build_spmv_plan(dst, src, None, n_rows=n, n_cols=n)
    if plan is None:
        print(json.dumps({"error": "planner refused graph"}))
        return
    static = (plan.n_rows, plan.n_cols, plan.block, spmv_lib.LO)
    tables = pc.compact_tables(plan)
    ov = plan.overflow
    x0 = jnp.ones((n,), jnp.float32) / n

    base = measure(lambda v: pc.compact_apply(static, tables, ov, v))
    res = {"baseline_ms": round(base * 1e3, 3), "chunked_ms": {}}
    best = None
    for k in (2, 4, 8):
        t = measure(lambda v, k=k: pc.compact_apply_chunked(
            static, tables, ov, v, chunks=k))
        res["chunked_ms"][k] = round(t * 1e3, 3)
        if best is None or t < best[1]:
            best = (k, t)
    gain = 1.0 - best[1] / base
    res["best_chunks"] = best[0]
    res["gain_pct"] = round(gain * 100, 1)
    res["verdict"] = ("IMPROVED — adopt chunked schedule" if gain >= 0.10
                      else "NEGATIVE — <10% gain; gather-engine floor "
                           "stands, schedule family exhausted")
    print(json.dumps({"metric": "pagerank_overlap_experiment", **res}))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
