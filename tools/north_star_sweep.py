"""Bounded north-star residual sweep (round-1 VERDICT #10) — one
command on a live chip: `python tools/north_star_sweep.py`.

Round-1 context (BASELINE.md row 6, docs/INTERNALS.md): the slab
schedule reaches 178.8 TFLOPS of a measured ~189 pure-matmul ceiling;
tile/panel sweeps all tied at ~6.34 s, locating the residual in
generator cost + slab glue. This sweep re-times the baseline plus the
most promising remaining variants, marginal-time methodology, and
appends the outcome to PROGRESS.jsonl. Per the VERDICT's stop rule: if
the top two schedules tie (<1% apart), the written negative result
stands and the sweep should not be re-run.

Probes the backend first via bench.py's harness.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _append_progress(event: dict) -> None:
    # $MATREL_PROGRESS_PATH: see tools/soak_guard.py (dry-batch redirect)
    path = os.environ.get("MATREL_PROGRESS_PATH",
                          os.path.join(REPO, "PROGRESS.jsonl"))
    try:
        with open(path, "a") as f:
            f.write(json.dumps({"ts": time.time(),
                                "event": "north_star_sweep", **event})
                    + "\n")
    except OSError:
        pass


def measure(fn, reps: int = 2) -> float:
    """Median wall-clock; fn blocks internally (scalar fetch)."""
    fn()                      # warm/compile
    ts = []
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main() -> int:
    import bench
    ok, payload = bench._run_child("probe", bench.PROBE_TIMEOUT_S)
    if not ok:
        print(json.dumps({"error": str(payload)}))
        _append_progress({"ok": False, "detail": str(payload)[:300]})
        return 2

    from matrel_tpu.workloads.big_chain import (
        cheap_gen, north_star_flops, streaming_chain_slab)

    # $MATREL_NS_N scales the sweep down for the dry-batch fire-drill
    # (tools/tpu_batch.sh --dry): same code path, same artifact shape,
    # toy dims on the CPU backend
    n = int(os.environ.get("MATREL_NS_N", 65_536))
    flops = north_star_flops(n)
    results = []
    # variants: the round-1 winner, its neighbours one step out in each
    # direction, and f32 reduce (isolates the reduce-glue term)
    variants = [
        ("tile8192_panel16384", dict(tile=8192, panel=16384)),
        ("tile8192_panel32768", dict(tile=8192, panel=32768)),
        ("tile16384_panel16384", dict(tile=16384, panel=16384)),
        ("tile4096_panel16384", dict(tile=4096, panel=16384)),
    ]
    if n < 65_536:
        t = max(n // 4, 128)
        variants = [(f"dry_tile{t}_panel{t}", dict(tile=t, panel=t))]
    for name, kw in variants:
        gens = tuple(cheap_gen(s, kw["tile"]) for s in (1, 2, 3))

        def run(kw=kw, gens=gens):
            float(streaming_chain_slab(n, *gens, **kw))

        try:
            dt = measure(run)
            tf = flops / dt / 1e12
            results.append({"variant": name, "s": round(dt, 3),
                            "tflops": round(tf, 1)})
        except Exception as e:  # keep sweeping
            results.append({"variant": name, "error": repr(e)[:200]})
        print(json.dumps(results[-1]), flush=True)

    timed = sorted((r for r in results if "tflops" in r),
                   key=lambda r: -r["tflops"])
    verdict = {"ok": bool(timed), "results": results}
    if len(timed) >= 2:
        tie = timed[0]["tflops"] - timed[1]["tflops"] < 0.01 * timed[0]["tflops"]
        verdict["top_tie"] = tie
        verdict["conclusion"] = (
            "schedules tie — negative result stands (stop rule)"
            if tie and timed[0]["tflops"] < 182 else
            f"best {timed[0]['variant']} at {timed[0]['tflops']} TFLOPS")
    print(json.dumps(verdict))
    _append_progress(verdict)
    return 0


if __name__ == "__main__":
    sys.exit(main())
