#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: builds the cell's deployment from the seed, warms the cell's
own queries (set-up), drives one closed-loop client for ``--seconds``
(``--trace 1``: a short window under the profiler instead), checks the
kept answers against the configuration's plain reference, and prints one
JSON object as its last line. Everything that belongs to one cell is found
by name from ``BENCHMARK.json``:

    benchmarks/configs/<config>.json + .py    sizes, guarantees / deployment, reference
    benchmarks/workloads/<traffic>.json       the mix the general generator reads
    benchmarks/metrics/<metric>.py            one reader per per-layer metric
    benchmarks/counts/<kernel>.py             operations and bytes from shapes

A run that finds no TPU, or another number of chips than the cell asks
for, exits non-zero and prints no result. ``--rehearse <scale>`` is the
CPU rehearsal (rows cut, Pallas interpreted): same control flow, and its
last line carries no metric value.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NULL_SPAN = contextlib.nullcontext()

# every key a traffic file may hold is read by this harness; a file with
# another key (``clients``, a rate) asks for traffic it does not generate,
# and is refused rather than run as one closed-loop client
TRAFFIC_KEYS = {"mix", "warm_calls", "check_every", "check_max",
                "trace_seconds", "trace_max_queries", "rehearse_scale"}
MIX_KEYS = {"query", "weight"}


def no_span(name):
    """The span factory of an untraced run."""
    return NULL_SPAN


def say(msg: str) -> None:
    """An earlier line of the output (the last line is the result)."""
    print(msg, flush=True)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .rsplit(".", 1)[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def percentile(sorted_vals, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    k = (len(sorted_vals) - 1) * p
    lo = int(k)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (k - lo)


def drive(dep, seq, seconds, max_queries, every, offset, keep_max, traced):
    """The closed loop of one client: the next query goes out when the
    last answer is materialised. Returns latencies, the kept answers
    (index, query, answer), failures and the window's length."""
    span = no_span
    if traced:
        import jax

        def span(name):
            return jax.profiler.TraceAnnotation("bench." + name)
    lat, kept, failed = [], [], 0
    last = {}           # the newest answer of each query is always kept
    n_seq = len(seq)
    i = 0
    w0 = time.perf_counter()
    deadline = w0 + seconds
    while True:
        q = seq[i % n_seq]
        t = time.perf_counter()
        try:
            with span("query:" + q):
                ans = dep.run(q, span)
        except Exception:   # a failed query is counted, and the run goes on
            logging.exception("query %s failed", q)
            ans, failed = None, failed + 1
        t1 = time.perf_counter()
        lat.append(t1 - t)
        if ans is not None:
            last[q] = (i, q, ans)
            if i % every == offset and len(kept) < keep_max:
                kept.append(last[q])
        i += 1
        if t1 >= deadline or i >= max_queries:
            break
    kept.extend(item for item in last.values() if item not in kept)
    return lat, kept, failed, t1 - w0


def settle_heap():
    """Hand the C heap's free memory back to the system at the end of
    set-up, so that the window starts from the same heap whether set-up
    compiled (and left the allocator holding what the compiler freed) or
    read the compile cache."""
    import ctypes
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):    # not glibc: nothing to settle
        pass


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def device_op_checks(dep, spec, queries):
    """(label, ok, detail) for every query whose configuration names a
    device operation that has to run in it (``device_op``: the Pallas
    kernels' ``custom_call_target``). One more call of the query under the
    profiler, after the window, and a look at the names of the operations
    that ran on the device: nothing of the program is asked, so a silent
    fallback to plain XLA shows however the program is built. A CPU
    rehearsal has no device plane and interprets Pallas: nothing to read."""
    import jax
    from benchmarks import trace_reduce
    out = []
    for q in queries:
        needle = spec["queries"][q].get("device_op")
        if not needle:
            continue
        if jax.devices()[0].platform != "tpu":
            out.append((f"{q}.device_op", True, f"{needle}: not read "
                        "(rehearsal: no device plane)"))
            continue
        with tempfile.TemporaryDirectory(prefix="matrel_bench_op_") as d:
            jax.profiler.start_trace(d, profiler_options=profile_options())
            try:
                dep.run(q, no_span)
            finally:
                jax.profiler.stop_trace()
            n = trace_reduce.ops_named(
                trace_reduce.load(trace_reduce.find_xplane(d)), needle)
        out.append((f"{q}.device_op", n > 0, f"{needle} ran {n} times"))
    return out


def check(dep, kept, spec, queries):
    """Every kept answer against the plain reference (computed once a
    query); each number beside its limit on a line of its own. A device
    operation that the configuration names and that did not run counts
    like a number out of its limit."""
    ok = bool(kept)
    worst, wants = {}, {}
    for _, q, ans in kept:
        if q not in wants:
            wants[q] = dep.reference(q)
        for label, value, limit in dep.compare(q, ans, wants[q]):
            w = worst.get(label)
            if w is None or not value <= w[0]:
                worst[label] = (value, limit)
    for label, (value, limit) in sorted(worst.items()):
        good = value <= limit
        ok = ok and good
        say(f"check {label} value={value!r} limit={limit!r} "
            f"{'ok' if good else 'OUT OF LIMIT'}")
    for label, good, detail in device_op_checks(dep, spec, queries):
        ok = ok and good
        say(f"check {label} {detail} {'ok' if good else 'FAILED'}")
    say(f"check answers_compared={len(kept)}")
    return ok


def load_cell(workload: str):
    """(benchmark, cell, configuration entry, configuration file, traffic
    file) of one cell, all found by name from BENCHMARK.json."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic_spec = load_json(os.path.join(HERE, "workloads",
                                          cell["traffic"] + ".json"))
    unknown = sorted(set(traffic_spec) - TRAFFIC_KEYS) + sorted(
        {k for m in traffic_spec.get("mix", []) for k in m} - MIX_KEYS)
    if unknown:
        raise KeyError(f"traffic {cell['traffic']!r} has keys the generator "
                       f"does not read: {unknown}")
    return (bench, cell, config, load_json(os.path.join(ROOT, config["file"])),
            traffic_spec)


def build_deployment(config, spec, seed, queries, rehearse_scale=0.0):
    """The configuration's Deployment: its .py lies beside its .json."""
    mod = load_module(os.path.join(
        ROOT, config["file"].rsplit(".", 1)[0] + ".py"))
    return mod.Deployment(spec, seed, queries, scale=rehearse_scale or 1.0,
                          interpret=rehearse_scale > 0)


def warm_up(dep, queries, calls):
    """First call and warm median of each of the cell's queries, and of
    nothing else."""
    out = {}
    for q in queries:
        times = []
        for _ in range(1 + calls):
            t = time.perf_counter()
            dep.run(q, no_span)
            times.append(time.perf_counter() - t)
        out[q] = (times[0], statistics.median(times[1:]))
        say(f"warm {q} first_call_s={out[q][0]:.4f} "
            f"warm_median_s={out[q][1]:.6f} "
            f"plan={json.dumps(dep.notes(q), default=str)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=0.0, metavar="SCALE",
                    help="CPU rehearsal at this share of the rows; never "
                    "prints a metric value")
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="write the profiler's trace here and keep it")
    args = ap.parse_args(argv)
    rehearse = args.rehearse > 0

    try:
        bench, cell, config, spec, traffic_spec = load_cell(args.workload)
    except KeyError as ex:
        print(f"run.py: {ex.args[0]}", file=sys.stderr)
        return 2

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    stages = [("python", time.perf_counter() - T_START)]

    def stage(name):
        stages.append((name, time.perf_counter() - T_START))

    import jax
    stage("import_jax")
    dev = jax.devices()[0]
    stage("devices")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not rehearse and (dev.platform != "tpu"
                         or device["count"] != cell["chips"]):
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {device}. No result.", file=sys.stderr)
        return 2

    # every program, however quick to compile, goes to the persistent
    # cache: after a cell's first run in a checkout nothing compiles
    from matrel_tpu.config import configure_compile_cache
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)

    from benchmarks import traffic
    mix = traffic_spec["mix"]
    queries = sorted({m["query"] for m in mix})
    dep = build_deployment(config, spec, args.seed, queries, args.rehearse)
    stage("deployment")
    say(f"setup device={json.dumps(device)} cell={cell['name']} "
        f"seed={args.seed} compile_cache={cache_dir} rehearsal={rehearse}")

    first_calls = warm_up(dep, queries,
                          int(traffic_spec.get("warm_calls", 3)))
    seq = traffic.sequence(mix, args.seed)
    every = int(traffic_spec.get("check_every", 1))
    offset = traffic.kept_offset(args.seed, every)
    keep_max = int(traffic_spec.get("check_max", 64))
    traced = bool(args.trace)
    seconds = args.seconds
    max_queries = sys.maxsize
    trace_dir = None
    if traced:
        seconds = min(seconds, float(traffic_spec.get("trace_seconds", 3)))
        max_queries = int(traffic_spec.get("trace_max_queries", 200))
        tmp = None
        if args.keep_trace:
            trace_dir = os.path.abspath(args.keep_trace)
            os.makedirs(trace_dir, exist_ok=True)
        else:
            tmp = tempfile.TemporaryDirectory(prefix="matrel_bench_trace_")
            trace_dir = tmp.name
        opts = profile_options()

    stage("warm_up")
    settle_heap()
    setup_s = time.perf_counter() - T_START
    say("setup stages (seconds since the process started) "
        + " ".join(f"{name}={t:.2f}" for name, t in stages)
        + f" window_starts={setup_s:.2f}")
    if traced:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        lat, kept, failed, window_s = drive(
            dep, seq, seconds, max_queries, every, offset, keep_max, traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    device["memory_peak_bytes"] = int(peak)
    say(f"memory peak_bytes_in_use={peak} "
        f"bytes_limit={stats.get('bytes_limit')}")

    t_check = time.perf_counter()
    correct = check(dep, kept, spec, queries) and failed == 0
    say(f"check seconds={time.perf_counter() - t_check:.2f} (outside set-up "
        "and the window)")

    lat_ms = sorted(x * 1e3 for x in lat)
    n = len(lat_ms)
    p95 = percentile(lat_ms, 0.95)
    beyond = sum(1 for x in lat_ms if x > p95)
    slowest = sorted(range(n), key=lambda i: -lat[i])[:5]
    say("slowest " + " ".join(f"#{i}:{lat[i] * 1e3:.3f}ms" for i in slowest))
    for q in queries:       # the mix's median is one number of several modes
        mine = [lat[i] * 1e3 for i in range(n) if seq[i % len(seq)] == q]
        say(f"by_query {q} n={len(mine)} p50_ms={statistics.median(mine):.4f}")
    say(f"window seconds={window_s:.4f} queries={n} failed={failed} "
        f"beyond_p95={beyond}"
        + ("" if beyond >= 10 else " (fewer than ten samples beyond the "
           "95th percentile: read it as a high quantile of few)"))

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    values = {}
    if not traced:
        values = {"query_p50_ms": statistics.median(lat_ms),
                  "query_p95_ms": p95,
                  "queries_per_s": n / window_s,
                  "setup_s": setup_s}
        values = {m["name"]: values[m["name"]] for m in bench["end_to_end"]
                  if cell["name"] in m.get("workloads", [cell["name"]])}
    else:
        from benchmarks import trace_reduce
        reduced = None
        try:
            reduced = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))
        except (FileNotFoundError, ValueError) as ex:
            say(f"trace: nothing to reduce ({ex})")
        if tmp is not None:
            tmp.cleanup()
        peaks = load_json(os.path.join(HERE, "peaks.json"))
        if not rehearse and device["kind"] not in peaks:
            print(f"run.py: no peaks for device kind {device['kind']!r}",
                  file=sys.stderr)
            return 2
        run = types.SimpleNamespace(
            reduced=reduced, first_calls=first_calls,
            shapes={q: dep.shapes(q) for q in queries},
            peaks=peaks.get(device["kind"]), here=HERE, say=say,
            load_module=load_module)
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            reader = load_module(os.path.join(
                HERE, "metrics", m["name"] + ".py"))
            v = reader.read(run)
            if v is not None:
                values[m["name"]] = v
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            say(f"trace queries={len(reduced['queries'])} "
                f"device_ops={reduced['n_device_ops']} "
                f"chips_traced={reduced['chips_traced']}")

    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if rehearse:
        # a CPU number never stands under a device metric's name
        result["rehearsal"] = True
        result["metric_names"] = sorted(values)
    else:
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items()}
    result["device"] = device
    if traced and not rehearse and reduced is not None:
        if not reduced["n_device_ops"]:
            print("run.py: the trace holds no device operation",
                  file=sys.stderr)
            return 2
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
