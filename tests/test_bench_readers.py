"""The benchmark's span readers (``benchmarks/metrics/``, PR 25) read
the program's own ring — ``obs.trace.profile_spans()`` — so a change
to the ring's records or the spans' names breaks them: tier-1 holds
each reader to a real ring, recorded under the CPU profiler. Their
arithmetic is checked on a synthetic ring by hand in
``benchmarks/tests/test_program_spans.py``."""

import os
import types

import jax
import numpy as np
import pytest

from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.session import MatrelSession
from matrel_tpu.workloads import pagerank as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = 3


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """What a reader is handed after a traced window of three SQL
    queries and three PageRank calls, warm, and one more of each after
    the count the trace would give."""
    from benchmarks import program_spans, run as harness
    from matrel_tpu.obs.trace import profile_spans
    sess = MatrelSession()
    rng = np.random.default_rng(7)
    sess.register("A", BlockMatrix.from_numpy(
        rng.standard_normal((32, 32)).astype(np.float32), mesh=sess.mesh))
    src = rng.integers(0, 200, 1500).astype(np.int32)
    dst = rng.integers(0, 200, 1500).astype(np.int32)

    def query():
        sess.compute(sess.sql("rowsum(A * A)")).to_numpy()
        pr.pagerank_edges(src, dst, 200, rounds=2, impl="onehot")

    query()                                             # warm
    before = len(profile_spans())   # the ring is the process's: other
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("prof")),
                             profiler_options=opts)
    try:
        for _ in range(QUERIES + 1):
            query()
    finally:
        jax.profiler.stop_trace()
    mine = sorted(profile_spans()[before:],     # tests traced before
                  key=lambda r: r["start_ns"])
    said = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(program_spans, "ring", lambda: mine)
        # two roots a query here (a compute and a pagerank), so the
        # count the trace gives is over both
        # and the window ends where the call after it starts
        roots = [r for r in mine if r["name"] in program_spans.QUERY_ROOTS]
        window_ns = roots[2 * QUERIES]["start_ns"] - mine[0]["start_ns"]
        yield types.SimpleNamespace(
            reduced={"queries": [{}] * (2 * QUERIES),
                     "window_s": window_ns * 1e-9}, say=said.append,
            said=said, load_module=harness.load_module)


@pytest.mark.parametrize("name, low, high", [
    ("plan_lookup_ms", 0.0, 50.0), ("dispatch_ms", 0.0, 500.0),
    ("compute_self_ms", 0.0, 50.0), ("fetch_ms", 0.0, 500.0),
    ("fingerprint_ms", 0.0, 50.0), ("compiles_in_window", 0, 0)])
def test_reader_reads_the_programs_ring(traced_run, name, low, high):
    reader = traced_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    value = reader.read(traced_run)
    assert value is not None, traced_run.said
    assert low <= value <= high
    if name != "compiles_in_window":
        assert value > 0
    # without a reduced trace to count the queries by: nothing, no raise
    assert reader.read(types.SimpleNamespace(
        reduced=None, say=lambda line: None)) is None
