"""Deployment ``ldbc_graphalytics_g500_22``: LDBC Graphalytics' PageRank
on the Graph500 Kronecker graph of scale 22, through
``workloads.pagerank.pagerank_edges`` on one chip.

The graph is made here from ``graph_seed`` by the Graph500 specification's
generator (R-MAT initiator, vertex labels permuted), cleaned as LDBC
cleans its data set (undirected, no self-loop, no duplicate, no isolated
vertex) and handed to the program as both directions of every edge, in an
order drawn from ``--seed``. The plain reference is a scipy float64 power
iteration of Graphalytics' equation; its bfloat16 control and the
program's own ``passes`` are the lower-precision readings the limits are
set between. The answer is also held to the executor that gave it: the
compact-table Pallas matvec over a plan with no overflow edge."""

from __future__ import annotations

import contextlib
import logging
import math
import time

import numpy as np

from benchmarks.reference import bf16, rel_err, seed_words

QUERY = "pagerank_g500"


def _no_span(name):
    return contextlib.nullcontext()


def kronecker_graph(scale: int, edge_factor: int, initiator, graph_seed: int):
    """(lo, hi, vertices): the undirected edges lo < hi of the Graph500
    Kronecker graph as LDBC Graphalytics keeps it, vertices renumbered
    0..V-1 in label order, edges sorted by (lo, hi).

    Graph500's generator: each of ``edge_factor << scale`` edges picks,
    bit by bit, a quadrant of the adjacency matrix with the initiator's
    probabilities (A, B, C; D the rest), then the vertex labels are
    permuted. The 67M edges of scale 22 are drawn on the device (44
    uniforms an edge); sorting them there too cost 50 s of compile in a
    cold set-up (a 67M-element sort, my chip run, PR 33), so the
    clean-up is the host's: one sort of 64-bit keys."""
    import jax
    import jax.numpy as jnp

    a, b, c = initiator
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    m, n = edge_factor << scale, 1 << scale

    @jax.jit
    def draw(key):
        def level(bit, ij):
            k = jax.random.fold_in(key, bit)
            u = jax.random.uniform(jax.random.fold_in(k, 0), (m,))
            v = jax.random.uniform(jax.random.fold_in(k, 1), (m,))
            ii = u > ab
            jj = v > jnp.where(ii, c_norm, a_norm)
            return (ij[0] | (ii.astype(jnp.int32) << bit),
                    ij[1] | (jj.astype(jnp.int32) << bit))

        zero = jnp.zeros((m,), jnp.int32)
        i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
        label = jax.random.permutation(jax.random.fold_in(key, scale),
                                       n).astype(jnp.int32)
        return label[i], label[j]

    i, j = (np.asarray(x) for x in draw(jax.random.PRNGKey(graph_seed)))
    i, j = np.minimum(i, j), np.maximum(i, j)
    keys = np.unique((i.astype(np.int64) << scale | j)[i != j])
    lo, hi = keys >> scale, keys & (n - 1)
    present = np.zeros(n, bool)
    present[lo] = present[hi] = True
    number = (np.cumsum(present) - 1).astype(np.int32)
    return number[lo], number[hi], int(present.sum())


def directed_in_seed_order(lo, hi, seed: int):
    """Both directions of every undirected edge as int32 ``src``, ``dst``
    on the host, in an order drawn from ``seed``: the pairs shuffled as
    one 64-bit item an edge, in place (a permutation and two gathers of
    128M took twice as long)."""
    m = lo.size
    both = np.empty((2 * m, 2), np.int32)
    both[:m, 0], both[:m, 1] = lo, hi
    both[m:, 0], both[m:, 1] = hi, lo
    np.random.default_rng(seed_words(seed) + (5,)).shuffle(
        both.view(np.int64).reshape(-1))
    return np.ascontiguousarray(both[:, 0]), np.ascontiguousarray(both[:, 1])


class _FallbackIsFatal(logging.Handler):
    """The program says so before it falls to the segment-sum path (a
    warning of ``matrel_tpu.pagerank``, ahead of the slow call): in the
    deployment's first call that warning ends the run there, instead of
    after two minutes of sorting and scattering 128M edges (the parent
    commit, my chip run, PR 33)."""

    def emit(self, record):
        raise RuntimeError(
            "ldbc_graphalytics_g500_22: the program cannot serve this "
            "deployment through the compact-table executor: "
            + record.getMessage())


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        from matrel_tpu.config import MatrelConfig, set_default_config

        self.spec = spec
        self.seed = seed
        self.interpret = interpret
        if interpret:
            # pagerank_edges asks the default config whether Pallas runs
            set_default_config(MatrelConfig(pallas_interpret=True))
        g = spec["graph"]
        # a rehearsal runs a smaller scale of the same generator: the
        # vertices' share, to the nearest power of two
        self.kron_scale = g["scale"] if scale >= 1.0 else max(
            10, g["scale"] + round(math.log2(scale)))
        t = time.perf_counter()
        lo, hi, self.nodes = kronecker_graph(
            self.kron_scale, g["edge_factor"], g["initiator"],
            g["graph_seed"])
        self.undirected = int(lo.size)
        t_gen = time.perf_counter()
        self.src, self.dst = directed_in_seed_order(lo, hi, seed)
        self.parts = {"generate_s": round(t_gen - t, 3),
                      "order_s": round(time.perf_counter() - t_gen, 3)}
        pr = spec["pagerank"]
        self.rounds, self.alpha = pr["rounds"], pr["alpha"]
        self.plan = {}          # what the program said of its plan, newest
        self.not_compact = 0    # calls another executor answered
        self.overflow_edges = 0     # the most any call's plan left to the
        self.calls = 0              # scalar overflow path
        self.plan_builds = 0    # calls that built their plan (hit false)

    # -- the timed path ------------------------------------------------------

    def run(self, query, span, **knobs):
        """``knobs`` are empty in a run; ``program_controls`` passes the
        program's own lower-precision settings."""
        if query != QUERY:
            raise KeyError(query)
        from matrel_tpu.workloads import pagerank as pr_lib
        before = pr_lib.path_counts()["compact"]
        fatal = _FallbackIsFatal(logging.WARNING)
        if self.calls == 0:
            pr_lib.log.addHandler(fatal)
        try:
            # off the TPU "auto" is the segment-sum path by design, so a
            # rehearsal names the executor (as chip_smoke.py does)
            with span("compute"):
                r = pr_lib.pagerank_edges(
                    self.src, self.dst, self.nodes, rounds=self.rounds,
                    alpha=self.alpha,
                    impl="onehot" if self.interpret else "auto", **knobs)
            with span("wait"):
                r.block_until_ready()
        finally:
            pr_lib.log.removeHandler(fatal)
        compact = pr_lib.path_counts()["compact"] == before + 1
        # a program without last_plan (a parent commit) says nothing more
        self.plan = getattr(pr_lib, "last_plan", dict)()
        self.calls += 1
        # the build's own parts (host fill, upload) are said once
        self.parts.update({k: self.plan.pop(k) for k in
                           ("build_s", "upload_s") if k in self.plan})
        if self.calls == 1 and not compact:
            # a tree that cannot lay this graph out answers through the
            # segment-sum path at tens of seconds a query: stop in set-up
            raise RuntimeError(
                "ldbc_graphalytics_g500_22: the first call was answered by "
                f"{self.plan.get('impl', 'another executor')}, not by the "
                "compact-table Pallas executor (path_counts: "
                f"{pr_lib.path_counts()}); this program cannot serve the "
                "deployment")
        self.not_compact += not compact
        self.plan_builds += self.plan.get("hit") is False
        self.overflow_edges = max(self.overflow_edges,
                                  int(self.plan.get("overflow_edges", 0)))
        return r

    def program_controls(self, query):
        """(knob, answer) for each lower-precision path the program has
        of its own: passes=3 is the f32-faithful default, 2 its
        "ranking-grade" setting, 1 a single bfloat16 part."""
        return [(f"passes={p}", np.asarray(self.run(query, _no_span,
                                                    passes=p)))
                for p in (2, 1)]

    def notes(self, query):
        """The graph as generated, the set-up's parts this file times,
        and what the program says of its prepared plan."""
        return {"kron_scale": self.kron_scale, "vertices": self.nodes,
                "undirected_edges": self.undirected, **self.parts,
                **self.plan}

    def shapes(self, query):
        """What the count functions take."""
        return {"nodes": self.nodes, "edges": int(self.src.size),
                "rounds": self.rounds}

    # -- the plain reference, after the window --------------------------------

    def reference(self, query, rnd=lambda x: x):
        """scipy float64 power iteration of Graphalytics' PageRank (an
        undirected edge counts in both directions; a vertex with no
        out-edge spreads its rank over all); ``rnd`` rounds what each
        round's matvec reads: the identity for the reference, bfloat16
        for the control."""
        import scipy.sparse as sp
        n, src, dst = self.nodes, self.src, self.dst
        outdeg = np.bincount(src, minlength=n).astype(np.float64)
        inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1e-30), 0.0)
        at = sp.csr_matrix((rnd(inv[src]), (dst, src)), shape=(n, n))
        dangling = outdeg == 0
        r = np.full(n, 1.0 / n)
        for _ in range(self.rounds):
            r = self.alpha * (at @ rnd(r) + r[dangling].sum() / n) \
                + (1 - self.alpha) / n
        return r

    def control(self, query):
        return self.reference(query, rnd=bf16)

    def compare(self, query, answer, want):
        """The ranks both as the repo measures them (max |got - want|
        over max |want|: a hub's rank hides a leaf's error) and as LDBC
        validates them (every vertex relative to its own rank); and the
        executor: calls another one answered and edges left to the scalar
        overflow path, each held to 0, and the plans built, held to the
        one a process needs."""
        q = self.spec["queries"][query]
        got = np.asarray(answer, np.float64)
        want = np.asarray(want, np.float64)
        vertex = float("inf")
        if got.shape == want.shape and np.all(np.isfinite(got)):
            vertex = float(np.max(np.abs(got - want) / np.abs(want)))
        return [(f"{query}.max_rel_err", rel_err(answer, want),
                 float(q["limit"])),
                (f"{query}.max_vertex_rel_err", vertex,
                 float(q["vertex_limit"])),
                (f"{query}.not_compact_calls", self.not_compact, 0),
                (f"{query}.overflow_edges", self.overflow_edges, 0),
                (f"{query}.plan_builds", self.plan_builds, 1)]
