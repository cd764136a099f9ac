"""Deployment ``ldbc_graphalytics_wcc_g500_22``: LDBC Graphalytics' WCC
(weakly connected components) on the Graph500 Kronecker graph of scale
22, as a relational query through ``session.sql`` + ``session.compute``
on one chip. One round of the label propagation every Pregel-style
Graphalytics driver runs is, in the program's SQL,

    elemmax(L, rowmax(joincols(A, t(L), "mul")))

``A`` the graph as a registered ``COOMatrix`` (both directions of every
undirected edge, entries 1.0), ``L`` the (n x 1) labels, ``L0[i] = i +
1``: the join pairs every entry ``A[i, j]`` with ``L[j]`` on the column
index, the aggregate takes each row's maximum. The client repeats the
round, ``L`` re-registered every time, until ``count(Lnew - L)`` reads
0; the fixpoint labels every vertex with the largest vertex id of its
component, plus one. Graphalytics validates WCC by equivalence of the
partition, so max-labels are as good as its min-labels; max because a
missing cell of ``A`` is a 0, which a positive label always beats.

Whole, the joined matrix is 2,396,366^2 x 4 B = 22.97 TB: a program
that would build it cannot serve the deployment, and the ``Deployment``
finds that out at a toy size before it makes any data
(:func:`can_serve`).

The graph is made here from ``graph_seed`` by this file's own copy of
the generator ``ldbc_graphalytics_g500_22`` describes (a configuration
file that is there is neither edited nor imported): the same seed gives
the same 2,396,366 vertices and 64,154,641 undirected edges. ``--seed``
orders the coordinate list the program is handed.

The plain reference knows nothing of the program: scipy's
``connected_components`` over the canonical edge list (lo < hi, sorted)
on the host, each component's largest id + 1; and the round count of the
synchronous propagation from a plain numpy loop over a CSR of the same
list. Its control runs that loop with the labels rounded to bfloat16."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.reference import bf16, seed_words

QUERY = "wcc_g500"
NAME = "ldbc_graphalytics_wcc_g500_22"
ROUNDS_MAX = 200        # a propagation that has not settled by then is
                        # a fault, not a graph (this one settles in < 10)


def kronecker_graph(scale: int, edge_factor: int, initiator, graph_seed: int):
    """(lo, hi, vertices): the undirected edges lo < hi of the Graph500
    Kronecker graph as LDBC Graphalytics keeps it, vertices renumbered
    0..V-1 in label order, edges sorted by (lo, hi).

    Graph500's generator: each of ``edge_factor << scale`` edges picks,
    bit by bit, a quadrant of the adjacency matrix with the initiator's
    probabilities (A, B, C; D the rest), then the vertex labels are
    permuted. The edges are drawn on the device (44 uniforms an edge at
    scale 22), the clean-up is the host's: one sort of 64-bit keys
    (``ldbc_graphalytics_g500_22.py``'s generator, copied: the same
    ``graph_seed`` gives the same graph)."""
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
    one 64-bit item an edge, in place."""
    m = lo.size
    both = np.empty((2 * m, 2), np.int32)
    both[:m, 0], both[:m, 1] = lo, hi
    both[m:, 0], both[m:, 1] = hi, lo
    np.random.default_rng(seed_words(seed) + (5,)).shuffle(
        both.view(np.int64).reshape(-1))
    return np.ascontiguousarray(both[:, 0]), np.ascontiguousarray(both[:, 1])


# -- the plain reference ------------------------------------------------------


def component_labels(lo, hi, nodes: int):
    """(labels (nodes,) float64, components): every vertex labelled with
    the largest vertex id of its weakly connected component, plus one,
    by scipy's ``connected_components`` over the undirected edges."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    count, comp = connected_components(
        sp.coo_matrix((np.ones(lo.size, np.int8), (lo, hi)),
                      shape=(nodes, nodes)), directed=False)
    top = np.zeros(count, np.float64)
    np.maximum.at(top, comp, np.arange(1, nodes + 1, dtype=np.float64))
    return top[comp], int(count)


def propagate(lo, hi, nodes: int, rnd=lambda x: x, rounds_max=ROUNDS_MAX):
    """(labels, rounds) of the synchronous max-label propagation from
    ``L0[i] = i + 1``: every round each vertex takes the largest of its
    own label and its neighbours' of the round before (``rnd`` rounds
    what the round reads: the identity for the reference, bfloat16 for
    the control), until a round changes nothing; that last round is
    counted, as the client's loop counts it. Plain numpy over a CSR of
    the canonical list."""
    import scipy.sparse as sp
    adj = sp.csr_matrix((np.ones(2 * lo.size, np.int8),
                         (np.concatenate([lo, hi]),
                          np.concatenate([hi, lo]))), shape=(nodes, nodes))
    has = np.diff(adj.indptr) > 0
    starts = adj.indptr[:-1][has]
    L = np.arange(1, nodes + 1, dtype=np.float64)
    for rounds in range(1, rounds_max + 1):
        read = rnd(L)
        new = L.copy()
        new[has] = np.maximum(L[has], np.maximum.reduceat(
            read[adj.indices], starts))
        if np.array_equal(new, L):
            return L, rounds
        L = new
    return L, rounds_max


class Labelling(tuple):
    """A query's answer: (labels on the host, the rounds it took).
    Times a scalar the labels are scaled, as an array answer would be
    (the harness's own test of a broken timed path multiplies an answer
    by 1.001)."""

    def __mul__(self, factor):
        labels, rounds = self
        return Labelling((labels * factor, rounds))


def can_serve(interpret=False):
    """Whether this program answers a round from the graph's entries
    alone, asked at a toy size (64 vertices) through a throw-away
    session: what ``last_plan()`` says of it. A program without the
    semiring product (a parent commit) materialises the join as a dense
    array — nothing at this size, 22.97 TB at the deployment's, where
    its executor refuses it by its cap only after the graph was made —
    and says so here, in seconds, before any data is made."""
    import jax
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.coo import COOMatrix
    from matrel_tpu.session import MatrelSession

    if not hasattr(MatrelSession, "last_plan"):
        return False, "no MatrelSession.last_plan"
    rng = np.random.default_rng(0)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    s = MatrelSession(mesh=mesh,
                      config=MatrelConfig(pallas_interpret=interpret))
    n = 64
    a, b = rng.integers(0, n, 96), rng.integers(0, n, 96)
    keys = np.unique(np.minimum(a, b) * n + np.maximum(a, b))
    lo, hi = keys // n, keys % n
    lo, hi = lo[lo != hi], hi[lo != hi]
    s.register("A", COOMatrix.from_edges(
        np.concatenate([lo, hi]), np.concatenate([hi, lo]), None,
        shape=(n, n)))
    s.register("L", BlockMatrix.from_numpy(
        np.arange(1, n + 1, dtype=np.float32)[:, None], mesh=mesh))
    try:
        s.compute(s.sql('elemmax(L, rowmax(joincols(A, t(L), "mul")))'))
    except Exception as ex:     # whatever it cannot parse, plan or run
        return False, f"{type(ex).__name__}: {ex}"
    said = s.last_plan()
    return bool(said.get("semiring")) and not said.get(
        "densified_products"), said


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        from matrel_tpu.config import MatrelConfig, set_default_config

        cfg = MatrelConfig(cse_enable=True, pallas_interpret=interpret)
        if interpret:
            # COOMatrix asks the default config whether Pallas runs
            set_default_config(cfg)
        ok, said = can_serve(interpret)
        if not ok:
            raise RuntimeError(
                f"{NAME}: this program cannot serve the deployment: the "
                "round elemmax(L, rowmax(joincols(A, t(L), \"mul\"))) at "
                "64 vertices was not answered as a semiring product of "
                f"the graph's entries (the program said: {said}); at "
                "2,396,366 vertices it would materialise the join, "
                "22.97 TB")
        from matrel_tpu.core import coo as coo_lib, mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.interpret = interpret
        g = spec["graph"]
        # a rehearsal runs a smaller scale of the same generator: the
        # vertices' share, to the nearest power of two
        self.kron_scale = g["scale"] if scale >= 1.0 else max(
            10, g["scale"] + round(math.log2(scale)))
        t = time.perf_counter()
        self.lo, self.hi, self.nodes = kronecker_graph(
            self.kron_scale, g["edge_factor"], g["initiator"],
            g["graph_seed"])
        if self.nodes >= 1 << 24:
            raise ValueError(f"{NAME}: {self.nodes} vertices: float32 "
                             "holds a label exactly only below 2^24")
        t_gen = time.perf_counter()
        src, dst = directed_in_seed_order(self.lo, self.hi, seed)
        self.edges = int(src.size)
        t_order = time.perf_counter()
        q = spec["queries"][QUERY]
        self.round_sql, self.changed_sql = q["round_sql"], q["changed_sql"]
        self._mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        # plan templates: a round with a new label array rebinds the
        # compiled program (serve/mqo.py) instead of compiling again
        self.session = MatrelSession(mesh=self._mesh, config=cfg)
        self.session.register("A", COOMatrix.from_edges(
            dst, src, None, shape=(self.nodes, self.nodes)))
        del src, dst
        self.L0 = BlockMatrix.from_numpy(
            np.arange(1, self.nodes + 1, dtype=np.float32)[:, None],
            mesh=self._mesh)
        self.parts = {"generate_s": round(t_gen - t, 3),
                      "order_s": round(t_order - t_gen, 3),
                      "register_s": round(time.perf_counter() - t_order, 3)}
        # plans the program had built before this deployment's first
        # call: the toy graph's of can_serve
        self._builds_before = coo_lib.plan_builds()
        self.calls = 0
        self.rounds = 0             # of the newest query
        self.facts = {}             # what the program said of the product
        self.densified = 0          # leaves or joins that were densified
        self.not_by_kernel = 0      # rounds the kernel did not answer
        self.overflow_edges = 0
        self.misses_after_first = 0     # plan lookups that compiled

    # -- the timed path ------------------------------------------------------

    def _note(self, said):
        """What the program said of the plan it answered a round with
        (session.last_plan)."""
        self._missed(said)
        products = said.get("semiring", [])
        self.densified += len(said.get("densified_products", []))
        self.not_by_kernel += not (
            products and all(r.get("how") == "kernel" for r in products))
        self.overflow_edges = max([self.overflow_edges] + [
            int(r.get("overflow_edges", 0)) for r in products])
        if products:
            self.facts = products[0]
        if self.calls == 0 and not products:
            raise RuntimeError(
                f"{NAME}: the first round was not answered as a semiring "
                f"product (the program said: {said}); this program "
                "cannot serve the deployment")

    def _missed(self, said):
        if self.calls and said.get("hit") is False:
            self.misses_after_first += 1

    def run(self, query, span):
        if query != QUERY:
            raise KeyError(query)
        s = self.session
        L = self.L0
        for rounds in range(1, ROUNDS_MAX + 1):
            s.register("L", L)
            with span("compute"):
                new = s.compute(s.sql(self.round_sql))
            self._note(s.last_plan())
            s.register("Lnew", new)
            with span("compute"):
                changed = s.compute(s.sql(self.changed_sql))
            self._missed(s.last_plan())
            with span("fetch"):
                moved = float(changed.to_numpy()[0, 0])
            L = new
            if moved == 0:
                break
        else:
            raise RuntimeError(f"{NAME}: no fixpoint in {ROUNDS_MAX} rounds")
        with span("fetch"):
            labels = L.to_numpy()[:, 0]
        self.calls += 1
        self.rounds = rounds
        return Labelling((labels, rounds))

    def program_controls(self, query):
        """The program has no lower-precision path of its own here: the
        product is one float32 multiply an entry and an extremum, with
        no ``passes``."""
        return []

    def _plan_builds(self):
        from matrel_tpu.core import coo as coo_lib
        return coo_lib.plan_builds() - self._builds_before

    def notes(self, query):
        return {"kron_scale": self.kron_scale, "vertices": self.nodes,
                "undirected_edges": int(self.lo.size), **self.parts,
                "rounds": self.rounds, "plan_builds": self._plan_builds(),
                "plan": self.facts}

    def shapes(self, query):
        """What the count functions take, and the rounds a query took
        (the span readers count a query's roots by them)."""
        return {"nodes": self.nodes, "edges": self.edges,
                "rounds": self.rounds}

    # -- the plain reference, after the window --------------------------------

    def reference(self, query, rnd=None):
        """(labels, components, rounds): scipy's components with each
        one's largest id + 1, and the plain loop's round count (whose
        labels have to be the same: checked here)."""
        want, count = component_labels(self.lo, self.hi, self.nodes)
        labels, rounds = propagate(self.lo, self.hi, self.nodes)
        if not np.array_equal(labels, want):
            raise RuntimeError(f"{NAME}: the plain loop's fixpoint is "
                               "not the components' labels")
        return want, count, rounds

    def control(self, query):
        """The plain loop with the labels a round reads rounded to
        bfloat16 (8 bits of a label that needs 22), in the program's
        place."""
        labels, rounds = propagate(self.lo, self.hi, self.nodes, rnd=bf16)
        return Labelling((labels, rounds))

    def compare(self, query, answer, want):
        """Every label EQUAL to the reference's (the partition's
        equivalence and more), the component count and the round count
        equal; and the executor: nothing densified, no entry left to a
        scalar tail, every round answered by the kernel, the plan built
        once a process, no plan lookup after the first query that
        compiled. A rehearsal on a mesh of several CPU devices lays its
        small graph out in blocks, which XLA's segment reduction
        answers: there the kernel's count is reported and not held."""
        labels, rounds = answer
        ref, count, ref_rounds = want
        got = np.asarray(labels, np.float64)
        wrong = (int(np.count_nonzero(got != ref))
                 if got.shape == ref.shape else int(ref.size))
        return [
            (f"{query}.label_mismatches", wrong, 0),
            (f"{query}.component_count_off",
             abs(int(np.unique(got).size) - count), 0),
            (f"{query}.rounds_off", abs(int(rounds) - ref_rounds), 0),
            (f"{query}.densified_products", self.densified, 0),
            (f"{query}.overflow_edges", self.overflow_edges, 0),
            (f"{query}.rounds_not_by_kernel", self.not_by_kernel,
             self.not_by_kernel if self.interpret else 0),
            (f"{query}.plan_builds", self._plan_builds(), 1),
            (f"{query}.compiles_after_first_query",
             self.misses_after_first, 0)]
