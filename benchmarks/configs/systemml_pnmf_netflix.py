"""Deployment ``systemml_pnmf_netflix``: non-negative matrix factorization
under the Kullback-Leibler (Poisson) divergence, Lee and Seung's second
multiplicative algorithm (NIPS 2000, Theorem 2) as Apache SystemML's
``PNMF.dml`` writes it, on a ratings matrix of the Netflix Prize data
set's shape, through ``session.sql`` + ``session.compute`` on one chip:

    H <- H .* (t(W) * (V / (W * H))) / t(colsum(W))
    W <- W .* ((V / (W * H)) * t(H)) / t(rowsum(H))

``V / (W * H)`` is wanted only where ``V`` has an entry (0 / x = 0): the
program's ``sampled`` node under a product, SystemML's fused ``wdivmm``.
Whole, ``W * H`` is 34 GB; a program that would densify it cannot serve
the deployment, and the ``Deployment`` finds that out at a toy size
before it makes any data.

Departures from Theorem 2 / ``PNMF.dml``, each also in the
configuration's ``assumed``: no epsilon in a denominator (the factors
stay positive: uniform (0, 1] starts, positive ratings); 3 iterations a
query; the objective (``sum(W H) - sum(V .* log(W H))``) is no part of
the timed query.

The ratings' STRUCTURE is made here from ``ratings_seed`` by this file's
own copy of the generator ``matfast_gnmf_netflix`` describes (a
configuration file that is there is neither edited nor imported): the
same seed gives the same 100,480,507 cells, and so the same plans, as the
GNMF cell's. ``--seed`` gives ``W0``, ``H0`` and the order of the
coordinate list the program is handed.

The plain reference computes the same updates from the canonical
coordinate list (sorted by user, then movie): ``(W H)`` at the entries
as gathered row products of 128 float32 terms with one tree sum, made
by plain ``jax.numpy`` in blocks; the quotient, the element-wise parts
and the final sums in float64 on the host, each product's sums from
float32 partial sums of 128 terms. No kernel, no plan, no slab: nothing
of the program. Its bfloat16 control (the dense sides rounded) and the
program's own ``passes`` are the lower-precision readings the limits are
set between."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.reference import bf16, device_key, seed_words

QUERY = "pnmf_fit"
SUB = 128               # terms a float32 partial sum of the reference holds
GEN_BLOCK = 4096        # users a block of the generator's passes
DOT_BLOCK = 1 << 20     # entries a block of the reference's dots
NAME = "systemml_pnmf_netflix"


def _lognormal_targets(n, median, largest, total, key):
    """n expected degrees: the stratified quantiles of a log-normal of
    this median, none above ``largest``, its sigma found by bisection so
    that they sum to ``total`` (the cap takes from the mean, a wider
    sigma puts it back and leaves the median alone), in an order drawn
    from ``key``."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import ndtri

    z = ndtri((jnp.arange(n, dtype=jnp.float32) + 0.5) / n)

    def degrees(sigma):
        return jnp.minimum(median * jnp.exp(sigma * z), largest)

    lo, hi = jnp.float32(0.0), jnp.float32(6.0)
    for _ in range(30):
        mid = 0.5 * (lo + hi)
        under = jnp.sum(degrees(mid)) < total
        lo, hi = jnp.where(under, mid, lo), jnp.where(under, hi, mid)
    d = degrees(0.5 * (lo + hi))
    return jax.random.permutation(key, d * (total / jnp.sum(d)))


def ratings_structure(users, movies, entries, marginals, ratings_seed):
    """(rows, cols) int32, sorted by (row, col): exactly ``entries``
    distinct cells of a users x movies matrix, every row and column
    non-empty, the degrees as ``marginals`` describes them; and the
    seconds its parts took."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(ratings_seed)
    mp = -(-movies // 8) * 8                # whole bytes a row of cells
    blocks = -(-users // GEN_BLOCK)
    up = blocks * GEN_BLOCK
    # six sigma of the draw's own spread above the count, and the fit's
    # residual: what is over is trimmed, what is short cannot be made up
    over = 1.0 + 6.0 / math.sqrt(entries) + 0.005
    mu, mm = marginals["user"], marginals["movie"]

    @jax.jit
    def draw(key):
        # a rehearsal's smaller matrix keeps the shape of the degrees:
        # medians and caps shrink with the means
        cut = entries / users / mu["mean"]
        du = _lognormal_targets(users, cut * mu["median"],
                                min(cut * mu["largest"], 0.995 * movies),
                                entries, jax.random.fold_in(key, 1))
        cut = entries / movies / mm["mean"]
        dm = _lognormal_targets(movies, cut * mm["median"],
                                min(cut * mm["largest"], 0.995 * users),
                                entries, jax.random.fold_in(key, 2))
        du = jnp.pad(du, (0, up - users)).reshape(blocks, GEN_BLOCK)
        dm = jnp.pad(dm, (0, mp - movies))

        def cells(a_blk, b):
            return -jnp.expm1(-a_blk[:, None] * b[None, :])

        def fit(_, ab):
            # rows, then columns, to their expected degrees
            a, b = ab
            row = jax.lax.map(lambda a_blk: jnp.sum(cells(a_blk, b), 1), a)
            a = a * du / jnp.maximum(row, 1e-30)
            col = jax.lax.fori_loop(
                0, blocks, lambda i, c: c + jnp.sum(cells(a[i], b), 0),
                jnp.zeros((mp,), jnp.float32))
            return a, b * dm / jnp.maximum(col, 1e-30)

        a, b = jax.lax.fori_loop(
            0, 12, fit, (du / math.sqrt(entries), dm / math.sqrt(entries)))
        # one forced rating a user and a movie, drawn by degree
        f = jnp.searchsorted(jnp.cumsum(dm) / jnp.sum(dm), jax.random.uniform(
            jax.random.fold_in(key, 3), (up,))).astype(jnp.int32)
        g = jnp.searchsorted(
            jnp.cumsum(du.reshape(-1)) / jnp.sum(du), jax.random.uniform(
                jax.random.fold_in(key, 4), (mp,))).astype(jnp.int32)
        f = jnp.minimum(f, movies - 1).reshape(blocks, GEN_BLOCK)
        g = jnp.minimum(g, users - 1)
        col = jnp.arange(mp, dtype=jnp.int32)

        def block(i):
            u = jax.random.uniform(jax.random.fold_in(key, 16 + i),
                                   (GEN_BLOCK, mp))
            row = i * GEN_BLOCK + jnp.arange(GEN_BLOCK, dtype=jnp.int32)
            held = ((u < over * cells(a[i], b))
                    | (col[None, :] == f[i][:, None])
                    | (g[None, :] == row[:, None]))
            held &= (col[None, :] < movies) & (row[:, None] < users)
            return jnp.packbits(held, axis=1)

        return (jax.lax.map(block, jnp.arange(blocks)), f.reshape(-1), g)

    packed, f, g = (np.asarray(x) for x in draw(key))
    t1 = time.perf_counter()
    # a block of users at a time: its bits as bytes, the set ones' places
    rows, cols = [], []
    for i in range(blocks):
        at = np.flatnonzero(np.unpackbits(packed[i].reshape(-1))
                            .view(bool)).astype(np.int32)
        row = at // mp
        rows.append(row + np.int32(i * GEN_BLOCK))
        cols.append(at - row * np.int32(mp))
    del packed
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    spare = rows.size - entries
    if spare < 0:
        raise RuntimeError(
            f"the draw gave {rows.size} ratings, {-spare} short of "
            f"{entries}: raise the oversampling")
    # trim to the exact count, the forced cells kept
    rng = np.random.default_rng([ratings_seed, 5])
    keep = np.ones(rows.size, bool)
    while spare:
        at = np.unique(rng.integers(0, rows.size, size=2 * spare + 16))
        at = at[keep[at] & (cols[at] != f[rows[at]])
                & (rows[at] != g[cols[at]])]
        at = rng.permutation(at)[:spare]
        keep[at] = False
        spare -= at.size
    rows, cols = rows[keep], cols[keep]
    return rows, cols, {"draw_s": round(t1 - t0, 3),
                        "unpack_s": round(time.perf_counter() - t1, 3)}


def _padded_segments(dest, n_dest):
    """Entries sorted by ``dest`` laid in rows of ``SUB``: (the entry a
    slot holds, -1 in padding: (rows, SUB) int32; the first row of each
    destination: (n_dest + 1,))."""
    cnt = np.bincount(dest, minlength=n_dest)
    first_row = np.zeros(n_dest + 1, np.int64)
    np.cumsum(np.maximum(-(-cnt // SUB), 1), out=first_row[1:])
    if np.all(dest[1:] >= dest[:-1]):
        order, d_sorted = None, dest
    else:
        # 16-bit keys sort by radix (movies are fewer than 32,768)
        key = dest.astype(np.int16) if n_dest < 2 ** 15 else dest
        order = np.argsort(key, kind="stable").astype(np.int32)
        d_sorted = dest[order]
    start = np.zeros(n_dest + 1, np.int64)
    np.cumsum(cnt, out=start[1:])
    slot = (first_row * SUB - start)[d_sorted]
    slot += np.arange(dest.size, dtype=np.int64)
    at = np.full(int(first_row[-1]) * SUB, -1, np.int32)
    at[slot] = (np.arange(dest.size, dtype=np.int32) if order is None
                else order)
    return at.reshape(-1, SUB), first_row


class Fit(tuple):
    """A query's answer: (H on the host, W on the device). Times a
    scalar both are scaled, as an array answer would be (the harness's
    own test of a broken timed path multiplies an answer by 1.001)."""

    def __mul__(self, factor):
        h, W = self
        return Fit((h * factor,
                    (W.data if hasattr(W, "to_numpy") else W) * factor))


def can_serve(interpret=False):
    """Whether this program answers the first update's sampled product
    at the entries alone, asked at a toy size (64 x 32, rank 8) through a
    throw-away session: what ``last_plan()`` says of it. A program
    without the ``sampled`` node (a parent commit) densifies ``V`` and
    multiplies ``W * H`` whole — nothing at this size, 34 GB at the
    deployment's — and says so here, in seconds, before any data is
    made."""
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
    n, m, k = 64, 32, 8
    at = rng.permutation(n * m)[:256]
    s.register("V", COOMatrix.from_edges(
        at // m, at % m, rng.integers(1, 6, at.size).astype(np.float32),
        shape=(n, m)))
    for name, shape in (("W", (n, k)), ("H", (k, m))):
        s.register(name, BlockMatrix.from_numpy(
            rng.uniform(0.1, 1.0, shape).astype(np.float32), mesh=mesh))
    try:
        s.compute(s.sql("H .* (t(W) * (V / (W * H))) / t(colsum(W))"))
    except Exception as ex:     # whatever it cannot parse, plan or run
        return False, f"{type(ex).__name__}: {ex}"
    said = s.last_plan()
    return bool(said.get("sampled")), said


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from matrel_tpu.config import MatrelConfig, set_default_config

        cfg = MatrelConfig(cse_enable=True, pallas_interpret=interpret)
        if interpret:
            # COOMatrix asks the default config whether Pallas runs
            set_default_config(cfg)
        ok, said = can_serve(interpret)
        if not ok:
            raise RuntimeError(
                f"{NAME}: this program cannot serve the deployment: the "
                "update H .* (t(W) * (V / (W * H))) / t(colsum(W)) at "
                "64 x 32, rank 8, was not answered by a sampled product "
                f"(the program said: {said}); at 480,189 x 17,770 it "
                "would densify V and multiply W * H whole, 34.1 GB each")
        from matrel_tpu.core import mesh as mesh_lib, padding
        from matrel_tpu.core import coo as coo_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.interpret = interpret
        m = spec["matrix"]
        self.users, self.movies = m["users"], m["movies"]
        self.entries = m["entries"]
        if scale < 1.0:     # rehearsal only: both sides cut, the rank never
            self.users = int(round(self.users * scale))
            self.movies = int(round(self.movies * scale))
            self.entries = int(round(self.entries * scale * scale))
        self.rank = spec["rank"]
        self.iterations = spec["iterations"]
        q = spec["queries"][QUERY]
        self.sql_h, self.sql_w = q["sql_h"], q["sql_w"]
        self._mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        # plan templates: an update with new factor arrays rebinds the
        # compiled program (serve/mqo.py) instead of compiling again
        self.session = MatrelSession(mesh=self._mesh, config=cfg)

        rows, cols, self.parts = ratings_structure(
            self.users, self.movies, self.entries, spec["marginals"],
            m["ratings_seed"])
        t = time.perf_counter()
        # ratings 1..5 by their shares, in thousandths
        shares = np.asarray(spec["marginals"]["value_shares"])
        of_draw = np.repeat(np.arange(1, 6, dtype=np.int8),
                            np.round(10 * shares).astype(int))
        vals = of_draw[np.random.default_rng(
            [m["ratings_seed"], 6]).integers(0, of_draw.size, rows.size)]
        # the canonical list (sorted by user, then movie) is the
        # reference's; the program gets it in an order drawn from
        # --seed: an entry one 64-bit item (user, movie, rating),
        # shuffled in place
        self.rows, self.cols, self.vals = rows, cols, vals
        item = (rows.astype(np.int64) << 24) | (cols.astype(np.int64) << 4) \
            | vals
        np.random.default_rng(seed_words(seed) + (7,)).shuffle(item)
        self.V = COOMatrix.from_edges(item >> 24, (item >> 4) & 0xFFFFF,
                                      (item & 15).astype(np.float32),
                                      shape=(self.users, self.movies))
        del item
        self.parts["values_order_s"] = round(time.perf_counter() - t, 3)
        self.generated = {
            "user_degree": _degree_facts(
                np.bincount(self.rows, minlength=self.users)),
            "movie_degree": _degree_facts(
                np.bincount(self.cols, minlength=self.movies)),
            "value_shares_pct": [round(100.0 * float(c) / vals.size, 2)
                                 for c in np.bincount(vals, minlength=6)[1:]]}

        key = device_key(seed)
        tiny = float(np.finfo(np.float32).tiny)

        def table(k, shape):
            # uniform in (0, 1], on the mesh's padded shape, the
            # padding zero
            padded = padding.padded_shape(shape, self._mesh)
            data = 1.0 - jax.random.uniform(k, shape, jnp.float32, 0.0,
                                            1.0 - tiny)
            return BlockMatrix.from_array(
                jnp.pad(data, [(0, p - n) for p, n in zip(padded, shape)]),
                shape, self._mesh,
                padding.canonical_spec(padded, self._mesh))

        self.W0 = table(jax.random.fold_in(key, 1), (self.users, self.rank))
        self.H0 = table(jax.random.fold_in(key, 2), (self.rank, self.movies))
        self.session.register("V", self.V)
        # plans the program had built before this deployment's first
        # call: the toy matrix's of can_serve
        self._builds_before = coo_lib.plan_builds()
        self.calls = 0
        self.facts = {}             # what the program said of its plans
        self.densified = 0          # leaves that were densified
        self.overflow_edges = 0     # the most any plan left to the tail
        self.misses_after_first = 0     # plan lookups that compiled
        self._ref = None            # the reference's tables on the device

    # -- the timed path ------------------------------------------------------

    def _fit(self, span):
        s = self.session
        W, H = self.W0, self.H0
        for _ in range(self.iterations):
            for name, sql in (("H", self.sql_h), ("W", self.sql_w)):
                s.register("W", W)
                s.register("H", H)
                with span("compute"):
                    out = s.compute(s.sql(sql))
                self._note(s.last_plan())
                if name == "H":
                    H = out
                else:
                    W = out
        return W, H

    def _note(self, said):
        """What the program said of the plan it answered an update
        with (session.last_plan)."""
        sampled = said.get("sampled", [])
        self.densified += len(said.get("densified_products", []))
        self.overflow_edges = max([self.overflow_edges] + [
            int(r.get("overflow_edges", 0)) for r in sampled])
        if self.calls and said.get("hit") is False:
            self.misses_after_first += 1
        for r in sampled:
            self.facts[r["orientation"]] = r
        if self.calls == 0 and not (
                sampled and "pallas_spmv" in said.get("executors", ())):
            # whatever can_serve saw at its toy size, at this one the
            # update densifies 34 GB or falls to XLA: stop in set-up
            raise RuntimeError(
                f"{NAME}: the first update was not answered by a sampled "
                f"product on the compact tables (the program said: {said}"
                "); this program cannot serve the deployment")

    def run(self, query, span):
        if query != QUERY:
            raise KeyError(query)
        try:
            W, H = self._fit(span)
        except Exception as ex:
            if self.calls == 0:
                raise RuntimeError(
                    f"{NAME}: the program cannot serve this deployment: "
                    f"{type(ex).__name__}: {ex}") from ex
            raise
        with span("fetch"):
            h = H.to_numpy()
            W.data.block_until_ready()
        self.calls += 1
        return Fit((h, W))

    def program_controls(self, query):
        """(knob, answer) for each lower-precision path the program has
        of its own: the same fit with the compact parts of both sampled
        products at ``passes`` 2 and 1 (3 is the f32-faithful default
        the executor runs); the dense lines' share is float32 at
        ``highest`` whatever ``passes`` says."""
        import jax
        import jax.numpy as jnp
        from matrel_tpu.ops import pallas_spmv as pc

        plans = [pc.plan_operands(self.V._get_wide_plan(transposed=t))
                 for t in (True, False)]
        (st_t, sts_t, arr_t), (st_f, sts_f, arr_f) = plans
        interp = self.interpret

        def fit(passes):
            @jax.jit
            def h_update(arrays, W, H):
                # the transposed plan's sources are the users: one
                # gather of W's rows serves the dot and the scatter
                num = pc.sampled_matmat_parts(
                    st_t, sts_t, arrays, W, "div", None, H.T,
                    passes=passes, interpret=interp)
                return H * num.T / jnp.sum(W, axis=0)[:, None]

            @jax.jit
            def w_update(arrays, W, H):
                num = pc.sampled_matmat_parts(
                    st_f, sts_f, arrays, H.T, "div", None, W,
                    passes=passes, interpret=interp)
                return W * num / jnp.sum(H, axis=1)[None, :]

            W = self.W0.data[:self.users, :self.rank]
            H = self.H0.data[:self.rank, :self.movies]
            for _ in range(self.iterations):
                H = h_update(arr_t, W, H)
                W = w_update(arr_f, W, H)
            return np.asarray(H), jnp.asarray(W)

        return [(f"passes={p}", fit(p)) for p in (2, 1)]

    def _plan_builds(self):
        from matrel_tpu.core import coo as coo_lib
        return coo_lib.plan_builds() - self._builds_before

    def notes(self, query):
        return {"users": self.users, "movies": self.movies,
                "entries": self.entries, "rank": self.rank,
                "iterations": self.iterations, **self.parts,
                "generated": self.generated,
                "plan_builds": self._plan_builds(), "plans": self.facts}

    def shapes(self, query):
        """What the count functions take, and what the program said of
        its two sampled products."""
        return {"users": self.users, "movies": self.movies,
                "entries": self.entries, "rank": self.rank,
                "iterations": self.iterations, "plans": self.facts}

    # -- the plain reference, after the window --------------------------------

    def _tables(self):
        """The reference's index tables on the device, made once: the
        canonical list in blocks of ``DOT_BLOCK`` entries for the dots,
        and for each product the entries sorted by destination in rows
        of ``SUB`` (the source a slot gathers; on the host the entry it
        holds, -1 in padding, and each destination's first row)."""
        import jax.numpy as jnp
        if self._ref is None:
            n = self.rows.size
            more = -n % DOT_BLOCK

            def blocks(ids):
                return jnp.asarray(np.pad(ids, (0, more))
                                   .reshape(-1, DOT_BLOCK))

            ref = {"dots": (blocks(self.rows), blocks(self.cols), n)}
            for name, dest, n_dest, src in (
                    ("movie", self.cols, self.movies, self.rows),
                    ("user", self.rows, self.users, self.cols)):
                at, first_row = _padded_segments(dest, n_dest)
                idx = src[np.maximum(at, 0)]
                idx[at < 0] = 0
                slab = 8192
                more_rows = -idx.shape[0] % slab    # whole slabs
                ref[name] = (jnp.asarray(np.pad(
                    idx, ((0, more_rows), (0, 0))).reshape(-1, slab, SUB)),
                    at, first_row, more_rows)
            self._ref = ref
        return self._ref

    def _entry_dots(self, W, H, rnd):
        """(W H) at the canonical list's entries, float64 from float32:
        an entry's two rows gathered, multiplied, one tree sum of
        ``rank`` terms, by plain jax.numpy in blocks."""
        import jax
        import jax.numpy as jnp
        rows, cols, n = self._tables()["dots"]

        @jax.jit
        def dots(w, ht, rows, cols):
            return jax.lax.map(
                lambda rc: jnp.sum(w[rc[0]] * ht[rc[1]], axis=1),
                (rows, cols)).reshape(-1)

        return np.asarray(dots(jnp.asarray(rnd(W), jnp.float32),
                               jnp.asarray(rnd(H.T), jnp.float32),
                               rows, cols), np.float64)[:n]

    def _sampled_product(self, orientation, q, D, rnd):
        """t(Q) · D (``orientation`` "movie": D users x rank) or Q · D
        ("user": D movies x rank) for the sampled values ``q`` of the
        canonical list, float64: float32 partial sums of ``SUB`` terms
        on the device, added on the host."""
        import jax
        import jax.numpy as jnp
        idx, at, first_row, more_rows = self._tables()[orientation]
        w = q.astype(np.float32)[np.maximum(at, 0)]
        w[at < 0] = 0
        w = jnp.asarray(np.pad(w, ((0, more_rows), (0, 0)))
                        .reshape(idx.shape))

        @jax.jit
        def partial_sums(d, idx, w):
            return jax.lax.map(
                lambda iw: jnp.sum(d[iw[0]] * iw[1][..., None], axis=1),
                (idx, w)).reshape(-1, d.shape[1])

        parts = np.asarray(partial_sums(jnp.asarray(rnd(D), jnp.float32),
                                        idx, w))
        return np.add.reduceat(parts, first_row[:-1], axis=0,
                               dtype=np.float64)

    def reference(self, query, rnd=lambda x: x):
        """(H, W) after the fit, float64. ``rnd`` rounds the dense
        sides — the factors a dot reads and the dense side of each
        product: the identity for the reference, bfloat16 for the
        control (the ratings 1 to 5 are exact in both)."""
        W = np.asarray(self.W0.data, np.float64)[:self.users, :self.rank]
        H = np.asarray(self.H0.data, np.float64)[:self.rank, :self.movies]
        v = self.vals.astype(np.float64)
        for _ in range(self.iterations):
            q = v / self._entry_dots(W, H, rnd)
            H = H * self._sampled_product("movie", q, W, rnd).T \
                / W.sum(axis=0)[:, None]
            q = v / self._entry_dots(W, H, rnd)
            W = W * self._sampled_product("user", q, H.T, rnd) \
                / H.sum(axis=1)[None, :]
        return H, W

    def control(self, query):
        return self.reference(query, rnd=lambda x: bf16(x))

    def compare(self, query, answer, want):
        """Both factors whole, each as the repo measures a matrix (max
        |got - want| over max |want|) and entry by entry relative to its
        own value over the entries above ``entry_floor``; every entry
        finite and not negative; and the executor: densified leaves and
        overflow entries held to 0, plan builds to the two a process
        needs, plan lookups after the first fit that compiled to 0."""
        q = self.spec["queries"][query]
        floor = float(q["entry_floor"])
        out, bad = [], 0
        for name, got, ref in zip("HW", answer, want):
            got = np.asarray(got.data if hasattr(got, "to_numpy") else got)
            got = got[:ref.shape[0], :ref.shape[1]]
            sound = bool(np.all(np.isfinite(got)))
            bad += int(got.size - np.count_nonzero(
                np.isfinite(got) & (got >= 0)))
            err = np.abs(got - ref)
            whole = float(err.max() / max(float(ref.max()), 1e-30)) \
                if sound else float("inf")
            # entry by entry: below the floor an entry's own value is no
            # scale (the reference is not negative)
            np.divide(err, ref, out=err, where=ref > floor)
            err[ref <= floor] = 0.0
            entry = float(err.max()) if sound else float("inf")
            out += [(f"{query}.{name}.max_rel_err", whole,
                     float(q["limit_" + name.lower()])),
                    (f"{query}.{name}.max_entry_rel_err", entry,
                     float(q["entry_limit"]))]
        return out + [
            (f"{query}.negative_or_not_finite", bad, 0),
            (f"{query}.densified_products", self.densified, 0),
            (f"{query}.overflow_edges", self.overflow_edges, 0),
            (f"{query}.plan_builds", self._plan_builds(), 2),
            (f"{query}.compiles_after_first_fit", self.misses_after_first, 0)]


def _degree_facts(deg):
    return {"least": int(deg.min()), "median": float(np.median(deg)),
            "mean": round(float(deg.mean()), 3), "largest": int(deg.max())}
