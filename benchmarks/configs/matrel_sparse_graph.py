"""Deployment ``matrel_sparse_graph``: the block-sparse x dense product
through ``session.compute`` and PageRank through
``workloads.pagerank.pagerank_edges``, on data made from the seed; the
plain float64 references (copied from chip_smoke.py, PR 22), their
bfloat16 controls, and the program's own lower-precision paths as
controls. Only what the cell's queries need is built."""

from __future__ import annotations

import contextlib
import math

import numpy as np

from benchmarks.reference import bf16, device_key, rel_err, seed_words


def _no_span(name):
    return contextlib.nullcontext()


def _rows(n, scale, mult, floor):
    if scale >= 1.0:
        return n
    return max(floor, int(round(n * scale / mult)) * mult)


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        from matrel_tpu.config import MatrelConfig, set_default_config
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.seed = seed
        self.interpret = interpret
        cfg = MatrelConfig(pallas_interpret=interpret)
        if interpret:
            # pagerank_edges asks the default config whether Pallas runs
            set_default_config(cfg)
        self.session = MatrelSession(config=cfg)
        if "spmm_sd" in queries:
            self._build_spmm(scale)
        if "pagerank_30" in queries:
            self._build_graph(scale)

    # -- block-sparse x dense ------------------------------------------------

    def _build_spmm(self, scale):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from matrel_tpu.core import padding
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core.sparse import BlockSparseMatrix

        sp = self.spec["spmm"]
        bs = sp["block_size"]
        n = _rows(sp["S"][0], scale, bs, 4 * bs)
        width = sp["D"][1]
        g = n // bs
        # where the tiles lie is the deployment's, not the seed's: the
        # program appends a zero tile for every empty block row (23 to 33
        # of them over eight structure seeds), so positions drawn from
        # --seed would change the kernel's shapes, and with them the
        # compiled program and the work (chip_smoke.py's generator)
        nnzb = max(1, int(round(g * g * sp["block_density"])))
        flat = np.sort(np.random.default_rng(sp["structure_seed"]).choice(
            g * g, size=nnzb, replace=False))
        rng = np.random.default_rng(seed_words(self.seed))
        self.block_rows = (flat // g).astype(np.int32)
        self.block_cols = (flat % g).astype(np.int32)
        mesh = self.session.mesh
        rep = NamedSharding(mesh, P())
        d_pad = padding.padded_shape((n, width), mesh)
        d_spec = padding.canonical_spec(d_pad, mesh)

        @jax.jit
        def generate(key):
            tiles = jax.random.uniform(jax.random.fold_in(key, 0),
                                       (nnzb, bs, bs), dtype=jnp.float32)
            d = jax.random.uniform(jax.random.fold_in(key, 1), d_pad,
                                   dtype=jnp.float32)
            r = jnp.arange(d_pad[0])[:, None] < n
            c = jnp.arange(d_pad[1])[None, :] < width
            return (jax.lax.with_sharding_constraint(tiles, rep),
                    jax.lax.with_sharding_constraint(
                        jnp.where(r & c, d, 0.0),
                        NamedSharding(mesh, d_spec)))

        self.tiles, self.d = generate(device_key(self.seed))
        self.S = BlockSparseMatrix(
            blocks=self.tiles, block_rows=jax.device_put(self.block_rows, rep),
            block_cols=jax.device_put(self.block_cols, rep),
            shape=(n, n), block_size=bs, mesh=mesh)
        self.D = BlockMatrix.from_array(
            self.d, (n, width), mesh, d_spec,
            block_size=self.session.config.block_size)
        self.n, self.width, self.bs = n, width, bs
        present = np.unique(self.block_rows)
        k = min(sp["sampled_block_rows"], present.size)
        self.sample = np.sort(rng.choice(present, size=k, replace=False))
        empty = np.setdiff1d(np.arange(g), self.block_rows)
        self.empty_row = int(empty[0]) if empty.size else None

    # -- the graph -------------------------------------------------------------

    def _build_graph(self, scale):
        pr = self.spec["pagerank"]
        self.nodes = pr["nodes"] if scale >= 1.0 \
            else max(int(pr["nodes"] * scale), 4096)
        m = pr["edges"] if scale >= 1.0 \
            else max(int(pr["edges"] * scale), 40960)
        # the graph is the deployment's (chip_smoke.py's generator at its
        # default seed); --seed orders the edge list. Twelve graphs drawn
        # from other seeds all pack into the same 1954 x 5376 slots, but
        # two of them carry an overflow tail (of 4 and of 9 entries),
        # which changes the compiled program's shapes: a run on such a
        # seed would miss the compile cache and compile in set-up.
        rng = np.random.default_rng(pr["graph_seed"])
        src = rng.integers(0, self.nodes, m).astype(np.int32)
        dst = rng.integers(0, self.nodes, m).astype(np.int32)
        order = np.random.default_rng(seed_words(self.seed) + (5,)) \
            .permutation(m)
        self.src, self.dst = src[order], dst[order]
        self.rounds, self.alpha = pr["rounds"], pr["alpha"]

    # -- the timed path ------------------------------------------------------

    def run(self, query, span, **knobs):
        """``knobs`` are empty in a run; ``program_controls`` passes the
        program's own lower-precision settings."""
        if query == "spmm_sd":
            with span("compute"):
                out = self.session.compute(
                    knobs.get("S", self.S).multiply(knobs.get("D", self.D)))
            with span("wait"):
                out.data.block_until_ready()
            return out.data
        if query == "pagerank_30":
            from matrel_tpu.workloads import pagerank as pr_lib
            # off the TPU "auto" is the segment-sum path by design, so a
            # rehearsal names the executor (as chip_smoke.py does)
            with span("compute"):
                r = pr_lib.pagerank_edges(
                    self.src, self.dst, self.nodes, rounds=self.rounds,
                    alpha=self.alpha,
                    impl="onehot" if self.interpret else "auto", **knobs)
            with span("wait"):
                r.block_until_ready()
            return r
        raise KeyError(query)

    def program_controls(self, query):
        """(knob, answer) for each lower-precision path the program has
        of its own for this query, switched on in the program's place."""
        if query == "pagerank_30":
            # passes=3 is the f32-faithful default; 2 is the program's
            # "ranking-grade" setting, 1 a single bf16 part
            return [(f"passes={p}", np.asarray(self.run(query, _no_span,
                                                        passes=p)))
                    for p in (2, 1)]
        import jax.numpy as jnp
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core.sparse import BlockSparseMatrix
        # the kernel's float32 precision is fixed in code (HIGHEST); its
        # lower path is bfloat16 tiles and D (one MXU pass, bf16 product)
        s16 = BlockSparseMatrix(
            blocks=self.tiles.astype(jnp.bfloat16),
            block_rows=self.S.block_rows, block_cols=self.S.block_cols,
            shape=self.S.shape, block_size=self.bs, mesh=self.session.mesh)
        d16 = BlockMatrix.from_array(
            self.d.astype(jnp.bfloat16), (self.n, self.width),
            self.session.mesh, self.D.spec,
            block_size=self.session.config.block_size)
        out = self.run(query, _no_span, S=s16, D=d16)
        return [("bf16_operands", out.astype(jnp.float32))]

    def notes(self, query):
        if query == "spmm_sd":
            meta = self.session.compile(self.S.multiply(self.D)).meta
            return {k: meta.get(k) for k in ("optimize_ms", "trace_ms")}
        return {}

    def shapes(self, query):
        """What the count functions take."""
        if query == "spmm_sd":
            return {"nnzb": int(self.block_rows.size), "block_size": self.bs,
                    "rows": self.n, "width": self.width, "itemsize": 4,
                    "precision": "highest"}
        return {"nodes": self.nodes, "edges": int(self.src.size),
                "rounds": self.rounds}

    # -- the plain references, after the window -------------------------------

    def reference(self, query, rnd=lambda x: x):
        """``rnd`` is applied to what every product reads: the identity
        for the reference, bfloat16 rounding for the control."""
        if query == "spmm_sd":
            return self._spmm_reference(rnd)
        return self._pagerank_reference(rnd)

    def control(self, query):
        """The reference in the program's place, its operands rounded to
        bfloat16: a rank vector, or block rows by index."""
        return self.reference(query, rnd=bf16)

    def _spmm_reference(self, rnd):
        """float64 tile products of the sampled block rows (a full host
        product is 100+ GFLOP), and zeros for the empty one."""
        bs = self.bs
        d = rnd(np.asarray(self.d, np.float64)[:self.n, :self.width])
        want = {}
        for i in self.sample:
            acc = np.zeros((bs, self.width), np.float64)
            for t in np.nonzero(self.block_rows == i)[0]:
                tile = rnd(np.asarray(self.tiles[int(t)], np.float64))
                c = int(self.block_cols[t])
                acc += tile @ d[c * bs:(c + 1) * bs]
            want[int(i)] = acc
        if self.empty_row is not None:
            want[self.empty_row] = np.zeros((bs, self.width), np.float64)
        return want

    def _pagerank_reference(self, rnd):
        """scipy float64 power iteration with the workload's semantics;
        ``rnd`` rounds what each round's matvec reads."""
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

    def compare(self, query, answer, want):
        limit = float(self.spec["queries"][query]["limit"])
        if query == "pagerank_30":
            return [("pagerank_30.max_rel_err", rel_err(answer, want), limit)]
        bs = self.bs
        rows = answer if isinstance(answer, dict) else {
            i: np.asarray(answer[i * bs:(i + 1) * bs, :self.width])
            for i in want}
        scale = max(float(np.max(np.abs(w))) for w in want.values())
        err = max(float(np.max(np.abs(
            np.asarray(rows[i], np.float64) - w))) for i, w in
            want.items() if i != self.empty_row) / max(scale, 1e-30)
        out = [("spmm_sd.max_rel_err", err if math.isfinite(err)
                else float("inf"), limit)]
        if self.empty_row is not None:
            z = np.asarray(rows[self.empty_row])
            out.append(("spmm_sd.empty_row_max_abs",
                        float(np.max(np.abs(z))), 0.0))
        return out
