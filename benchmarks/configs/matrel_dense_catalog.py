"""Deployment ``matrel_dense_catalog``: builds the catalog on the device
from the seed, hands it to a default-config MatrelSession, runs its SQL
queries through ``session.compute(session.sql(q)).to_numpy()``, and holds
the plain float64 reference, its bfloat16 control, and the program's own
lower ``matmul_precision`` settings as controls."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import bf16, device_key, rel_err


def _cut(shape, scale):
    """Rehearsal only: rows (and the square sizes that are rows x rows)
    are cut, widths of 100 never."""
    if scale >= 1.0:
        return tuple(shape)
    return tuple(d if d <= 100 else max(256, int(round(d * scale / 128)) * 128)
                 for d in shape)


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core import padding
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.sql = {q: spec["queries"][q]["sql"] for q in queries}
        self.session = MatrelSession(config=MatrelConfig())
        self._lower = {}
        mesh = self.session.mesh
        names = sorted(spec["tables"])
        shapes = [_cut(spec["tables"][n], scale) for n in names]
        padded = [padding.padded_shape(s, mesh) for s in shapes]
        specs = [padding.canonical_spec(p, mesh) for p in padded]
        key = device_key(seed)

        @jax.jit
        def generate(key):
            out = []
            for i, (shape, ps, sp) in enumerate(zip(shapes, padded, specs)):
                vals = jax.random.uniform(jax.random.fold_in(key, i), ps,
                                          dtype=jnp.float32)
                r = jnp.arange(ps[0])[:, None] < shape[0]
                c = jnp.arange(ps[1])[None, :] < shape[1]
                out.append(jax.lax.with_sharding_constraint(
                    jnp.where(r & c, vals, 0.0), NamedSharding(mesh, sp)))
            return out

        self._arrays = dict(zip(names, zip(generate(key), shapes)))
        self._tables_of = {
            name: BlockMatrix.from_array(
                self._arrays[name][0], self._arrays[name][1], mesh, sp,
                block_size=self.session.config.block_size)
            for name, sp in zip(names, specs)}
        for name, table in self._tables_of.items():
            self.session.register(name, table)
        self._host = None

    # -- the timed path ------------------------------------------------------

    def run(self, query, span, session=None):
        """``session`` is the deployment's own in a run;
        ``program_controls`` passes one of a lower precision."""
        session = session or self.session
        with span("parse"):
            expr = session.sql(self.sql[query])
        with span("compute"):
            out = session.compute(expr)
        with span("fetch"):
            return out.to_numpy()

    def program_controls(self, query):
        """(knob, answer) for each lower ``matmul_precision`` the program
        has, switched on in the program's place: the same tables in a
        session of that configuration."""
        import contextlib
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.session import MatrelSession
        out = []
        for precision in ("high", "default"):
            if precision not in self._lower:
                s = MatrelSession(config=MatrelConfig(
                    matmul_precision=precision))
                for name, table in self._tables_of.items():
                    s.register(name, table)
                self._lower[precision] = s
            out.append((f"matmul_precision={precision}", self.run(
                query, lambda name: contextlib.nullcontext(),
                session=self._lower[precision])))
        return out

    def notes(self, query):
        """Optimizer and trace times of the query's plan, for the line
        beside ``first_call_s``."""
        meta = self.session.compile(self.session.sql(self.sql[query])).meta
        return {k: meta.get(k) for k in ("optimize_ms", "trace_ms",
                                         "rule_hits")}

    def shapes(self, query):
        return {}

    # -- the plain reference, after the window -------------------------------

    def _tables(self):
        if self._host is None:
            self._host = {n: np.asarray(a, np.float64)[:s[0], :s[1]]
                          for n, (a, s) in self._arrays.items()}
        return self._host

    def reference(self, query, rnd=lambda x: x):
        """float64 on the host. ``rnd`` is applied to every matmul operand
        and to the selected table: the identity for the reference, bfloat16
        rounding for the control."""
        t = self._tables()
        if query == "rowsum_mn":
            return (rnd(t["M"]) @ rnd(t["N"])).sum(1, keepdims=True)
        if query == "rowsum_chain":
            bc = rnd(t["B"]) @ rnd(t["C"])
            return (rnd(t["A"]) @ rnd(bc)).sum(1, keepdims=True)
        if query == "select_rowcount":
            return (rnd(t["M"]) > 0.9).sum(1, keepdims=True).astype(np.float64)
        raise KeyError(query)

    def control(self, query):
        return self.reference(query, rnd=bf16)

    def compare(self, query, answer, want):
        return [(f"{query}.max_rel_err", rel_err(answer, want),
                 float(self.spec["queries"][query]["limit"]))]
