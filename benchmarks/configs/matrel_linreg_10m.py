"""Deployment ``matrel_linreg_10m``: one chip's quarter of the 10M x 1k
regression table and its column of responses, made from the seed on the
device panel by panel, handed to a default-config MatrelSession; the query
``inv(t(X) * X) * t(X) * y`` as upstream writes it, through
``session.sql`` + ``session.compute`` + ``to_numpy``; the plain reference
by partial sums of short panels, its control with X rounded to bfloat16, and the
program's own lower ``matmul_precision`` settings as controls. The
generator and the reference are this file's own: plain ``jax.numpy``,
nothing of the program."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import device_key, rel_err

QUERY = "theta"
REHEARSAL_PANEL = 1024


def _identity(x):
    return x


def _bf16(x):
    """x rounded to bfloat16, back in float32: what one MXU pass sees of
    a float32 operand."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.sql = spec["queries"][QUERY]["sql"]
        n, k = spec["tables"]["X"]
        panel = int(spec["panel_rows"])
        if scale < 1.0:     # rehearsal only: rows are cut, k never
            panel = REHEARSAL_PANEL
            n = panel * max(2, int(round(n * scale / panel)))
        if n % panel:
            raise ValueError(f"{n} rows are no whole number of panels of "
                             f"{panel}")
        self.n, self.k = n, k
        sigma = float(spec["noise_sigma"])
        devs = jax.devices()
        # the deployment is one chip; a rehearsal on a host with several
        # CPU devices takes the first
        self._mesh = mesh_lib.make_mesh((1, 1), devices=devs[:1])
        self.session = MatrelSession(mesh=self._mesh, config=MatrelConfig())
        self._lower = {}
        whole = NamedSharding(self._mesh, P(None, None))

        def generate(key):
            """Both tables, filled in place a panel at a time: a
            whole-table ``uniform`` would hold its 10 GB of bits beside
            its 10 GB of floats."""
            theta_star = jax.random.normal(jax.random.fold_in(key, 0),
                                           (k, 1), jnp.float32)

            def fill(i, tables):
                x, y = tables
                kx, ke = jax.random.split(jax.random.fold_in(key, 1 + i))
                xp = jax.random.uniform(kx, (panel, k), jnp.float32,
                                        -1.0, 1.0)
                yp = jnp.dot(xp, theta_star, precision="highest") \
                    + sigma * jax.random.normal(ke, (panel, 1), jnp.float32)
                at = (i * panel, 0)
                return (jax.lax.dynamic_update_slice(x, xp, at),
                        jax.lax.dynamic_update_slice(y, yp, at))

            return jax.lax.fori_loop(
                0, n // panel, fill,
                (jnp.zeros((n, k), jnp.float32),
                 jnp.zeros((n, 1), jnp.float32)))

        x, y = jax.jit(generate, out_shardings=(whole, whole))(
            device_key(seed))
        self.arrays = {"X": x, "y": y}
        self._tables_of = {
            name: BlockMatrix.from_array(
                arr, tuple(arr.shape), self._mesh, P(None, None),
                block_size=self.session.config.block_size)
            for name, arr in self.arrays.items()}
        for name, table in self._tables_of.items():
            self.session.register(name, table)

        ref_panel = min(int(spec["reference_panel_rows"]), panel)
        self.ref_panels = n // ref_panel

        def partial_sums(x, y, i, rnd):
            at = (i * ref_panel, 0)
            xp = rnd(jax.lax.dynamic_slice(x, at, (ref_panel, k)))
            yp = jax.lax.dynamic_slice(y, at, (ref_panel, 1))
            return (jnp.dot(xp.T, xp, precision="highest"),
                    jnp.dot(xp.T, yp, precision="highest"))

        self._partial = jax.jit(partial_sums, static_argnums=3)
        print("setup deployment "
              f"rows={n} k={k} panels={n // panel} "
              + " ".join(f"{name}_bytes_on_device="
                         f"{arr.on_device_size_in_bytes()}"
                         for name, arr in self.arrays.items()), flush=True)

    # -- the timed path ------------------------------------------------------

    def run(self, query, span, session=None):
        """``session`` is the deployment's own in a run;
        ``program_controls`` passes one of a lower precision."""
        session = session or self.session
        with span("parse"):
            expr = session.sql(self.sql)
        with span("compute"):
            out = session.compute(expr)
        with span("fetch"):
            return out.to_numpy()

    def program_controls(self, query):
        """(knob, answer) for each lower ``matmul_precision`` the program
        has, switched on in the program's place: the same two tables (no
        copy) in a session of that configuration."""
        import contextlib
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.session import MatrelSession
        out = []
        for precision in ("high", "default"):
            if precision not in self._lower:
                s = MatrelSession(mesh=self._mesh, config=MatrelConfig(
                    matmul_precision=precision))
                for name, table in self._tables_of.items():
                    s.register(name, table)
                self._lower[precision] = s
            out.append((f"matmul_precision={precision}", self.run(
                query, lambda name: contextlib.nullcontext(),
                session=self._lower[precision])))
        return out

    def notes(self, query):
        meta = self.session.compile(self.session.sql(self.sql)).meta
        return {k: meta.get(k) for k in (
            "optimize_ms", "trace_ms", "rule_hits", "executors", "mesh",
            "hbm_plan_bytes", "products")}

    def shapes(self, query):
        """What counts/linreg.py takes."""
        return {"n": self.n, "k": self.k, "itemsize": 4,
                "precision": "highest"}

    # -- the plain reference, after the window -------------------------------

    def reference(self, query, rnd=_identity):
        """``t(X) * X`` and ``t(X) * y`` a panel at a time in float32 at
        ``precision="highest"`` on the device (a panel's sum runs over
        8,192 rows: the float32 accumulator of a longer one loses more
        than the limit has room for), the panels' partial sums added in
        float64 on the host, and the k x k system solved in float64 by
        numpy. ``rnd`` is applied to every panel of X as it is read: the
        identity for the reference, bfloat16 rounding for the control."""
        gram = np.zeros((self.k, self.k), np.float64)
        rhs = np.zeros((self.k, 1), np.float64)
        for i in range(self.ref_panels):
            g, r = self._partial(self.arrays["X"], self.arrays["y"], i, rnd)
            gram += np.asarray(g, np.float64)
            rhs += np.asarray(r, np.float64)
        return np.linalg.solve(gram, rhs)

    def control(self, query):
        """The reference in the program's place, X rounded to bfloat16."""
        return self.reference(query, rnd=_bf16)

    def compare(self, query, answer, want):
        return [(f"{query}.max_rel_err", rel_err(answer, want),
                 float(self.spec["queries"][query]["limit"]))]
