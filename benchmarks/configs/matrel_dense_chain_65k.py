"""Deployment ``matrel_dense_chain_65k``: three bfloat16 catalog tables
made from the seed on the devices, sharded ``P(x, y)`` over the host's
2x2 mesh (a 1x1 mesh where the process has fewer than four devices: a CPU
rehearsal), handed to a default-config MatrelSession; the query
``A * B * C`` through ``session.sql`` + ``session.compute``, the product
left sharded on the devices; the plain reference of sampled rows of it,
and the reference with its operands rounded to float8 as the control.
The generator and the reference are this file's own: plain ``jax.numpy``,
nothing of the program."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import device_key, rel_err, seed_words

QUERY = "chain_abc"


def _identity(x):
    return x


def _float8(x):
    """x rounded to float8 (e4m3: three bits of mantissa), back in
    float32: the nearest precision below the bfloat16 that the
    configuration states."""
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.sql = spec["queries"][QUERY]["sql"]
        devs = jax.devices()
        if len(devs) == 4:
            # the deployment: the session derives the 2x2 mesh itself
            self.session = MatrelSession()
        else:
            side = 2 if len(devs) > 4 else 1
            self.session = MatrelSession(mesh=mesh_lib.make_mesh(
                (side, side), devices=devs[:side * side]))
        mesh = self.session.mesh
        self.mesh = mesh
        n = spec["tables"]["A"][0]
        if scale < 1.0:     # rehearsal only
            n = max(512, int(round(n * scale / 512)) * 512)
        self.n = n
        x, y = mesh.axis_names
        sharded = NamedSharding(mesh, P(x, y))
        names = sorted(spec["tables"])

        @jax.jit
        def generate(key):
            # symmetric about zero (the file's ``assumed`` says why)
            return tuple(jax.lax.with_sharding_constraint(
                jax.random.uniform(jax.random.fold_in(key, i), (n, n),
                                   jnp.bfloat16, -1.0, 1.0), sharded)
                for i in range(len(names)))

        self.arrays = dict(zip(names, generate(device_key(seed))))
        for name, arr in self.arrays.items():
            self.session.register(name, BlockMatrix.from_array(
                arr, (n, n), mesh, P(x, y),
                block_size=self.session.config.block_size))
        k = min(int(spec["check_rows"]), n)
        self.rows = np.sort(np.random.default_rng(seed_words(seed))
                            .choice(n, size=k, replace=False))
        rows = jnp.asarray(self.rows)
        self._take = jax.jit(lambda m: m[rows, :].astype(jnp.float32))
        print("setup deployment "
              f"mesh={'x'.join(str(s) for s in mesh.devices.shape)} "
              f"n={n} tables={len(names)} "
              f"bytes_a_chip={len(names) * n * n * 2 // mesh.size}",
              flush=True)

    # -- the timed path ------------------------------------------------------

    def run(self, query, span):
        with span("parse"):
            expr = self.session.sql(self.sql)
        with span("compute"):
            out = self.session.compute(expr)
        with span("wait"):
            out.data.block_until_ready()
        # the whole product was waited for; what is kept of it for the
        # check is a few rows, so that no 8.6 GB answer outlives its query
        with span("sample"):
            answer = self._answer(np.asarray(self._take(out.data)),
                                  len(out.data.sharding.device_set))
        del out
        return answer

    @staticmethod
    def _answer(rows, devices):
        """One array, as the other configurations' answers are: the
        sampled rows, and a last row that holds the count of devices the
        product lay on."""
        return np.concatenate(
            [rows, np.full((1, rows.shape[1]), devices, rows.dtype)])

    def program_controls(self, query):
        """The program has no lower-precision path of its own for
        bfloat16 tables: the precision tiers take float32 and integer
        tables only, ``matmul_precision`` does not reach a bfloat16
        product (one MXU pass either way), and the panelled product never
        sums rounded partial products."""
        return []

    def notes(self, query):
        meta = self.session.compile(self.session.sql(self.sql)).meta
        return {k: meta.get(k) for k in (
            "optimize_ms", "trace_ms", "executors", "mesh",
            "hbm_plan_bytes", "products")}

    def shapes(self, query):
        """What counts/dense_chain.py takes."""
        return {"n": self.n, "itemsize": 2, "precision": "default"}

    # -- the plain reference, after the window -------------------------------

    def reference(self, query, rnd=_identity):
        """``R[rows, :] = (A[rows, :] . B) . C`` with nothing rounded in
        between: each product in float32 at ``precision="highest"``
        (bfloat16 operands are exact in float32, and so is each of their
        products; the sum over 65536 of them is float32's). B and C stay
        sharded where they lie and the 16 rows go to them, so the
        compiler needs no block of a table beside the table (compiled for
        the 2x2 mesh at 65536: no temporary for the identity, 1 GiB a
        chip for the float8 rounding). ``rnd`` is applied to what every
        product reads: the identity for the reference, float8 rounding
        for the control."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def times(left, m):
            return jnp.dot(rnd(left), rnd(m.astype(jnp.float32)),
                           precision="highest",
                           preferred_element_type=jnp.float32)

        t = times(self._take(self.arrays["A"]), self.arrays["B"])
        return np.asarray(times(t, self.arrays["C"]), np.float64)

    def control(self, query):
        """The reference in the program's place, its operands rounded to
        float8."""
        return self._answer(self.reference(query, rnd=_float8),
                            self.mesh.size)

    def compare(self, query, answer, want):
        return [(f"{query}.max_rel_err", rel_err(answer[:-1], want),
                 float(self.spec["queries"][query]["limit"])),
                (f"{query}.devices_short",
                 float(np.max(np.abs(self.mesh.size - answer[-1]))), 0)]
