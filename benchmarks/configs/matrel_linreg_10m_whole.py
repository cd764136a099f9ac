"""Deployment ``matrel_linreg_10m_whole``: the WHOLE 10M x 1k regression
table and its column of responses on one host of four chips, cut by rows
over all four (``P(('x', 'y'), None)``: each chip holds its own quarter,
whole rows), made from the seed on the devices, every device filling its
own rows panel by panel; handed to a default-config MatrelSession on the
2x2 mesh that the session derives itself (a 1x1 mesh where the process
has fewer than four devices: a CPU rehearsal); the query ``inv(t(X) * X)
* t(X) * y`` as upstream writes it, through ``session.sql`` +
``session.compute`` + ``to_numpy``; the plain reference by partial sums
of short panels where the rows lie, its control with X rounded to
bfloat16, and the program's own lower ``matmul_precision`` settings as
controls. The generator and the reference are this file's own: plain
``jax`` and ``numpy``, nothing of the program."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import device_key, rel_err

QUERY = "theta"
REHEARSAL_PANEL = 1024

#: Backend compiles this process has made, as jax's own monitoring says
#: them (a module-level count: a listener that held a Deployment would
#: keep its 10 GB a device alive after the deployment is dropped).
_COMPILES = [0]


def _heard(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


def _identity(x):
    return x


def _bf16(x):
    """x rounded to bfloat16, back in float32: what one MXU pass sees of
    a float32 operand."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def generate(mesh, n, k, panel, sigma, key):
    """(X, y) cut by rows over all the mesh's devices, every device
    filling the rows it holds a panel at a time, in place (a whole-table
    ``uniform`` would hold its 10 GB of bits beside its 10 GB of floats):
    X uniform [-1, 1), y = X . theta_star + sigma * normal, theta_star
    standard normal, all from ``key``. One jitted, sharded call; nothing
    passes through the host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    rows = n // mesh.size
    if n % mesh.size or rows % panel:
        raise ValueError(f"{n} rows are no whole number of panels of "
                         f"{panel} on each of {mesh.size} devices")
    panels = rows // panel
    by_rows = NamedSharding(mesh, P(axes, None))

    def own_rows(key):
        """One device's rows: the panels' keys count on from the
        device's place on the mesh, so no two devices draw the same."""
        first = jnp.int32(0)
        for axis in axes:
            first = first * mesh.shape[axis] + jax.lax.axis_index(axis)
        theta_star = jax.random.normal(jax.random.fold_in(key, 0),
                                       (k, 1), jnp.float32)

        def fill(i, tables):
            x, y = tables
            kx, ke = jax.random.split(
                jax.random.fold_in(key, 1 + first * panels + i))
            xp = jax.random.uniform(kx, (panel, k), jnp.float32, -1.0, 1.0)
            yp = jnp.dot(xp, theta_star, precision="highest") \
                + sigma * jax.random.normal(ke, (panel, 1), jnp.float32)
            at = (i * panel, 0)
            return (jax.lax.dynamic_update_slice(x, xp, at),
                    jax.lax.dynamic_update_slice(y, yp, at))

        return jax.lax.fori_loop(
            0, panels, fill,
            (jnp.zeros((rows, k), jnp.float32),
             jnp.zeros((rows, 1), jnp.float32)))

    fill_all = jax.shard_map(own_rows, mesh=mesh, in_specs=P(),
                             out_specs=(P(axes, None), P(axes, None)),
                             check_vma=False)
    return jax.jit(fill_all, out_shardings=(by_rows, by_rows))(key)


class PanelSums:
    """``t(X) * X`` and ``t(X) * y`` of a row-partitioned table, a panel
    of ``ref_panel`` rows at a time in float32 at ``precision="highest"``
    on the device that holds the rows (a panel's sum runs over 8,192
    rows: the float32 accumulator of a longer one loses more than the
    limit has room for), the partial sums brought to the host and added
    in float64."""

    def __init__(self, k, ref_panel):
        import jax
        import jax.numpy as jnp

        self.k, self.ref_panel = k, ref_panel

        def partial_sums(x, y, start, rows, rnd):
            xp = rnd(jax.lax.dynamic_slice(x, (start, 0), (rows, k)))
            yp = jax.lax.dynamic_slice(y, (start, 0), (rows, 1))
            return (jnp.dot(xp.T, xp, precision="highest"),
                    jnp.dot(xp.T, yp, precision="highest"))

        self._partial = jax.jit(partial_sums, static_argnums=(3, 4))

    def solve(self, x, y, rnd=_identity):
        """theta in float64: the shards' partial sums (one panel of each
        shard in flight at a time, so the devices work side by side),
        then ``numpy.linalg.solve``."""
        xs = [s.data for s in x.addressable_shards]
        ys = [s.data for s in y.addressable_shards]
        rows = xs[0].shape[0]
        gram = np.zeros((self.k, self.k), np.float64)
        rhs = np.zeros((self.k, 1), np.float64)
        for start in range(0, rows, self.ref_panel):
            size = min(self.ref_panel, rows - start)
            parts = [self._partial(xd, yd, start, size, rnd)
                     for xd, yd in zip(xs, ys)]
            for g, r in parts:
                gram += np.asarray(g, np.float64)
                rhs += np.asarray(r, np.float64)
        return np.linalg.solve(gram, rhs)


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        from jax.sharding import PartitionSpec as P
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.sql = spec["queries"][QUERY]["sql"]
        self.whole = scale >= 1.0
        devs = jax.devices()
        if len(devs) == 4:
            # the deployment: the session derives the 2x2 mesh itself
            self.session = MatrelSession()
        else:
            side = 2 if len(devs) > 4 else 1
            self.session = MatrelSession(mesh=mesh_lib.make_mesh(
                (side, side), devices=devs[:side * side]))
        mesh = self.mesh = self.session.mesh
        n, k = spec["tables"]["X"]
        panel = int(spec["panel_rows"]) // int(spec["exact"]["devices"])
        if not self.whole:     # rehearsal only: rows are cut, k never
            panel = REHEARSAL_PANEL
            n = panel * mesh.size * max(
                2, int(round(n * scale / (panel * mesh.size))))
        self.n, self.k = n, k
        x, y = generate(mesh, n, k, panel, float(spec["noise_sigma"]),
                        device_key(seed))
        self.arrays = {"X": x, "y": y}
        by_rows = P(tuple(mesh.axis_names), None)
        self._tables_of = {
            name: BlockMatrix.from_array(
                arr, tuple(arr.shape), mesh, by_rows,
                block_size=self.session.config.block_size)
            for name, arr in self.arrays.items()}
        for name, table in self._tables_of.items():
            self.session.register(name, table)
        self._sums = PanelSums(
            k, min(int(spec["reference_panel_rows"]), panel))
        self._lower = {}
        self._runs = self._late_compiles = 0
        if _COMPILES[0] == 0:       # once a process: a count starts at 1
            _COMPILES[0] = 1
            jax.monitoring.register_event_duration_secs_listener(_heard)
        print("setup deployment "
              f"mesh={'x'.join(str(s) for s in mesh.devices.shape)} "
              f"rows={n} k={k} rows_a_device={n // mesh.size} "
              + " ".join(
                  f"{name}_bytes_a_device="
                  + ",".join(str(s.data.on_device_size_in_bytes())
                             for s in arr.addressable_shards)
                  for name, arr in self.arrays.items()), flush=True)

    # -- the timed path ------------------------------------------------------

    def run(self, query, span, session=None):
        """``session`` is the deployment's own in a run;
        ``program_controls`` passes one of a lower precision. A compile
        heard during any query of the deployment's own session but its
        first is one after the warm-up."""
        own = session is None
        session = session or self.session
        before = _COMPILES[0]
        with span("parse"):
            expr = session.sql(self.sql)
        with span("compute"):
            out = session.compute(expr)
        with span("fetch"):
            answer = out.to_numpy()
        if own:
            if self._runs:
                self._late_compiles += _COMPILES[0] - before
            self._runs += 1
        return answer

    def program_controls(self, query):
        """(knob, answer) for each lower ``matmul_precision`` the program
        has, switched on in the program's place: the same two tables (no
        copy) in a session of that configuration. A setting under which
        the program refuses the plan by name (``high`` multiplies a Gram
        through a ranked mesh strategy, which would re-lay the shards)
        gives no reading, and a line that says so."""
        import contextlib
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel.planner import PlanMemoryError
        from matrel_tpu.session import MatrelSession
        out = []
        for precision in ("high", "default"):
            if precision not in self._lower:
                s = MatrelSession(mesh=self.mesh, config=MatrelConfig(
                    matmul_precision=precision))
                for name, table in self._tables_of.items():
                    s.register(name, table)
                self._lower[precision] = s
            try:
                got = self.run(query, lambda name: contextlib.nullcontext(),
                               session=self._lower[precision])
            except PlanMemoryError as ex:
                print(f"control matmul_precision={precision}: no reading, "
                      f"the program refused the plan: {str(ex)[:300]}",
                      flush=True)
                continue
            out.append((f"matmul_precision={precision}", got))
        return out

    def notes(self, query):
        meta = self.session.compile(self.session.sql(self.sql)).meta
        return {k: meta.get(k) for k in (
            "optimize_ms", "trace_ms", "rule_hits", "executors", "mesh",
            "hbm_plan_bytes", "products")}

    def shapes(self, query):
        """What counts/linreg.py takes: the whole table's rows."""
        return {"n": self.n, "k": self.k, "itemsize": 4,
                "precision": "highest"}

    # -- the plain reference, after the window -------------------------------

    def reference(self, query, rnd=_identity):
        """``PanelSums.solve`` over the four shards where they lie.
        ``rnd`` is applied to every panel of X as it is read: the
        identity for the reference, bfloat16 rounding for the control."""
        return self._sums.solve(self.arrays["X"], self.arrays["y"], rnd)

    def control(self, query):
        """The reference in the program's place, X rounded to bfloat16."""
        return self.reference(query, rnd=_bf16)

    def _exact(self):
        """The numbers that are exact, each as its distance from what the
        configuration states (0 is right): read from the arrays as they
        lie and from ``last_plan()``. What the panelled lowering stamps
        (``gram_tiles``, ``gram_rides``, the devices its all-reduce
        adds over) is read at the deployment's size alone: a rehearsal
        cuts the rows below the program's long contraction."""
        want = self.spec["exact"]
        size = self.mesh.size
        lay_on = min(len(arr.sharding.device_set)
                     for arr in self.arrays.values())
        rows = [s.data.shape[0] for arr in self.arrays.values()
                for s in arr.addressable_shards]
        out = {"devices_short": size - lay_on,
               "rows_a_device_off": max(abs(r - self.n // size)
                                        for r in rows),
               "compiles_after_warm": self._late_compiles}
        if self.whole:
            gram = next((p for p in self.session.last_plan().get(
                "products", ()) if "gram_tiles" in p), {})
            out["devices_short"] = max(
                out["devices_short"],
                want["devices"] - min(lay_on, gram.get("devices", 0)))
            out["rows_a_device_off"] = max(
                out["rows_a_device_off"],
                abs(want["rows_a_device"] - gram.get("rows_a_device", 0)),
                max(abs(r - want["rows_a_device"]) for r in rows))
            out["gram_tiles_off"] = int(
                list(gram.get("gram_tiles", ())) != want["gram_tiles"])
            out["gram_rides_off"] = abs(
                want["gram_rides"] - gram.get("gram_rides", 0))
        return out

    def compare(self, query, answer, want):
        return [(f"{query}.max_rel_err", rel_err(answer, want),
                 float(self.spec["queries"][query]["limit"]))] \
            + [(f"{query}.{label}", float(value), 0)
               for label, value in sorted(self._exact().items())]
