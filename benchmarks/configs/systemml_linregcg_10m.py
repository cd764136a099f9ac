"""Deployment ``systemml_linregcg_10m``: Apache SystemML's
``LinearRegCG.dml`` (conjugate gradient on the normal equations,
``icpt=0``) over one chip's quarter of the dense 10M x 1K table of
SystemML's scenario L, through ``session.sql`` + ``session.compute`` on
one chip. The script's loop, which is the whole algorithm, one SQL
statement a line, every scalar a registered 1 x 1 table so that no value
enters a statement's text:

    r = -(t(X) * y);  p = -r;  rr = sum(r^2);  target = rr * tol^2
    while i < k and rr > target:
        q    = t(X) * (X * p) + p * lam         # SystemML's fused mmchain
        a    = rr / (t(p) * q)
        beta = beta + p * a
        r    = r + q * a
        rr2  = t(r) * r                         # 4 bytes read back
        p    = p * (rr2 / rr) - r;  rr = rr2

``X`` and ``y`` are registered once; ``p``, ``r``, ``beta`` and the
scalars are new arrays every round, re-registered, and a round after the
first rebinds the compiled programs through plan templates
(``MatrelConfig(cse_enable=True)``). ``t(X) * (X * p)`` is the program's
``mmchain`` node: one pass over X (``last_plan()["mmchain"]``
``one_read``), which the ``Deployment`` asks for at a toy size before it
makes any data (:func:`can_serve`).

The generator is this file's own copy of ``matrel_linreg_10m``'s (a
configuration file that is there is neither edited nor imported): the
same seed gives the same tables. The plain reference knows nothing of
the program: the same loop line for line with its vectors and scalars in
float64 on the host, the two products of a round panel by panel (8,192
rows) in ``jax.numpy`` float32 at ``precision="highest"`` on the device,
the panels' partial sums added in float64 on the host. Its control
rounds every panel of X to bfloat16 as it is read."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import device_key, rel_err

QUERY = "beta_cg"
NAME = "systemml_linregcg_10m"
REHEARSAL_PANEL = 1024


def _identity(x):
    return x


def _bf16(x):
    """x rounded to bfloat16, back in float32: what one MXU pass sees of
    a float32 operand."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


class Fit(tuple):
    """A query's answer: (beta on the host, the rounds it took). Times
    a scalar the coefficients are scaled, as an array answer would be
    (the harness's own test of a broken timed path multiplies an answer
    by 1.001)."""

    def __mul__(self, factor):
        beta, rounds = self
        return Fit((beta * factor, rounds))


def can_serve(interpret=False):
    """Whether this program knows the fused chain, asked at a toy size
    (256 x 16) through a throw-away session: ``last_plan()`` names an
    ``mmchain`` for ``t(X) * (X * p)``. A program without the node (a
    parent commit) answers the chain as two products that read X twice,
    which the deployment's guarantees rule out, and says so here, in
    seconds, before any data is made."""
    import jax
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession

    if not hasattr(MatrelSession, "last_plan"):
        return False, "no MatrelSession.last_plan"
    rng = np.random.default_rng(0)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    s = MatrelSession(mesh=mesh, config=MatrelConfig(
        cse_enable=True, pallas_interpret=interpret))
    for name, shape in (("X", (256, 16)), ("p", (16, 1))):
        s.register(name, BlockMatrix.from_numpy(
            rng.uniform(-1, 1, shape).astype(np.float32), mesh=mesh))
    try:
        s.compute(s.sql("t(X) * (X * p)"))
    except Exception as ex:     # whatever it cannot parse, plan or run
        return False, f"{type(ex).__name__}: {ex}"
    said = s.last_plan()
    return bool(said.get("mmchain")), said


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from matrel_tpu.config import MatrelConfig

        ok, said = can_serve(interpret)
        if not ok:
            raise RuntimeError(
                f"{NAME}: this program cannot serve the deployment: "
                "t(X) * (X * p) at 256 x 16 was not planned as a fused "
                f"chain (the program said: {said}); at 2,555,904 x 1000 "
                "every round would read the 10 GB table twice")
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.session import MatrelSession

        self.spec = spec
        self.interpret = interpret
        self.sql = dict(spec["queries"][QUERY]["sql"])
        n, k = spec["tables"]["X"]
        panel = int(spec["panel_rows"])
        if scale < 1.0:     # rehearsal only: rows are cut, k never
            panel = REHEARSAL_PANEL
            n = panel * max(16, int(round(n * scale / panel)))
        if n % panel:
            raise ValueError(f"{n} rows are no whole number of panels of "
                             f"{panel}")
        self.n, self.k = n, k
        cg = spec["cg"]
        self.reg, self.tol = float(cg["reg"]), float(cg["tol"])
        self.max_rounds = int(cg["maxi"]) or k
        sigma = float(spec["noise_sigma"])
        # the deployment is one chip; a rehearsal on a host with several
        # CPU devices takes the first
        self._mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        self._config = MatrelConfig(cse_enable=True,
                                    pallas_interpret=interpret)
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
        block = self._config.block_size
        self._tables_of = {
            name: BlockMatrix.from_array(
                arr, tuple(arr.shape), self._mesh, P(None, None),
                block_size=block)
            for name, arr in self.arrays.items()}
        # the loop's constants, 1 x 1 and k x 1 tables like its variables
        for name, value in (("lam", [[self.reg]]), ("neg", [[-1.0]]),
                            ("beta0", np.zeros((k, 1)))):
            self._tables_of[name] = BlockMatrix.from_numpy(
                np.asarray(value, np.float32), mesh=self._mesh)
        self.session = self._session(self._config)
        self._lower = {}

        ref_panel = min(int(spec["reference_panel_rows"]), panel)
        self.ref_panels = n // ref_panel

        def rhs(x, y, i, rnd):
            at = (i * ref_panel, 0)
            xp = rnd(jax.lax.dynamic_slice(x, at, (ref_panel, k)))
            yp = jax.lax.dynamic_slice(y, at, (ref_panel, 1))
            return jnp.dot(xp.T, yp, precision="highest")

        def chain(x, p, i, rnd):
            xp = rnd(jax.lax.dynamic_slice(x, (i * ref_panel, 0),
                                           (ref_panel, k)))
            return jnp.dot(xp.T, jnp.dot(xp, p, precision="highest"),
                           precision="highest")

        self._rhs = jax.jit(rhs, static_argnums=3)
        self._chain = jax.jit(chain, static_argnums=3)
        self.calls = 0
        self.rounds = 0                 # of the newest query
        self.statements = 0             # session.compute calls of it
        self.facts = {}                 # what the program said of a chain
        self.chains_not_fused = 0
        self.misses_after_first = 0     # plan lookups that compiled
        print("setup deployment "
              f"rows={n} k={k} panels={n // panel} "
              + " ".join(f"{name}_bytes_on_device="
                         f"{arr.on_device_size_in_bytes()}"
                         for name, arr in self.arrays.items()), flush=True)

    def _session(self, config):
        from matrel_tpu.session import MatrelSession
        s = MatrelSession(mesh=self._mesh, config=config)
        for name, table in self._tables_of.items():
            s.register(name, table)
        return s

    # -- the timed path ------------------------------------------------------

    def _step(self, session, span, line, into=None, chain=False):
        """One line of the loop: one ``session.sql`` + ``compute``, the
        result registered under ``into``; what the program said of the
        plan it answered with (``last_plan``) noted when the session is
        the deployment's own."""
        with span("parse"):
            expr = session.sql(self.sql[line])
        with span("compute"):
            out = session.compute(expr)
        if session is self.session:
            said = session.last_plan()
            if self.calls and said.get("hit") is False:
                self.misses_after_first += 1
            if chain:
                recs = said.get("mmchain") or [{}]
                self.chains_not_fused += not all(
                    r.get("one_read") for r in recs)
                self.facts = dict(recs[0], hbm_plan_bytes=said.get(
                    "hbm_plan_bytes"))
        if into:
            session.register(into, out)
        return out

    def _scalar(self, table, span):
        with span("fetch"):
            return float(table.to_numpy()[0, 0])

    def run(self, query, span, session=None):
        """A whole solve from ``beta = 0``. ``session`` is the
        deployment's own in a run; ``program_controls`` passes one of a
        lower precision."""
        if query != QUERY:
            raise KeyError(query)
        s = session or self.session
        steps = 3
        self._step(s, span, "p0", into="p")
        self._step(s, span, "r0", into="r")
        rr = self._scalar(self._step(s, span, "rr", into="rr"), span)
        s.register("beta", self._tables_of["beta0"])
        target = rr * self.tol ** 2
        rounds = 0
        while rounds < self.max_rounds and rr > target:
            self._step(s, span, "q", into="q", chain=True)
            self._step(s, span, "a", into="a")
            self._step(s, span, "beta", into="beta")
            self._step(s, span, "r", into="r")
            rr_new = self._step(s, span, "rr", into="rr2")
            self._step(s, span, "p", into="p")
            s.register("rr", rr_new)
            rr = self._scalar(rr_new, span)
            rounds += 1
            steps += 6
        with span("fetch"):
            beta = s.table("beta").to_numpy()
        if s is self.session:
            if rounds and self.calls == 0 and self.chains_not_fused:
                raise RuntimeError(
                    f"{NAME}: the first query's chains were not answered "
                    f"in one read of X (the program said: {self.facts}); "
                    "this program cannot serve the deployment")
            self.calls += 1
            self.rounds, self.statements = rounds, steps
        return Fit((beta, rounds))

    def program_controls(self, query):
        """(knob, answer) for each lower ``matmul_precision`` the program
        has, switched on in the program's place: the same two tables (no
        copy) in a session of that configuration, whose chains the
        planner un-fuses (``why_not`` matmul_precision)."""
        import contextlib
        import dataclasses
        out = []
        for precision in ("high", "default"):
            if precision not in self._lower:
                self._lower[precision] = self._session(dataclasses.replace(
                    self._config, matmul_precision=precision))
            out.append((f"matmul_precision={precision}", self.run(
                query, lambda name: contextlib.nullcontext(),
                session=self._lower[precision])))
        return out

    def notes(self, query):
        return {"rows": self.n, "k": self.k, "rounds": self.rounds,
                "statements": self.statements, "chain": self.facts}

    def shapes(self, query):
        """What counts/linregcg.py takes (the span readers count a
        query's statements by ``rounds``)."""
        return {"n": self.n, "k": self.k, "itemsize": 4,
                "rounds": self.rounds, "precision": "highest"}

    # -- the plain reference, after the window -------------------------------

    def _panels(self, fn, *operands):
        """The float64 sum over the reference's panels of ``fn(X,
        *operands, i, rnd)``: each a float32 product at
        ``precision="highest"`` on the device over 8,192 rows (the
        float32 accumulator of a longer dot loses more than the limit
        has room for, PR 31), added in float64 on the host."""
        total = np.zeros((self.k, 1), np.float64)
        for i in range(self.ref_panels):
            total += np.asarray(fn(self.arrays["X"], *operands, i),
                                np.float64)
        return total

    def reference(self, query, rnd=_identity):
        """(beta, rounds): LinearRegCG.dml's loop line for line, vectors
        and scalars float64 on the host. ``rnd`` is applied to every
        panel of X as it is read: the identity for the reference,
        bfloat16 rounding for the control."""
        import jax.numpy as jnp
        r = -self._panels(lambda x, y, i: self._rhs(x, y, i, rnd),
                          self.arrays["y"])
        p = -r
        rr = float(np.sum(r * r))
        target = rr * self.tol ** 2
        beta = np.zeros_like(r)
        rounds = 0
        while rounds < self.max_rounds and rr > target:
            p32 = jnp.asarray(p, jnp.float32)
            q = self._panels(lambda x, v, i: self._chain(x, v, i, rnd),
                             p32) + self.reg * p
            a = rr / float(np.sum(p * q))
            beta = beta + a * p
            r = r + a * q
            rr_new = float(np.sum(r * r))
            p = -r + (rr_new / rr) * p
            rr = rr_new
            rounds += 1
        return beta, rounds

    def control(self, query):
        """The reference in the program's place, X rounded to bfloat16."""
        return Fit(self.reference(query, rnd=_bf16))

    def compare(self, query, answer, want):
        """Every coefficient within the limit of the reference's, the
        round count equal; and the executor: every chain of every round
        of the deployment's own queries answered in one read of X, no
        plan lookup after the first query that compiled."""
        beta, rounds = answer
        ref, ref_rounds = want
        return [
            (f"{query}.max_rel_err", rel_err(beta, ref),
             float(self.spec["queries"][query]["limit"])),
            (f"{query}.rounds_off", abs(int(rounds) - ref_rounds), 0),
            (f"{query}.chains_not_fused", self.chains_not_fused, 0),
            (f"{query}.compiles_after_first_query",
             self.misses_after_first, 0)]
