"""Deployment ``fivm_linreg_window_10m``: a regression kept fresh over a
sliding window of one chip's quarter of the 10M x 1k table (F-IVM's and
LINVIEW's maintained views over MatRel's BASELINE config 3 table). The
table is a ring of slots of ``batch_rows`` rows; one TICK is

    session.register_delta("X", (row_ids, N), kind="rows")   the oldest slot's rows replaced
    session.register_delta("y", (row_ids, n), kind="rows")   ... in place, the views patched
    theta = session.compute(session.sql("inv(t(X) * X) * t(X) * y")).to_numpy()
    xty   = session.compute(session.sql("t(X) * y")).to_numpy()

with ``N``, ``n`` the next of a pool of host batches drawn from the seed
before the window. The two views ``t(X) * X`` and ``t(X) * y`` are asked
once at set-up as statements and live in the session's result cache
(``MatrelConfig(result_cache_max_bytes=...)``, the configuration's one
setting); a tick's reads are answered from them. The program is asked
at a toy size whether it can do that before any data is made
(:func:`can_serve`).

The generator is this file's own copy of ``matrel_linreg_10m``'s (a
configuration file that is there is neither edited nor imported): the
same seed gives the same tables. The plain reference knows nothing of
the program: what slot ``s`` holds after ``T`` ticks follows from the
ring alone (the generator's rows, or the pool batch the write sequence
last put there), every slot's ``t(S) * S`` and ``t(S) * y_S`` is one
``jax.numpy`` float32 product at ``precision="highest"`` on the device,
the slots' sums are added in float64 on the host, theta is a float64
solve. ``reference`` hands ``compare`` the function that builds the
reference of the kept answer's OWN tick (an answer carries its tick).
Its control rounds every slot of X to bfloat16 as it is read; the
program's controls are the views of one tick earlier and
``matmul_precision`` default."""

from __future__ import annotations

import collections
import contextlib

import numpy as np

from benchmarks.reference import device_key, rel_err, seed_words

QUERY = "tick"
NAME = "fivm_linreg_window_10m"
REHEARSAL_BATCH = 1024
REHEARSAL_POOL = 7      # no divisor of a rehearsal's 16 slots: a slot's next batch is another
PROBE = (4096, 64, 256)         # rows, columns, batch of can_serve


def _identity(x):
    return x


def _bf16(x):
    """x rounded to bfloat16, back in float32: what one MXU pass sees of
    a float32 operand."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _no_span(name):
    return contextlib.nullcontext()


class Tick(tuple):
    """A query's answer: (theta, ``t(X) * y``, the ticks the table had
    taken when they were read). Times a scalar both vectors are scaled,
    as an array answer would be (the harness's own test of a broken
    timed path multiplies an answer by 1.001)."""

    def __mul__(self, factor):
        theta, xty, tick = self
        return Tick((theta * factor, xty * factor, tick))


def can_serve(interpret=False):
    """Whether this program keeps the two views under a rows delta,
    asked at a toy size (4,096 x 64, a batch of 256) through a
    throw-away session: ``register_delta(kind="rows")`` is taken, says
    it ran in place and patched, and the theta statement after it was
    answered from both views with no table among its leaves
    (``last_plan()``). A program without the kind (a parent commit)
    raises at the first call; one that kills and recomputes would pass
    over the 10 GB table every tick. Either says so here, in seconds,
    before any data is made."""
    import jax
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession

    n, k, c = PROBE
    rng = np.random.default_rng(0)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    s = MatrelSession(mesh=mesh, config=MatrelConfig(
        result_cache_max_bytes=1 << 26, pallas_interpret=interpret))
    for name, cols in (("X", k), ("y", 1)):
        s.register(name, BlockMatrix.from_numpy(
            rng.uniform(-1, 1, (n, cols)).astype(np.float32), mesh=mesh))
    said = {}
    try:
        for text in ("t(X) * X", "t(X) * y"):
            s.compute(s.sql(text))
        ids = np.arange(c)
        for name, cols in (("X", k), ("y", 1)):
            said[name] = s.register_delta(
                name, (ids, rng.uniform(-1, 1, (c, cols))
                       .astype(np.float32)), kind="rows")
        s.compute(s.sql("inv(t(X) * X) * t(X) * y"))
        said["theta"] = {key: s.last_plan().get(key) for key in
                         ("views_hit", "table_pass")}
    except Exception as ex:     # whatever it cannot take, plan or run
        return False, f"{type(ex).__name__}: {ex}"
    ok = (all(said[name].get("in_place") and said[name].get("patched")
              and not said[name].get("table_passes")
              for name in ("X", "y"))
          and said["theta"] == {"views_hit": 2, "table_pass": False})
    return ok, said


class Deployment:
    def __init__(self, spec, seed, queries, scale=1.0, interpret=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from matrel_tpu.config import MatrelConfig

        ok, said = can_serve(interpret)
        if not ok:
            raise RuntimeError(
                f"{NAME}: this program cannot serve the deployment: at "
                f"{PROBE[0]} x {PROBE[1]} a batch of {PROBE[2]} rows handed "
                "to register_delta(kind='rows') was not written in place "
                "with both views patched and the theta statement answered "
                f"from them (the program said: {said}); at 2,555,904 x "
                "1000 every tick would copy or pass over the 10 GB table")
        from matrel_tpu.core import mesh as mesh_lib
        from matrel_tpu.core.blockmatrix import BlockMatrix

        self.spec = spec
        self.sql = dict(spec["queries"][QUERY]["sql"])
        n, k = spec["tables"]["X"]
        window = spec["window"]
        batch, slots = int(window["batch_rows"]), int(window["slots"])
        pool = int(window["pool_batches"])
        panel = int(spec["panel_rows"])
        if scale < 1.0:     # rehearsal only: rows are cut, k never
            batch = panel = REHEARSAL_BATCH
            slots = max(16, int(round(n * scale / batch)))
            pool = min(pool, REHEARSAL_POOL)
            n = batch * slots
        if n != batch * slots or n % panel or panel % batch:
            raise ValueError(f"{n} rows are no ring of {slots} slots of "
                             f"{batch} in panels of {panel}")
        self.n, self.k = n, k
        self.batch, self.slots, self.pool = batch, slots, pool
        sigma = float(spec["noise_sigma"])
        # the deployment is one chip; a rehearsal on a host with several
        # CPU devices takes the first
        self._mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
        self._config = MatrelConfig(
            result_cache_max_bytes=int(spec["result_cache_max_bytes"]),
            pallas_interpret=interpret)
        whole = NamedSharding(self._mesh, P(None, None))
        key = device_key(seed)

        def panel_of(key, theta_star, i):
            kx, ke = jax.random.split(jax.random.fold_in(key, 1 + i))
            xp = jax.random.uniform(kx, (panel, k), jnp.float32, -1.0, 1.0)
            yp = jnp.dot(xp, theta_star, precision="highest") \
                + sigma * jax.random.normal(ke, (panel, 1), jnp.float32)
            return xp, yp

        def generate(key):
            """Both tables, filled in place a panel at a time: a
            whole-table ``uniform`` would hold its 10 GB of bits beside
            its 10 GB of floats."""
            theta_star = jax.random.normal(jax.random.fold_in(key, 0),
                                           (k, 1), jnp.float32)

            def fill(i, tables):
                x, y = tables
                xp, yp = panel_of(key, theta_star, i)
                at = (i * panel, 0)
                return (jax.lax.dynamic_update_slice(x, xp, at),
                        jax.lax.dynamic_update_slice(y, yp, at))

            return jax.lax.fori_loop(
                0, n // panel, fill,
                (jnp.zeros((n, k), jnp.float32),
                 jnp.zeros((n, 1), jnp.float32)))

        x, y = jax.jit(generate, out_shardings=(whole, whole))(key)
        block = self._config.block_size
        # the session overwrites these two in place: the BlockMatrix
        # stays, its ``data`` is the newest array (``arrays``)
        self._tables_of = {
            name: BlockMatrix.from_array(
                arr, tuple(arr.shape), self._mesh, P(None, None),
                block_size=block)
            for name, arr in (("X", x), ("y", y))}
        print("setup deployment "
              f"rows={n} k={k} slots={slots} batch_rows={batch} "
              f"pool_batches={pool} "
              + " ".join(f"{name}_bytes_on_device="
                         f"{t.data.on_device_size_in_bytes()}"
                         for name, t in self._tables_of.items()), flush=True)

        # the pool: host batches from the same distribution and the same
        # theta_star, drawn from the seed before the window and cycled
        theta_star = np.asarray(jax.random.normal(
            jax.random.fold_in(key, 0), (k, 1), jnp.float32))
        rng = np.random.default_rng([*seed_words(seed), 0x706F6F6C])
        self.pool_x, self.pool_y = [], []
        for _ in range(pool):
            xb = rng.random((batch, k), np.float32)
            xb *= 2.0
            xb -= 1.0
            self.pool_x.append(xb)
            self.pool_y.append(
                xb @ theta_star + sigma * rng.standard_normal(
                    (batch, 1), np.float32))

        # the reference's programs: one slot's two products, a
        # generator slot made again from the seed, a slot's fingerprint
        def products(xs, ys, rnd):
            xs = rnd(xs)
            return (jnp.dot(xs.T, xs, precision="highest"),
                    jnp.dot(xs.T, ys, precision="highest"))

        def generated(key, slot):
            theta_star = jax.random.normal(jax.random.fold_in(key, 0),
                                           (k, 1), jnp.float32)
            xp, yp = panel_of(key, theta_star, slot * batch // panel)
            at = (slot * batch % panel, 0)
            return (jax.lax.dynamic_slice(xp, at, (batch, k)),
                    jax.lax.dynamic_slice(yp, at, (batch, 1)))

        def words(table, slot):
            """A slot's bits, added up as unsigned words (wrapping) and
            as words times their place: equal for equal bits."""
            rows = jax.lax.dynamic_slice_in_dim(table, slot * batch, batch)
            bits = jax.lax.bitcast_convert_type(rows, jnp.uint32)
            place = (jnp.arange(bits.size, dtype=jnp.uint32)
                     .reshape(bits.shape) | jnp.uint32(1))
            return jnp.stack([jnp.sum(bits), jnp.sum(bits * place)])

        def differ(table, slot, rows):
            mine = jax.lax.dynamic_slice_in_dim(table, slot * batch, batch)
            return jnp.any(jax.lax.bitcast_convert_type(mine, jnp.uint32)
                           != jax.lax.bitcast_convert_type(rows, jnp.uint32))

        self._key = key
        self._products = jax.jit(products, static_argnums=2)
        self._generated = jax.jit(generated)
        self._words = jax.jit(words)
        self._differ = jax.jit(differ)
        # what set-up put into every slot, as fingerprints: a slot the
        # write sequence never reaches has to read the same afterwards
        self._made = {name: [np.asarray(self._words(t.data, s))
                             for s in range(slots)]
                      for name, t in self._tables_of.items()}
        self._sums = {}                 # (pool batch, rounding) -> products
        self._checked = None            # (ticks, slots differing)

        self.ticks = 0                  # ticks the table has taken
        self.facts = {}                 # what the program said of a tick
        self.counts = {"rebases": 0, "table_passes": 0, "unpatched": 0,
                       "reads_over_the_table": 0, "compiles_after_first": 0}
        self.hbm_plan_bytes = 0
        self._warm = False              # the session's first tick is done
        self.session = self._session(self._config)

    @property
    def arrays(self):
        """The two tables as they stand on the device."""
        return {name: t.data for name, t in self._tables_of.items()}

    def _session(self, config):
        """A session over the deployment's two tables with both views
        asked once as statements: they are result-cache entries from
        then on."""
        from matrel_tpu.session import MatrelSession
        s = MatrelSession(mesh=self._mesh, config=config)
        for name, table in self._tables_of.items():
            s.register(name, table)
        for text in self.spec["views"]:
            s.compute(s.sql(text))
        return s

    # -- the timed path ------------------------------------------------------

    def _write(self, session, span):
        """The tick's two writes: the next pool batch over the oldest
        slot. Returns the two summaries."""
        slot, b = self.ticks % self.slots, self.ticks % self.pool
        ids = np.arange(slot * self.batch, (slot + 1) * self.batch)
        with span("delta"):
            said = [session.register_delta(name, (ids, rows), kind="rows")
                    for name, rows in (("X", self.pool_x[b]),
                                       ("y", self.pool_y[b]))]
        self.ticks += 1
        return said

    def _read(self, session, span, text):
        with span("parse"):
            expr = session.sql(text)
        with span("compute"):
            out = session.compute(expr)
        said = session.last_plan()
        with span("fetch"):
            return out.to_numpy(), said

    def run(self, query, span, session=None):
        """One tick. ``session`` is the deployment's own in a run;
        ``program_controls`` passes one of a lower precision."""
        if query != QUERY:
            raise KeyError(query)
        s = session or self.session
        wrote = self._write(s, span)
        theta, said_theta = self._read(s, span, self.sql["theta"])
        xty, said_xty = self._read(s, span, self.sql["xty"])
        if s is self.session:
            c = self.counts
            rebased = sum(w.get("rebased", 0) for w in wrote)
            c["rebases"] += rebased
            c["table_passes"] += sum(w.get("table_passes", 0)
                                     for w in wrote) - rebased
            c["unpatched"] += any(
                w.get("killed", 0) - w.get("no_rule", 0) > 0
                or not w.get("patched") for w in wrote)
            c["reads_over_the_table"] += (
                said_theta.get("table_pass") is not False
                or said_theta.get("views_hit") != 2
                or not said_xty.get("root_hit"))
            if self._warm:
                c["compiles_after_first"] += (
                    said_theta.get("hit") is False
                    or any(w.get("patched", 0) > w.get("reused_plans", 0)
                           + w.get("rebased", 0) for w in wrote))
            self.hbm_plan_bytes = max(
                self.hbm_plan_bytes,
                *(w.get("hbm_plan_bytes") or 0 for w in wrote))
            self._warm = True
            self.facts = {"X": wrote[0], "y": wrote[1],
                          "theta": {key: said_theta.get(key) for key in (
                              "hit", "views_hit", "table_pass",
                              "executors", "hbm_plan_bytes")}}
        return Tick((theta, xty, self.ticks))

    def program_controls(self, query):
        """(knob, answer) for the two programs the guarantees name, each
        in the program's place for one more tick: the views of ONE TICK
        EARLIER (read before the tick's writes, handed over as the
        tick's answer), and the same tables (no copy) in a session at
        ``matmul_precision`` default, its views computed and patched at
        that precision. The second tick's writes went through the other
        session, so the deployment's own is made again after it."""
        import dataclasses
        stale = [self._read(self.session, _no_span, self.sql[name])[0]
                 for name in ("theta", "xty")]
        self.run(query, _no_span)
        out = [("views_of_the_tick_before", Tick((*stale, self.ticks)))]
        low = self._session(dataclasses.replace(
            self._config, matmul_precision="default"))
        out.append(("matmul_precision=default",
                    self.run(query, _no_span, session=low)))
        self.session, self._warm = self._session(self._config), False
        return out

    def notes(self, query):
        return {"rows": self.n, "k": self.k, "slots": self.slots,
                "batch_rows": self.batch, "ticks": self.ticks,
                **self.counts, "said": self.facts}

    def shapes(self, query):
        """What counts/window.py takes."""
        return {"c": self.batch, "k": self.k, "n": self.n, "itemsize": 4,
                "precision": "highest",
                "rebases_a_tick": self.counts["rebases"]
                / max(self.ticks, 1)}

    # -- the plain reference, after the window -------------------------------

    def batch_in(self, slot, ticks):
        """The pool batch that slot ``slot`` holds after ``ticks`` ticks
        of the ring (tick t replaces slot t mod slots by batch t mod
        pool), or None where it still holds the generator's rows."""
        last = ticks - 1 - (ticks - 1 - slot) % self.slots
        return last % self.pool if ticks > slot and last >= 0 else None

    def _slot_sums(self, b, slot, rnd):
        """(t(S) * S, t(S) * y_S) of one slot's content in float64:
        pool batch ``b``, or slot ``slot`` of the generator."""
        import jax.numpy as jnp
        if b is None:
            xs, ys = self._generated(self._key, slot)
            return tuple(np.asarray(a, np.float64)
                         for a in self._products(xs, ys, rnd))
        if (b, rnd) not in self._sums:
            self._sums[b, rnd] = tuple(
                np.asarray(a, np.float64) for a in self._products(
                    jnp.asarray(self.pool_x[b]), jnp.asarray(self.pool_y[b]),
                    rnd))
        return self._sums[b, rnd]

    def reference_at(self, ticks, rnd=_identity):
        """(theta, ``t(X) * y``) of the table as it stands after
        ``ticks`` ticks: every slot's two products in float32 at
        ``precision="highest"`` on the device (a slot is 8,192 rows: the
        float32 accumulator of a longer dot loses more than the limit
        has room for, PR 31), added slot by slot in float64 on the host,
        and a float64 solve. ``rnd`` is applied to every slot of X as it
        is read: the identity for the reference, bfloat16 rounding for
        the control."""
        gram = np.zeros((self.k, self.k), np.float64)
        rhs = np.zeros((self.k, 1), np.float64)
        held = collections.Counter()    # slots holding a pool batch
        for slot in range(self.slots):
            b = self.batch_in(slot, ticks)
            if b is None:
                g, r = self._slot_sums(None, slot, rnd)
                gram += g
                rhs += r
            else:
                held[b] += 1
        for b, times in sorted(held.items()):
            g, r = self._slot_sums(b, None, rnd)
            gram += times * g           # the same float64 terms, added
            rhs += times * r            # once a slot that holds them
        return np.linalg.solve(gram, rhs), rhs

    def reference(self, query):
        """``run.py`` asks once a query and the table moves every tick:
        what it gets is the function ``compare`` builds the reference of
        the kept answer's own tick with."""
        return self.reference_at

    def control(self, query):
        """The reference in the program's place at the table's newest
        tick, every slot of X rounded to bfloat16."""
        return Tick((*self.reference_at(self.ticks, rnd=_bf16), self.ticks))

    def slots_differing(self):
        """How many slots of X and of y on the device differ, in any
        bit, from what the write sequence put there: the pool batch of
        the slot's last write (compared word for word on the device),
        or what set-up made (by its fingerprint)."""
        import jax.numpy as jnp
        if self._checked is None or self._checked[0] != self.ticks:
            bad = 0
            by_batch = {}
            for slot in range(self.slots):
                by_batch.setdefault(self.batch_in(slot, self.ticks),
                                    []).append(slot)
            for b, held in sorted(by_batch.items(),
                                  key=lambda kv: (kv[0] is None, kv[0])):
                for name, pool in (("X", self.pool_x), ("y", self.pool_y)):
                    table = self._tables_of[name].data
                    if b is None:
                        bad += sum(not np.array_equal(
                            np.asarray(self._words(table, s)),
                            self._made[name][s]) for s in held)
                        continue
                    rows = jnp.asarray(pool[b])
                    bad += sum(bool(self._differ(table, s, rows))
                               for s in held)
            self._checked = (self.ticks, bad)
        return self._checked[1]

    def compare(self, query, answer, want):
        """Both vectors of the answer's own tick within the limit of the
        reference at that tick; the writes durable (every slot on the
        device what the write sequence put there); and the executor, of
        the deployment's own ticks: no read and no patch passed over the
        table outside a re-base, every view patched every tick, nothing
        compiled after the first tick, re-bases under 1 tick in 100, the
        reckoned peak under 65% of the device (one table)."""
        theta, xty, tick = answer
        ref_theta, ref_xty = want(tick)
        limit = float(self.spec["queries"][query]["limit"])
        errs = rel_err(theta, ref_theta), rel_err(xty, ref_xty)
        print(f"reference tick={tick} theta_max_rel_err={errs[0]!r} "
              f"xty_max_rel_err={errs[1]!r}", flush=True)
        c = self.counts
        ticks = max(self.ticks, 1)
        import jax
        bytes_limit = (jax.devices()[0].memory_stats() or {}) \
            .get("bytes_limit")
        share = self.hbm_plan_bytes / bytes_limit if bytes_limit else 0.0
        return [
            (f"{query}.theta_max_rel_err", errs[0], limit),
            (f"{query}.xty_max_rel_err", errs[1], limit),
            ("table.slots_differing", self.slots_differing(), 0),
            (f"{query}.table_passes_outside_a_rebase",
             c["table_passes"] + c["reads_over_the_table"], 0),
            (f"{query}.ticks_with_a_view_not_patched", c["unpatched"], 0),
            (f"{query}.compiles_after_first_tick",
             c["compiles_after_first"], 0),
            # under 1 tick in 100 on the deployment's ring; a rehearsal's
            # is shorter, a tick that much more of a view's rows, and
            # the bound grows that much faster
            (f"{query}.rebases_a_tick", c["rebases"] / ticks,
             0.01 * int(self.spec["window"]["slots"]) / self.slots),
            (f"{query}.planned_hbm_share", share, 0.65)]
