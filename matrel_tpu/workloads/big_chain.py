"""North-star workload: the 65k×65k chain A·B·C (BASELINE.json:2).

65k² f32 is 17 GB per matrix — three operands plus intermediates cannot be
resident on a 16 GB v5e chip, and the pod-scale path (v5e-64: operands
sharded P(x,y), strategies from parallel/) is exercised by dryrun_multichip.
This module makes the chain FEASIBLE AND FAST on chips it doesn't fit on,
by streaming:

    out_panel_i = (A_i · B) · C         for row panels A_i

with B and C never fully resident — their k-tiles are produced on demand by
traceable generator functions (synthetic data, checkpoint shards, or
gathers from host storage). Memory is O(panel × n); every FLOP is an MXU
tile GEMM; the whole triple loop is ONE jitted program (fori_loops).

This is the blockwise-accumulation answer SURVEY.md §6/§7 calls for
("intermediates force thought about donation/accumulation order; blockwise
chain evaluation may be needed").
"""

from __future__ import annotations

import functools
from typing import Callable

import jax

from matrel_tpu.utils import compat
import jax.numpy as jnp

Gen = Callable[[jax.Array, jax.Array], jax.Array]
# Gen(bi, bj) -> tile of shape (tile, tile): block (bi, bj) of the operand.


def default_gen(seed: int, tile: int, dtype=jnp.bfloat16, scale: float = None
                ) -> Gen:
    """Deterministic tile generator (iota arithmetic — RNG at 65k² costs
    more than the matmuls). Scaled ~1/sqrt(n) so chained products stay in
    bf16 range. Carries a ``.slab(r0, c0, shape)`` fast path generating an
    arbitrary global-coordinate rectangle in one fused elementwise op."""
    s = scale if scale is not None else 0.01

    def gen(bi, bj):
        r = jax.lax.broadcasted_iota(jnp.float32, (tile, tile), 0)
        c = jax.lax.broadcasted_iota(jnp.float32, (tile, tile), 1)
        v = jnp.sin(r * 0.1 + c * 0.37 + bi * 1.7 + bj * 0.3 + seed) * s
        return v.astype(dtype)

    def slab(r0, c0, shape):
        rg = jax.lax.broadcasted_iota(jnp.float32, shape, 0) + r0
        cg = jax.lax.broadcasted_iota(jnp.float32, shape, 1) + c0
        r, bi = rg % tile, rg // tile
        c, bj = cg % tile, cg // tile
        v = jnp.sin(r * 0.1 + c * 0.37 + bi * 1.7 + bj * 0.3 + seed) * s
        return v.astype(dtype)

    gen.slab = slab
    return gen


def cheap_gen(seed: int, tile: int, dtype=jnp.bfloat16, scale: float = None
              ) -> Gen:
    """Generator with a ~4-op elementwise body (fractional-part mixing
    instead of sin) — at 65k² the transcendental in ``default_gen`` is
    VPU time stolen from the MXU. Values are uniform-ish in [-s, s];
    statistically crude but plenty for exercising/benchmarking the
    pipeline, and fully deterministic."""
    s = scale if scale is not None else 0.01

    def _vals(rg, cg):
        x = rg * 0.6180339887 + cg * 0.7548776662 + (seed + 1) * 0.5545497
        return ((x - jnp.floor(x)) * 2.0 - 1.0) * s

    def gen(bi, bj):
        r = jax.lax.broadcasted_iota(jnp.float32, (tile, tile), 0)
        c = jax.lax.broadcasted_iota(jnp.float32, (tile, tile), 1)
        return _vals(r + bi * tile, c + bj * tile).astype(dtype)

    def slab(r0, c0, shape):
        rg = jax.lax.broadcasted_iota(jnp.float32, shape, 0) + r0
        cg = jax.lax.broadcasted_iota(jnp.float32, shape, 1) + c0
        return _vals(rg, cg).astype(dtype)

    gen.slab = slab
    return gen


def streaming_chain(n: int,
                    gen_a: Gen, gen_b: Gen, gen_c: Gen,
                    tile: int = 8192,
                    panel: int = 16384,
                    dtype=jnp.bfloat16,
                    reduce: str = "fro") -> jax.Array:
    """Evaluate reduce(A·B·C) for n×n operands produced tile-wise.

    Per output row panel i:
        T_i[., :]  = Σ_k gen_a(i, k) · B_k      (B_k = row-block k of B)
        O_i[., :]  = Σ_k T_i[., k] · C_k
        acc       += reduction(O_i)
    The returned scalar (Frobenius² by default, or 'sum') certifies the
    whole product was computed without materialising any n×n array.
    """
    if n % tile or n % panel or panel % tile:
        raise ValueError("n must divide by tile and panel; panel by tile")
    kt = n // tile         # tiles along contraction
    npan = n // panel      # row panels
    prec = jax.lax.Precision.DEFAULT

    run = _chain_runner(n, tile, panel, kt, npan, gen_a, gen_b, gen_c,
                        dtype, reduce, prec)
    return run()


def streaming_chain_slab(n: int,
                         gen_a: Gen, gen_b: Gen, gen_c: Gen,
                         tile: int = 8192,
                         panel: int = 16384,
                         dtype=jnp.bfloat16,
                         reduce: str = "fro") -> jax.Array:
    """Slab-structured evaluation of reduce(A·B·C) — the fast single-chip
    north-star path.

    Differs from ``streaming_chain`` in how the contraction is scheduled:
    instead of accumulating a (panel, n) f32 carry across k-steps (which
    round-trips the 4 GB accumulator through HBM kt× per phase), every
    output slab is ONE ``dot_general`` over the full 65k contraction —
    the f32 accumulation happens inside the MXU's tiling, never touching
    HBM. Operand column slabs (n, tile) are produced by the generators'
    ``.slab`` fast path in one fused elementwise op each.

        T_i[:, j] = A_i · B[:, j]      (one dot per slab, full k)
        acc      += reduce(T_i · C[:, j])

    Requires gens built by ``default_gen``/``cheap_gen`` (anything with
    ``.slab(r0, c0, shape)``).
    """
    if n % tile or n % panel or panel % tile:
        raise ValueError("n must divide by tile and panel; panel by tile")
    for g in (gen_a, gen_b, gen_c):
        if not hasattr(g, "slab"):
            raise ValueError("streaming_chain_slab needs .slab-capable "
                             "generators (default_gen / cheap_gen)")
    run = _slab_runner(n, tile, panel, gen_a, gen_b, gen_c, dtype, reduce)
    return run()


def _vma_zeros(shape, dt, vma_axes):
    """Zeros marked varying over ``vma_axes`` (loop carries under
    shard_map need this or the fori carry types mismatch)."""
    z = jnp.zeros(shape, dtype=dt)
    if vma_axes:
        z = compat.pvary(z, vma_axes)
    return z


def _make_slab_panel_body(n, tile, panel, gen_a, gen_b, gen_c, dtype,
                          reduce, vma_axes=()):
    """Slab-scheduled per-panel contraction, shared by the single- and
    multi-chip evaluators. ``vma_axes`` as in ``_make_panel_body``."""
    kt = n // tile

    def zeros(shape, dt):
        return _vma_zeros(shape, dt, vma_axes)

    def panel_body(i, acc):
        a_i = gen_a.slab(i * panel, 0, (panel, n)).astype(dtype)

        # (Unrolling these j loops was measured identical to fori_loop —
        # 6.30 s either way at n=65k — so keep the compact loop form.)
        def fill_t(j, t):
            b_j = gen_b.slab(0, j * tile, (n, tile)).astype(dtype)
            s = jax.lax.dot_general(
                a_i, b_j, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jax.lax.dynamic_update_slice(
                t, s.astype(dtype), (0, j * tile))

        t_i = jax.lax.fori_loop(0, kt, fill_t, zeros((panel, n), dtype))

        def reduce_o(j, a2):
            c_j = gen_c.slab(0, j * tile, (n, tile)).astype(dtype)
            o = jax.lax.dot_general(
                t_i, c_j, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return a2 + (jnp.sum(o * o) if reduce == "fro"
                         else jnp.sum(o))

        return acc + jax.lax.fori_loop(0, kt, reduce_o,
                                       zeros((), jnp.float32))

    return panel_body


@functools.lru_cache(maxsize=8)
def _slab_runner(n, tile, panel, gen_a, gen_b, gen_c, dtype, reduce):
    npan = n // panel
    panel_body = _make_slab_panel_body(n, tile, panel, gen_a, gen_b, gen_c,
                                       dtype, reduce)

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def run():
        return jax.lax.fori_loop(0, npan, panel_body,
                                 jnp.zeros((), jnp.float32))

    return run


def streaming_chain_sharded(n: int,
                            gen_a: Gen, gen_b: Gen, gen_c: Gen,
                            mesh,
                            tile: int = 8192,
                            panel: int = 16384,
                            dtype=jnp.bfloat16,
                            reduce: str = "fro") -> jax.Array:
    """Multi-chip streaming chain: row panels distributed over ALL mesh
    devices (each device generates and contracts its own panels — the
    generators make operands location-free, so there is no input comm at
    all), one psum of the scalar reduction at the end.

    This is the v5e-64 shape of the north star: wall-clock scales ~1/P.
    Validated on the virtual CPU mesh by dryrun_multichip.
    """
    from matrel_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    if n % tile or n % panel or panel % tile:
        raise ValueError("n must divide by tile and panel; panel by tile")
    kt = n // tile
    npan = n // panel
    axes = tuple(mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    if npan % p:
        raise ValueError(f"panels ({npan}) must divide over devices ({p})")
    per_dev = npan // p
    prec = jax.lax.Precision.DEFAULT
    # slab schedule when the generators support it (same fast structure
    # as the single-chip north star); tile-assembly body otherwise
    if all(hasattr(g, "slab") for g in (gen_a, gen_b, gen_c)):
        panel_body = _make_slab_panel_body(n, tile, panel, gen_a, gen_b,
                                           gen_c, dtype, reduce,
                                           vma_axes=axes)
    else:
        panel_body = _make_panel_body(n, tile, panel, kt, gen_a, gen_b,
                                      gen_c, dtype, reduce, prec,
                                      vma_axes=axes)

    def kernel():
        idx = jnp.zeros((), jnp.int32)
        mult = 1
        for a in reversed(axes):
            idx = idx + jax.lax.axis_index(a) * mult
            mult *= mesh.shape[a]

        def body(j, acc):
            return panel_body(idx * per_dev + j, acc)

        acc0 = jnp.zeros((), jnp.float32)
        acc0 = compat.pvary(acc0, axes)
        local = jax.lax.fori_loop(0, per_dev, body, acc0)
        return jax.lax.psum(local, axes)

    f = jax.jit(shard_map(kernel, mesh=mesh, in_specs=(), out_specs=P()))  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    return f()


def _make_panel_body(n, tile, panel, kt, gen_a, gen_b, gen_c, dtype,
                     reduce, prec, vma_axes=()):
    """The per-panel contraction shared by the single- and multi-chip
    streaming evaluators. ``vma_axes``: mesh axes this body runs manual
    over (shard_map) — loop-carry zeros must be marked varying over them
    or the fori carries type-mismatch."""
    def zeros(shape, dt):
        return _vma_zeros(shape, dt, vma_axes)

    def row_block(gen, k, width_tiles):
        """Assemble row-block k (tile × n) from width_tiles generated tiles."""
        def one(j, acc):
            t = gen(k, j).astype(dtype)
            return jax.lax.dynamic_update_slice(acc, t, (0, j * tile))
        return jax.lax.fori_loop(0, width_tiles, one,
                                 zeros((tile, n), dtype))

    pt = panel // tile

    def col_panel(gen, i, k):
        """(panel, tile) column slab: tiles (i*pt+ti, k) stacked."""
        def one(ti, acc):
            t = gen(i * pt + ti, k).astype(dtype)
            return jax.lax.dynamic_update_slice(acc, t, (ti * tile, 0))
        return jax.lax.fori_loop(0, pt, one, zeros((panel, tile), dtype))

    def panel_body(i, acc):
        # --- T_i = A_i · B, contracted k-block by k-block so each B
        #     row-block is generated ONCE per panel (not once per
        #     tile-row — an 8× generation saving at panel=8*tile)
        def contract_b(k, part):
            a_col = col_panel(gen_a, i, k)                # (panel, tile)
            b_row = row_block(gen_b, k, kt)               # (tile, n)
            return part + jax.lax.dot_general(
                a_col, b_row, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)

        t_i = jax.lax.fori_loop(
            0, kt, contract_b, zeros((panel, n), jnp.float32)).astype(dtype)

        # --- O_i = T_i · C, contracted tile-column by tile-column
        def contract_c(k, part):
            t_slice = jax.lax.dynamic_slice(
                t_i, (0, k * tile), (panel, tile))
            c_row = row_block(gen_c, k, kt)               # (tile, n)
            return part + jax.lax.dot_general(
                t_slice, c_row, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)

        o_i = jax.lax.fori_loop(
            0, kt, contract_c, zeros((panel, n), jnp.float32))
        if reduce == "fro":
            return acc + jnp.sum(o_i * o_i)
        return acc + jnp.sum(o_i)

    return panel_body


@functools.lru_cache(maxsize=8)
def _chain_runner(n, tile, panel, kt, npan, gen_a, gen_b, gen_c, dtype,
                  reduce, prec):
    panel_body = _make_panel_body(n, tile, panel, kt, gen_a, gen_b, gen_c,
                                  dtype, reduce, prec)

    @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims outside the plan path
    def run():
        return jax.lax.fori_loop(0, npan, panel_body,
                                 jnp.zeros((), jnp.float32))

    return run


def north_star_flops(n: int) -> float:
    """A·B then ·C: 2n³ + 2n³."""
    return 4.0 * n ** 3
