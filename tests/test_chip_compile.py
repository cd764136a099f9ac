"""The main path's Pallas kernels, compiled by the chip's own compiler
for a DESCRIBED v5e 2x2 (nothing attached, no chip time): what
interpret mode cannot show — VMEM refusals, tiling, partitioning.
Shapes are the ones chip_smoke.py's plans produce at README scale
(PageRank 1M nodes / 10M edges: 1954 blocks of 512 rows, 5376 slots;
SpMM 100 352^2 at 1% of 512^2 tiles, 512 dense columns).

One file on purpose: the worker that describes the topology holds the
TPU library until it exits, so a second file would skip on another
worker. The topology is described inside a fixture, never at import.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from matrel_tpu.config import MatrelConfig
from matrel_tpu.ops import kernel_registry as kr
from matrel_tpu.ops import pallas_spmm, pallas_spmv as pc
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.parallel import strategies

# README-scale PageRank plan (tools: build_spmv_plan on 1M/10M uniform)
NB, CAP, BLOCK = 1954, 5376, 512
N_NODES = 1_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # can never be read back without a chip: keep it off for this file
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                ("x", "y"))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; the text must hold the kernel."""
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _refusal(fn, *args):
    """None when the compile passes, else the compiler's VMEM refusal."""
    try:
        fn.lower(*args).compile()
        return None
    except Exception as e:  # noqa: BLE001 — the refusal text is the result
        m = re.search(r"Scoped allocation with size \S+ and limit \S+"
                      r"|Ran out of memory in memory space vmem", str(e))
        assert m, f"refused for another reason than VMEM: {e}"
        return m.group(0)


def _compact_table_shapes(nb, sharding):
    shp = (nb, CAP // pc.LANE, pc.LANE)
    return tuple(_sds(sharding, shp, dt)
                 for dt in (jnp.int32, jnp.int8, jnp.int32, jnp.float32))


def test_compact_spmv(one_chip):
    static = (N_NODES, N_NODES, BLOCK, spmv_lib.LO)
    x = _sds(one_chip, (N_NODES,), jnp.float32)
    _compile(pc._compact_jitted, static,
             _compact_table_shapes(NB, one_chip), (), x, 3, False)


def _assert_no_padded_gather(compiled):
    """PR 28: gathered as 8 float32 a slot, x[idx] came out as
    f32[10504704,8] in (8,128) tiles: 128 lanes a row, a 5.38 GB
    temporary written and read back every round. Neither may return."""
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9
    assert f"f32[{NB * CAP},{spmv_lib.WIDTH}]" not in compiled.as_text()


def test_compact_spmv_gathers_no_padded_rows(one_chip):
    static = (N_NODES, N_NODES, BLOCK, spmv_lib.LO)
    x = _sds(one_chip, (N_NODES,), jnp.float32)
    _assert_no_padded_gather(_compile(
        pc._compact_jitted, static, _compact_table_shapes(NB, one_chip),
        (), x, 3, False))


def test_pagerank_loop_gathers_no_padded_rows(one_chip):
    from matrel_tpu.workloads import pagerank
    static = (N_NODES, N_NODES, BLOCK, spmv_lib.LO)
    loop = pagerank._compact_runner_loop(N_NODES, 30, 0.85, static, 0, 3,
                                         False)
    dangling = _sds(one_chip, (N_NODES,), jnp.float32)
    _assert_no_padded_gather(_compile(
        loop, _compact_table_shapes(NB, one_chip), (), dangling))


def test_compact_spmv_k_wide(one_chip):
    """The blocks layout through the k-wide chunk kernel: a row of 5,376
    slots walked as 3 chunks of 1,792."""
    static = (N_NODES, N_NODES, BLOCK, spmv_lib.LO)
    X = _sds(one_chip, (N_NODES, 8), jnp.float32)
    compiled = _compile(pc._compact_matmat_jitted, static, ((0, static),),
                        ((_compact_table_shapes(NB, one_chip), (), None),),
                        X, 3, False)
    assert "matrel_spmm_scatter_chunks" in compiled.as_text()


# The Netflix-shaped ratings matrix of cell gnmf_netflix_r128_1c (PR 37):
# 480,189 users x 17,770 movies, 100,480,507 ratings in chunks of 2,048
# slots (1.01 slots an entry), rank 128. Users as rows: one plan, the
# 9.1 MB t(H) its gather table. Movies as rows: W is 246 MB, past what a
# gather table keeps its row rate for, so four source panels of 120,048
# users (61.5 MB) each.
NF_USERS, NF_MOVIES, NF_RANK = 480_189, 17_770, 128
NF_CHUNKS, NF_PANEL_CHUNKS, NF_PANEL_USERS = 49_560, 12_300, 120_048


def _chunk_table_shapes(chunks, sharding):
    shp = (chunks, spmv_lib.CHUNK // pc.LANE, pc.LANE)
    return tuple(_sds(sharding, shp, dt) for dt in (
        jnp.int32, jnp.int8, jnp.int32, jnp.float32)) + (
        _sds(sharding, (chunks,), jnp.int32),)


def test_gnmf_forward_product_runs_in_panels(one_chip, monkeypatch):
    """V * t(H) at the full shape: gathering 512 B for every one of
    101.5M slots at once is 52 GB on a 15.75 GB chip; in panels of
    chunks the temporaries are what ``wide_plan_bytes`` reckons and the
    three output-sized buffers of the aliased scatter, a slot's row is
    gathered once for all 128 columns from a table in fast memory, and
    the multiply by the ratings happens inside the kernel, which holds a
    body a height of the one-hot (PR 49: 1,931 bundles a step at a
    128-row window, 3,089 at a 256-row one, 5,796 at the whole block,
    read offline; ``win`` one int32 a chunk as before)."""
    monkeypatch.setattr(pc, "_hbm_limit", lambda: int(15.75 * 2 ** 30))
    static = (NF_USERS, NF_MOVIES, BLOCK, spmv_lib.LO)
    compiled = _compile(
        pc._compact_matmat_jitted, static, ((0, static),),
        ((_chunk_table_shapes(NF_CHUNKS, one_chip), (),
          (_sds(one_chip, (NF_CHUNKS,), jnp.int32),)),),
        _sds(one_chip, (NF_MOVIES, NF_RANK), jnp.float32), 3, False)
    text = compiled.as_text()
    per = pc.wide_panel_rows(NF_CHUNKS, spmv_lib.CHUNK)
    assert 1 < per < NF_CHUNKS
    slots = per * spmv_lib.CHUNK
    assert f"f32[{slots},128]" in text                  # a panel's rows
    assert f"f32[{NF_CHUNKS * spmv_lib.CHUNK},128]" not in text
    assert text.count(f"f32[{slots},128]{{1,0:T(8,128)}} fusion(") == 1, \
        "the gathered rows are written once: no multiply pass of XLA's"
    assert re.search(rf"f32\[{NF_MOVIES + 8},128\]\{{[^}}]*S\(1\)\}}", text)
    stats = compiled.memory_analysis()
    out = stats.output_size_in_bytes
    reckoned = pc.wide_plan_bytes(NF_CHUNKS, spmv_lib.CHUNK) + 3 * out
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)


def test_gnmf_transposed_product_gathers_from_source_panels(one_chip,
                                                            monkeypatch):
    """t(V) * W at the full shape: W whole (246 MB) is no fast gather
    table; each of the four source panels' slices of it is (S(1))."""
    monkeypatch.setattr(pc, "_hbm_limit", lambda: int(15.75 * 2 ** 30))
    assert spmv_lib.source_panels(NF_USERS) == 4
    assert spmv_lib.source_panels(NF_MOVIES) == 1
    static = (NF_MOVIES, NF_USERS, BLOCK, spmv_lib.LO)
    statics = tuple(
        (c0, (NF_MOVIES, min(NF_PANEL_USERS, NF_USERS - c0), BLOCK,
              spmv_lib.LO))
        for c0 in range(0, NF_USERS, NF_PANEL_USERS))
    compiled = _compile(
        pc._compact_matmat_jitted, static, statics,
        tuple((_chunk_table_shapes(NF_PANEL_CHUNKS, one_chip), (),
               (_sds(one_chip, (NF_PANEL_CHUNKS,), jnp.int32),))
              for _ in statics),
        _sds(one_chip, (NF_USERS, NF_RANK), jnp.float32), 3, False)
    text = compiled.as_text()
    assert re.search(rf"f32\[{NF_PANEL_USERS + 8},128\]\{{[^}}]*S\(1\)\}}",
                     text)
    assert f"f32[{NF_USERS + 8},128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 5.0e9


# The same matrix with its dense part (PR 43): the 2,176 hottest movie
# columns (25% of the chip's bytes_limit: 17 groups of 128) in one float32
# slab, 83.3% of the ratings; the compact plans hold the other 16.8M
# entries in chunks of 2,048 slots.
NF_LINES, NF_REST_CHUNKS = 2_176, 8_300


def _cycles_ms(text, shape):
    """Milliseconds at 1.45 GHz of the fusions of ``text`` whose result
    is ``shape`` and whose root is a dot, by the compiler's own
    ``estimated_cycles`` (PR 34)."""
    out = []
    for line in text.splitlines():
        m = re.search(r'"estimated_cycles":"?(\d+)', line)
        if m and f"= {shape}" in line and "dot_general" in line:
            out.append(int(m.group(1)) / 1.45e6)
    return out


def test_gnmf_products_with_a_slab(one_chip, monkeypatch):
    """Both products of a GNMF iteration over plans with a dense part, at
    the cell's shapes: the slab is an argument read where it lies (no
    transposed or relaid copy among the temporaries), the memory is what
    ``plan_facts`` reckons (tables, a panel, the slab once), and the two
    dense parts cost what the MXU takes for them: the forward one ONE
    fused dot, the transposed one a loop of 58 panels of 8,192 users
    and a tail, each within twice the six-pass floor (8.1 ms)."""
    monkeypatch.setattr(pc, "_hbm_limit", lambda: int(15.75 * 2 ** 30))
    slab = _sds(one_chip, (NF_USERS, NF_LINES), jnp.float32)
    lines = _sds(one_chip, (NF_LINES,), jnp.int32)
    floor_ms = 2 * NF_USERS * NF_LINES * NF_RANK * 6 / 197e12 * 1e3

    def wins(chunks):
        return (_sds(one_chip, (chunks,), jnp.int32),)

    # V * t(H): the lines are sources
    static = (NF_USERS, NF_MOVIES, BLOCK, spmv_lib.LO)
    fwd = _compile(
        pc._compact_matmat_jitted, static,
        ((0, static), ("sources", None)),
        ((_chunk_table_shapes(NF_REST_CHUNKS, one_chip), (),
          wins(NF_REST_CHUNKS)), (slab, lines)),
        _sds(one_chip, (NF_MOVIES, NF_RANK), jnp.float32), 3, False)
    stats = fwd.memory_analysis()
    out = stats.output_size_in_bytes
    reckoned = (pc.wide_plan_bytes(NF_REST_CHUNKS, spmv_lib.CHUNK)
                + 4 * NF_USERS * NF_LINES + 3 * out)
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)
    (dot_ms,) = _cycles_ms(fwd.as_text(), f"f32[{NF_USERS},128]")
    assert floor_ms < dot_ms < 2 * floor_ms, dot_ms

    # t(V) * W: the lines are destinations, W in four source panels
    static = (NF_MOVIES, NF_USERS, BLOCK, spmv_lib.LO)
    statics = tuple(
        (c0, (NF_MOVIES, min(NF_PANEL_USERS, NF_USERS - c0), BLOCK,
              spmv_lib.LO))
        for c0 in range(0, NF_USERS, NF_PANEL_USERS))
    per = NF_REST_CHUNKS // 4
    bwd = _compile(
        pc._compact_matmat_jitted, static,
        statics + (("destinations", None),),
        tuple((_chunk_table_shapes(per, one_chip), (), wins(per))
              for _ in statics) + ((slab, lines),),
        _sds(one_chip, (NF_USERS, NF_RANK), jnp.float32), 3, False)
    stats = bwd.memory_analysis()
    # no second slab: the temporaries are two source panels' gathered
    # rows (the next one's gather under this one's scatter)
    rows = pc._TEMP_BYTES_A_SLOT_WIDE * per * spmv_lib.CHUNK
    assert stats.temp_size_in_bytes < 2.1 * rows
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < \
        int(15.75 * 2 ** 30)
    text = bwd.as_text()
    assert f"f32[{NF_LINES},{NF_USERS}]" not in text       # no transpose
    assert not re.search(rf"= f32\[{NF_USERS},{NF_LINES}\]\S* "
                         r"(copy|transpose)\(", text)
    panel_ms = _cycles_ms(text, f"f32[{NF_LINES},128]")
    assert len(panel_ms) == 2                   # the loop's body, the tail
    whole, tail = divmod(NF_USERS, strategies.ACC_PANEL_ROWS)
    loop_ms = max(panel_ms) * whole + min(panel_ms)
    assert tail and floor_ms < loop_ms < 2 * floor_ms, panel_ms


# The cell pnmf_netflix_r128_1c's two sampled products (PR 46) over the
# same matrix as the chip laid it out (PR 43's slab as the chip chose it:
# 4,224 lines in bfloat16; the residual's 7.8M entries in 4,289 forward
# chunks, and 3,902 transposed ones in four source panels)
PN_LINES, PN_FWD_CHUNKS = 4_224, 4_289
PN_BWD_CHUNKS = (996, 978, 970, 958)


def _gather_operands(text, rows_over=0):
    """The shape of the TABLE of every gather of more than ``rows_over``
    rows in a compiled program's text: the first operand of each
    ``gather(`` instruction, by its own definition (XLA fuses a gather:
    its table is then a parameter of the fused computation, which names
    its shape)."""
    shape_of = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    return [shape_of[name] for rows, name in re.findall(
        r"= \w+\[(\d+)[\d,]*\]\S* gather\((%[\w.\-]+)", text)
        if int(rows) > rows_over]


def test_pnmf_sampled_products(one_chip, monkeypatch):
    """(V ./ (W H)) * t(H) and t(W) * (V ./ (W H)) at the cell's shapes
    through the sampled kernel (PR 47): neither W H nor the quotient
    exists whole (no users x movies and no users x lines float32 array),
    NO gather reads the destination's factor — the kernel takes its rows
    off the block tile it adds into — so W's 246 MB are no gather's
    table in either product (a source panel's 61.5 MB of them are, in
    fast memory), the panels are ``wide_panel_rows``' (2 forward, as
    GNMF's), the slab is read where it lies, and arguments and
    temporaries stay inside what ``sampled_facts`` reckons the plan to
    hold. ``win`` is one int32 a chunk, start and rung of the ladder in
    one word (PR 49: the operands are PR 47's). PR 57: the dense lines
    of both products (the slab 480,189 x 4,224 bfloat16, ``k`` = ``inner``
    = 128, the lines the W update's sources and the H update's
    destinations) through ``matrel_sampled_lines`` beside the scatter
    kernel — no panel of the quotient (``f32[8192,4224]``) is left, no
    copy or transpose of a factor the size of ``W`` feeds the kernel, and
    the reckoning holds WITHOUT the loop's 415 MB a panel, as
    ``sampled_facts`` now reckons it. Prints what the compile
    says of the kernel; its bundles a step are read offline, one body a
    compile (PERF.md section 6, PR 49: 3,418 at a 128-row window, 5,883
    at a 256-row one and 11,983 at the whole block, where the plain
    kernel reads 1,931, 3,089 and 5,796; the three bodies in the one
    kernel 20,783 and 10,397, each ``pl.when`` region what it reads
    alone less a prologue of ~210)."""
    monkeypatch.setattr(pc, "_hbm_limit", lambda: int(15.75 * 2 ** 30))
    slab = _sds(one_chip, (NF_USERS, PN_LINES), jnp.bfloat16)
    lines = _sds(one_chip, (PN_LINES,), jnp.int32)
    run = jax.jit(pc.sampled_matmat_parts,
                  static_argnums=(0, 1, 4, 7, 8))
    from matrel_tpu.ops import sampled_lines
    assert sampled_lines.plan(PN_LINES, 2) == {"lines_by": "kernel",
                                               "panel_rows": 512}

    def wins(chunks):
        return (_sds(one_chip, (chunks,), jnp.int32),)

    def check(compiled, slots_a_panel, out_bytes, dst_rows):
        text = compiled.as_text()
        assert f"f32[{NF_USERS},{NF_MOVIES}]" not in text
        assert f"f32[{NF_USERS},{PN_LINES}]" not in text
        assert f"f32[{PN_LINES},{NF_USERS}]" not in text
        assert not re.search(rf"= bf16\[{NF_USERS},{PN_LINES}\]\S* "
                             r"(copy|transpose)\(", text)
        assert "matrel_sampled_scatter_chunks" in text
        assert "matrel_sampled_lines" in text
        assert "matrel_spmm_scatter_chunks" not in text
        assert f"f32[{strategies.ACC_PANEL_ROWS},{PN_LINES}]" not in text
        assert not re.search(rf"= f32\[(128,{NF_USERS}|{NF_USERS},128)\]\S* "
                             r"(copy|transpose)\(", text)
        # the gathers by the slot (the dense lines' own factor rows, a
        # gather of 4,224, are the dense part's and stay)
        tables = _gather_operands(text, rows_over=PN_LINES)
        nb = -(-dst_rows // BLOCK)
        assert tables
        # the destination's factor, as the kernel takes it or as given
        assert not any(t in tables for t in (
            f"f32[{nb * BLOCK},128]", f"f32[{nb},{BLOCK},128]",
            f"f32[{dst_rows},128]")), tables
        # and W whole (246 MB) under no name
        assert not any(re.match(rf"f32\[{NF_USERS}\D|f32\[{NF_USERS + 8}\D"
                                rf"|f32\[{938 * BLOCK}\D", t)
                       for t in tables), tables
        stats = compiled.memory_analysis()
        reckoned = (stats.argument_size_in_bytes + 3 * out_bytes
                    + pc._wide_slot_bytes(0) * slots_a_panel)
        taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
        assert taken < 1.05 * reckoned, (reckoned, taken)
        assert taken + out_bytes < int(15.75 * 2 ** 30)
        print(f"gather tables {sorted(set(tables))}; temporaries "
              f"{stats.temp_size_in_bytes:,} B of {reckoned:,} reckoned")
        return text

    # the W update: sources the movies (t(H)'s rows serve the dot and
    # the scatter), destinations the users: W's rows off the block tile
    static = (NF_USERS, NF_MOVIES, BLOCK, spmv_lib.LO)
    per = pc.wide_panel_rows(PN_FWD_CHUNKS, spmv_lib.CHUNK)
    assert -(-PN_FWD_CHUNKS // per) == 2
    fwd = _compile(
        run, static, ((0, static), ("sources", None)),
        ((_chunk_table_shapes(PN_FWD_CHUNKS, one_chip), (),
          wins(PN_FWD_CHUNKS)), (slab, lines)),
        _sds(one_chip, (NF_MOVIES, NF_RANK), jnp.float32), "div", None,
        _sds(one_chip, (NF_USERS, NF_RANK), jnp.float32), 3, False)
    text = check(fwd, per * spmv_lib.CHUNK, 4 * NF_USERS * NF_RANK,
                 NF_USERS)
    assert f"f32[{per * spmv_lib.CHUNK},128]" in text   # a panel's rows
    assert re.search(rf"f32\[{NF_MOVIES + 8},128\]\{{[^}}]*S\(1\)\}}", text)

    # the H update: sources the users in four source panels (W's rows
    # serve both: a panel's 120,048 are a fast table), destinations the
    # movies: t(H)'s rows off the block tile
    static = (NF_MOVIES, NF_USERS, BLOCK, spmv_lib.LO)
    statics = tuple(
        (c0, (NF_MOVIES, min(NF_PANEL_USERS, NF_USERS - c0), BLOCK,
              spmv_lib.LO))
        for c0 in range(0, NF_USERS, NF_PANEL_USERS))
    bwd = _compile(
        run, static, statics + (("destinations", None),),
        tuple((_chunk_table_shapes(c, one_chip), (), wins(c))
              for c in PN_BWD_CHUNKS) + ((slab, lines),),
        _sds(one_chip, (NF_USERS, NF_RANK), jnp.float32), "div", None,
        _sds(one_chip, (NF_MOVIES, NF_RANK), jnp.float32), 3, False)
    text = check(bwd, 2 * max(PN_BWD_CHUNKS) * spmv_lib.CHUNK,
                 4 * NF_MOVIES * NF_RANK, NF_MOVIES)
    assert re.search(rf"f32\[{NF_PANEL_USERS + 8},128\]\{{[^}}]*S\(1\)\}}",
                     text)


@pytest.mark.parametrize("role", ["sources", "destinations"])
@pytest.mark.parametrize("dtype,tile", [("bfloat16", 128), ("float32", 512)])
def test_the_lines_kernel_fits_vmem_wherever_its_plan_says_so(
        one_chip, dtype, tile, role):
    """``sampled_lines.plan`` reckons a step's VMEM from the shapes
    (``vmem_bytes``); interpret mode cannot say whether Mosaic agrees.
    The WIDEST slab it still takes at a row tile — 15,232 bfloat16 lines
    at 128 rows, 4,864 float32 lines at 512 — compiles for the described
    chip inside ``VMEM_LIMIT`` in both roles."""
    from matrel_tpu.ops import sampled_lines
    cell = jnp.dtype(dtype).itemsize
    width = 128
    while sampled_lines.vmem_bytes(tile, width + 128, cell) <= \
            sampled_lines.VMEM_LIMIT:
        width += 128
    assert width == {"bfloat16": 15_232, "float32": 4_864}[dtype]
    assert sampled_lines.plan(width, cell) == {"lines_by": "kernel",
                                               "panel_rows": tile}
    rows, others = 3_000, 20_000
    tall = _sds(one_chip, (rows, 128), jnp.float32)
    wide = _sds(one_chip, (others, 128), jnp.float32)
    Z, Y = (wide, tall) if role == "sources" else (tall, wide)
    text = _compile(
        jax.jit(lambda Y, slab, lines, Z, P, R: sampled_lines.sampled_lines(
            Y, role, slab, lines, Z, "div", P, R, tile=tile)),
        Y, _sds(one_chip, (rows, width), jnp.dtype(dtype)),
        _sds(one_chip, (width,), jnp.int32), Z, tall, wide).as_text()
    assert "matrel_sampled_lines" in text


def test_compact_spmv_sharded_2x2(mesh_2x2):
    mesh = mesh_2x2
    axes = tuple(mesh.axis_names)
    nb_pad = -(-NB // mesh.size) * mesh.size
    tables = _compact_table_shapes(
        nb_pad, NamedSharding(mesh, P(axes, None, None)))
    x = _sds(NamedSharding(mesh, P()), (N_NODES,), jnp.float32)
    run = pc._compact_sharded_runner(
        (N_NODES, N_NODES, BLOCK, spmv_lib.LO), mesh, 3, 0, False)
    text = _compile(run, *tables, x).as_text()
    assert "all-gather" in text


# The Graph500 scale-22 plan of cell pagerank_g500_22_1c (PR 33): chunks of
# spmv.CHUNK slots for 128.3M directed edges, 8 values a gathered row
G500_NODES, G500_CHUNKS = 2_396_366, 64_976


def _g500_loop(one_chip):
    from matrel_tpu.workloads import pagerank
    shp = (G500_CHUNKS, spmv_lib.CHUNK // pc.LANE, pc.LANE)
    tables = tuple(_sds(one_chip, shp, dt) for dt in (
        jnp.int32, jnp.int8, jnp.int32, jnp.float32)) + (
        _sds(one_chip, (G500_CHUNKS,), jnp.int32),)     # chunk -> block
    static = (G500_NODES, G500_NODES, BLOCK, spmv_lib.LO)
    loop = pagerank._compact_runner_loop(G500_NODES, 10, 0.85, static, 0, 3,
                                         False)
    return _compile(loop, tables, (),
                    _sds(one_chip, (G500_NODES,), jnp.float32))


def test_chunked_pagerank_loop_runs_in_panels(one_chip):
    """One gather over all 133M slots is a 17 GB temporary on a 15.75 GB
    chip. In panels a round's temporaries are what ``plan_bytes``
    reckons, the byte table stays in fast memory inside the panel loop,
    and the chunk scatter's 65k-entry scalar prefetch compiles."""
    compiled = _g500_loop(one_chip)
    text = compiled.as_text()
    slots = G500_CHUNKS * spmv_lib.CHUNK
    per = pc.panel_rows(G500_CHUNKS, spmv_lib.CHUNK)
    assert 1 < per < G500_CHUNKS
    assert f"u8[{per * spmv_lib.CHUNK},32]" in text       # a panel's rows
    assert f"u8[{slots},32]" not in text
    stats = compiled.memory_analysis()
    # what the gate and the plan cache reckon holds what the compiler
    # takes: arguments (13 B a slot) and temporaries, within 5%
    reckoned = pc.plan_bytes(G500_CHUNKS, spmv_lib.CHUNK)
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)
    rows = G500_NODES // 8 + 1
    assert spmv_lib._row_values(G500_NODES) == 8
    assert re.search(rf"u8\[{rows},32\]\{{[^}}]*S\(1\)\}}", text)
    assert "matrel_spmv_scatter_chunks" in text


@pytest.mark.parametrize("reduce,chunks", [("max", G500_CHUNKS),
                                           ("min", 4_096)])
def test_semiring_round_reduces_in_the_chunk_grid(one_chip, reduce, chunks):
    """Cell wcc_g500_22_1c's product (PR 50): the (max, x) reduction of
    the graph's 133M slots and a label column, compiled by Mosaic at the
    cell's size (the lane rotations of the segmented scan, the 65k-entry
    scalar prefetch), gathered in the matvec's own panels, its
    temporaries what ``plan_bytes`` reckons; (min, x) at a smaller
    size. No sum scatter is in the program."""
    shp = (chunks, spmv_lib.CHUNK // pc.LANE, pc.LANE)
    tables = tuple(_sds(one_chip, shp, dt) for dt in (
        jnp.int32, jnp.int8, jnp.int32, jnp.float32)) + (
        _sds(one_chip, (chunks,), jnp.int32),)          # chunk -> block
    static = (G500_NODES, G500_NODES, BLOCK, spmv_lib.LO)
    compiled = _compile(
        jax.jit(pc.reduce_apply, static_argnums=(0, 3, 4)), static, tables,
        _sds(one_chip, (G500_NODES,), jnp.float32), reduce, False)
    text = compiled.as_text()
    assert "matrel_spmv_reduce_chunks" in text
    assert "matrel_spmv_scatter" not in text
    if chunks != G500_CHUNKS:
        return
    per = pc.panel_rows(chunks, spmv_lib.CHUNK)
    assert 1 < per < chunks
    assert f"u8[{per * spmv_lib.CHUNK},32]" in text       # a panel's rows
    assert f"u8[{chunks * spmv_lib.CHUNK},32]" not in text
    stats = compiled.memory_analysis()
    reckoned = pc.plan_bytes(chunks, spmv_lib.CHUNK)
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)


# The same graph's plan with the hub table build_spmv_plan chooses for it
# (PR 36, PR 42): 286,720 hubs (2,240 table rows) hold 110.5M of the edges,
# in chunks of their own whose registers walk the table rows they name
G500_MAIN_CHUNKS, G500_HUB_CHUNKS, G500_HUBS = 11_179, 56_310, 286_720


def _g500_hub_tables(one_chip):
    """``compact_tables`` of that plan, as shapes: eleven of them."""
    def chunks(n, *dtypes):
        shp = (n, spmv_lib.CHUNK // pc.LANE, pc.LANE)
        return tuple(_sds(one_chip, shp, dt) for dt in dtypes) + (
            _sds(one_chip, (n,), jnp.int32),)            # chunk -> block

    return chunks(G500_MAIN_CHUNKS, jnp.int32, jnp.int8, jnp.int32,
                  jnp.float32) + (
        _sds(one_chip, (G500_HUBS,), jnp.int32),) + chunks(
        G500_HUB_CHUNKS, jnp.int32, jnp.int32, jnp.float32) + (
        _sds(one_chip, (G500_HUB_CHUNKS,), jnp.int32),)          # walks


def _g500_hub_loop(one_chip):
    from matrel_tpu.workloads import pagerank
    static = (G500_NODES, G500_NODES, BLOCK, spmv_lib.LO)
    loop = pagerank._compact_runner_loop(G500_NODES, 10, 0.85, static, 0, 3,
                                         False)
    return _compile(loop, _g500_hub_tables(one_chip), (),
                    _sds(one_chip, (G500_NODES,), jnp.float32))


def test_chunked_pagerank_loop_with_a_hub_table(one_chip):
    """The round with hub chunks: two kernels (the chunk scatter over the
    main chunks, the hub scatter over the others, their slot weights
    made in VMEM from the (2240, 128) table by a walk of the rows each
    register names: a loop with scalar-prefetched bounds), the main set
    in panels, the two scalar-prefetch arrays of the 55k hub chunks
    (chunk -> block, a chunk's two walks in one word: 450 KB) inside
    SMEM, and arguments and temporaries what ``plan_bytes`` reckons with
    12 B a hub slot."""
    compiled = _g500_hub_loop(one_chip)
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    assert "matrel_spmv_scatter_chunks" in text
    assert "matrel_spmv_scatter_hubs" in text
    per = pc.panel_rows(G500_MAIN_CHUNKS, spmv_lib.CHUNK)
    assert -(-G500_MAIN_CHUNKS // per) == 2 and per % 64 == 0
    assert f"u8[{per * spmv_lib.CHUNK},32]" in text
    assert f"f32[{G500_HUBS // pc.LANE},{pc.LANE}]" in text  # the hub table
    stats = compiled.memory_analysis()
    hub_slots = G500_HUB_CHUNKS * spmv_lib.CHUNK
    reckoned = pc.plan_bytes(G500_MAIN_CHUNKS, spmv_lib.CHUNK, hub_slots)
    assert stats.temp_size_in_bytes < reckoned - 13 * (
        G500_MAIN_CHUNKS * spmv_lib.CHUNK) - pc.HUB_BYTES_A_SLOT * hub_slots
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)


def test_semiring_round_with_a_hub_table(one_chip):
    """Cell wcc_g500_22_1c's product since PR 51: the (max, x) reduction
    over the plan cell 6 runs — 11,179 main chunks through the row
    gather in 2 panels and ``matrel_spmv_reduce_chunks``, 56,310 hub
    chunks through ``matrel_spmv_reduce_hubs`` (the walk of the (2240,
    128) table and the segmented scan in one body, compiled by Mosaic
    at the cell's size) — no sum scatter, and arguments and temporaries
    what ``plan_bytes`` reckons with 12 B a hub slot."""
    static = (G500_NODES, G500_NODES, BLOCK, spmv_lib.LO)
    compiled = _compile(
        jax.jit(pc.reduce_apply, static_argnums=(0, 3, 4)), static,
        _g500_hub_tables(one_chip),
        _sds(one_chip, (G500_NODES,), jnp.float32), "max", False)
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    assert "matrel_spmv_reduce_chunks" in text
    assert "matrel_spmv_reduce_hubs" in text
    assert "matrel_spmv_scatter" not in text
    per = pc.panel_rows(G500_MAIN_CHUNKS, spmv_lib.CHUNK)
    assert -(-G500_MAIN_CHUNKS // per) == 2 and per % 64 == 0
    assert f"u8[{per * spmv_lib.CHUNK},32]" in text
    assert f"f32[{G500_HUBS // pc.LANE},{pc.LANE}]" in text  # the hub table
    stats = compiled.memory_analysis()
    hub_slots = G500_HUB_CHUNKS * spmv_lib.CHUNK
    reckoned = pc.plan_bytes(G500_MAIN_CHUNKS, spmv_lib.CHUNK, hub_slots)
    taken = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 0.9 * reckoned < taken < 1.05 * reckoned, (reckoned, taken)
    # a quarter less than the plan without hub chunks held (PR 50)
    assert reckoned < 0.75 * pc.plan_bytes(G500_CHUNKS, spmv_lib.CHUNK)


def test_the_most_hub_chunks_the_rule_allows_fit_smem(one_chip):
    """The hub kernel's scalar prefetch is 8 B a chunk in a 1 MiB SMEM:
    as many chunks as ``_hub_rows`` lets a graph have compile (a twelfth
    more did not, PR 42), at the tallest table it may choose."""
    n, nb = spmv_lib._HUB_CHUNKS_MAX, 2 * G500_NODES // BLOCK
    rows = spmv_lib.hub_table_rows(spmv_lib._HUB_ROWS_MAX)
    run = pc._hub_runner(n, spmv_lib.CHUNK, nb, BLOCK, spmv_lib.LO, 3, rows,
                         spmv_lib.HUB_WALK, False)
    slots = (n, spmv_lib.CHUNK // pc.LANE, pc.LANE)
    _compile(jax.jit(run), _sds(one_chip, (n,), jnp.int32),
             _sds(one_chip, (n,), jnp.int32),
             *(_sds(one_chip, slots, dt)
               for dt in (jnp.int32, jnp.int32, jnp.float32)),
             _sds(one_chip, (rows, pc.LANE), jnp.float32),
             _sds(one_chip, (nb, BLOCK // spmv_lib.LO, spmv_lib.LO),
                  jnp.float32))


def test_aligned_table_rows_load_at_a_prefetched_offset(one_chip):
    """The hub walk stands on an (8, 128) load of the table in VMEM at a
    row offset read from a scalar-prefetch operand and promised a
    multiple of 8, inside a loop whose trip count is prefetched too;
    where a compiler stops taking it, its own words are the failure."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(walk_ref, table_ref, out_ref):
        first, steps = walk_ref[0], walk_ref[1]

        def step(t, acc):
            at = pl.multiple_of(first + 8 * t, 8)
            return acc + table_ref[pl.ds(at, 8), :]

        out_ref[...] = jax.lax.fori_loop(
            0, steps, step, jnp.zeros(out_ref.shape, jnp.float32))

    rows = G500_HUBS // pc.LANE
    walk = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec((rows, pc.LANE), lambda i, w: (0, 0))],
            out_specs=pl.BlockSpec((8, pc.LANE), lambda i, w: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((8, pc.LANE), jnp.float32))
    try:
        _compile(jax.jit(walk), _sds(one_chip, (2,), jnp.int32),
                 _sds(one_chip, (rows, pc.LANE), jnp.float32))
    except Exception as e:  # noqa: BLE001 — the compiler's words are the result
        pytest.fail("an aligned dynamic (8, 128) load of the hub table no "
                    f"longer lowers for the described v5e: {e}")


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32],
                         ids=["i32", "f32"])
def test_lane_permute_lowers(one_chip, dtype):
    """The hub kernel stands on ``take_along_axis`` along the lanes of an
    (8, 128) register lowering to the chip's lane permute; where a
    compiler stops taking it, its own words are the failure."""
    from jax.experimental import pallas as pl

    def kernel(table_ref, lane_ref, out_ref):
        out_ref[...] = jnp.take_along_axis(table_ref[...], lane_ref[...],
                                           axis=1)

    shape = (8, pc.LANE)
    permute = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(shape, dtype))
    try:
        _compile(jax.jit(permute), _sds(one_chip, shape, dtype),
                 _sds(one_chip, shape, jnp.int32))
    except Exception as e:  # noqa: BLE001 — the compiler's words are the result
        pytest.fail(f"take_along_axis(axis=1) on {shape} no longer lowers "
                    f"for the described v5e: {e}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_spmm(one_chip, dtype):
    bs, gr, pm = 512, 196, 512           # 100 352 = 196 * 512
    nnzb = gr * gr // 100 + gr           # 1% of tiles + the row padding
    tm = pallas_spmm._pick_tm(pm)
    kernel = pallas_spmm.spmm_call(bs, tm, pm // tm, nnzb, gr, pm, dtype)
    _compile(jax.jit(kernel), _sds(one_chip, (nnzb,), jnp.int32),
             _sds(one_chip, (nnzb,), jnp.int32),
             _sds(one_chip, (nnzb, bs, bs), dtype),
             _sds(one_chip, (gr, bs, pm), dtype))


def _pair_args(one_chip, bs, dtype, npairs, n_tiles):
    tiles = _sds(one_chip, (n_tiles, bs, bs), dtype)
    table = _sds(one_chip, (npairs,), jnp.int32)
    return tiles, tiles, table, table, table


@pytest.mark.parametrize("bs", [256, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_spgemm_pallas_generic(one_chip, bs, dtype):
    npairs, n_out = 384, 128
    run = kr._build_pallas_generic(bs, npairs, n_out, dtype, False)
    _compile(run, *_pair_args(one_chip, bs, dtype, npairs, 256))


def _grouped(one_chip, bs, dtype, G, n_groups=64, n_out=16):
    kernel = kr._grouped_call(bs, G, n_groups, n_out, dtype, False)
    return (jax.jit(kernel), _sds(one_chip, (n_groups,), jnp.int32),
            _sds(one_chip, (n_groups, bs, G * bs), dtype),
            _sds(one_chip, (n_groups, G * bs, bs), dtype))


GROUPED_KERNELS = [k for k, s in kr.REGISTRY.items() if s.group > 1]


@pytest.mark.parametrize("kernel_id", GROUPED_KERNELS)
@pytest.mark.parametrize("bs", [256, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_admissible_iff_compiles(one_chip, kernel_id, bs, dtype):
    """admissible() is true exactly where the chip's compiler takes the
    grouped schedule: an admitted kernel compiles at the widest G its
    builder may pick, and a refused one is refused even at G = 2 (the
    narrowest grouped schedule) — no shape admitted and refused."""
    cfg = MatrelConfig(pallas_interpret=True)    # the Pallas gate, on CPU
    isz = jnp.dtype(dtype).itemsize
    spec = kr.get_kernel(kernel_id)
    G = kr.grouped_factor(bs, spec.group, isz)
    if kr.admissible(kernel_id, bs, 64, cfg, dtype=dtype):
        assert G >= 2
        assert _refusal(*_grouped(one_chip, bs, dtype, G)) is None
        # and the clamp is not slack: twice the group is refused
        if G < spec.group:
            assert _refusal(*_grouped(one_chip, bs, dtype, 2 * G))
    else:
        assert G == 1
        assert _refusal(*_grouped(one_chip, bs, dtype, 2))


@pytest.mark.parametrize("bs,dtype,wa,rc,fits", [
    (256, jnp.float32, 3, 3, True), (256, jnp.float32, 5, 3, False),
    (512, jnp.bfloat16, 3, 2, True), (512, jnp.bfloat16, 3, 3, False),
    (512, jnp.float32, 1, 1, True), (512, jnp.float32, 2, 1, False),
])
def test_band_budget_matches_compiler(one_chip, bs, dtype, wa, rc, fits):
    """The band builder's chunk arithmetic (dot_step_vmem_bytes, no
    accumulator) against the compiler on both sides of the limit."""
    tile = bs * bs
    need = kr.dot_step_vmem_bytes(wa * tile, wa * rc * tile, rc * tile,
                                  jnp.dtype(dtype).itemsize, acc=False)
    assert (need <= kr.VMEM_SCOPED_LIMIT_BYTES) == fits
    gr, nch = 16, 2
    kernel = kr._band_call(bs, wa, rc, gr, nch, dtype, False)
    refused = _refusal(
        jax.jit(kernel), _sds(one_chip, (gr, bs, wa * bs), dtype),
        _sds(one_chip, (gr * nch, wa * bs, rc * bs), dtype))
    assert (refused is None) == fits


# -- the 65k chain's two panelled products (cell chain_65k_2x2) --------------

CHAIN_N, CHAIN_PANELS = 65536, (1, 8)


@pytest.fixture(scope="module")
def chain_program(mesh_2x2):
    """``(A * B) * C`` at 65536^2 bfloat16 under the panelled rmm as the
    cell's plan runs it, both products in ONE program (one product
    alone misleads: with 6 GiB of arguments beside them the compiler
    moves what it left in place before, PERF.md section 6, PR 30)."""
    table = _sds(NamedSharding(mesh_2x2, P("x", "y")), (CHAIN_N, CHAIN_N),
                 jnp.bfloat16)

    def mm(u, v):
        return strategies.run_matmul("rmm", u, v, mesh_2x2, MatrelConfig(),
                                     panels=CHAIN_PANELS,
                                     out_dtype=jnp.bfloat16)

    return jax.jit(lambda a, b, c: mm(mm(a, b), c)).lower(
        table, table, table).compile()


#: The line that opens a computation of ``compiled.as_text()``; group 1
#: is its name.
_COMPUTATION_HEAD = re.compile(
    r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def _entry_schedule(text, conv_shape):
    """The scheduled entry computation as a list of operation kinds, in
    order: ``permute-start``/``permute-done`` with the start's name,
    and ``dot`` for a fusion whose computation holds a convolution of
    ``conv_shape``."""
    dots, current = set(), None
    for line in text.splitlines():
        head = _COMPUTATION_HEAD.match(line)
        if head:
            current = head.group(1)
        elif current and re.search(
                rf"=\s*{re.escape(conv_shape)}\S*\s+convolution\(", line):
            dots.add(current)
    entry = text[text.index("\nENTRY "):]
    out = []
    for line in entry[:entry.index("\n}")].splitlines():
        start = re.match(r"\s*%([\w.\-]+)\s*=.*\scollective-permute-start\(",
                         line)
        done = re.search(r"\scollective-permute-done\(%([\w.\-]+)\)", line)
        calls = re.search(r"\sfusion\(.*calls=%([\w.\-]+)", line)
        if start:
            out.append(("permute-start", start.group(1)))
        elif done:
            out.append(("permute-done", done.group(1)))
        elif calls and calls.group(1) in dots:
            out.append(("dot", calls.group(1)))
    return out


def test_chain_moves_run_under_a_dot(chain_program):
    """Each product moves a 2 GiB slice of its left operand along the
    mesh row. In front of a loop the move stood exposed, 39 ms of every
    chip's time a product (PR 27 to 29); with column panel 0 multiplied
    ahead of the loop, its own chunk's dot runs between the move's
    start and its arrival. And no dot is there twice: four ahead of the
    two loops, the own chunk's and the moved one's of either product."""
    rows, cols = CHAIN_N // 2, CHAIN_N // 2 // CHAIN_PANELS[1]
    ops = _entry_schedule(chain_program.as_text(), f"f32[{rows},{cols}]")
    starts = [i for i, (kind, _) in enumerate(ops) if kind == "permute-start"]
    assert len(starts) == 2, ops
    for i in starts:
        done = ops.index(("permute-done", ops[i][1]))
        assert "dot" in [kind for kind, _ in ops[i:done]], ops
    assert [kind for kind, _ in ops].count("dot") == 4, ops


def test_chain_temporaries_are_what_the_plan_reckons(chain_program):
    """The program's temporaries are the 2 GiB intermediate and one
    product's transient (the two products' are not alive together):
    ``rmm_transient_bytes`` may stand at most 5% under the compiler."""
    reckoned = CHAIN_N * CHAIN_N * 2 / 4 + strategies.rmm_transient_bytes(
        CHAIN_N, CHAIN_N, CHAIN_N, 2, 2, 2, CHAIN_PANELS)
    assert chain_program.memory_analysis().temp_size_in_bytes \
        <= 1.05 * reckoned


# -- the regression's plan at the cell's size (cell linreg_10m_1c) ------------

LINREG_N, LINREG_K = 2_555_904, 1000


#: How the two tables may lie on the chip, as jax.experimental.layout
#: names it: the chip's default for these shapes puts the long dimension
#: on the 128 lanes (what ``device_put`` and a jitted generator both
#: gave on a v5e, PR 31: X 10,223,616,000 B, y 10,223,616 B, nothing
#: padded); row-major in (8, 128) tiles, X takes 1024 lanes a row
#: (10,468,982,784 B) and y 128 (1,308,622,848 B).
LINREG_LAYOUTS = {"as_the_chip_lays_them": ((1, 0), 1000 * 4 + 4),
                  "row_major": ((0, 1), 1024 * 4 + 128 * 4)}


@pytest.fixture(scope="module", params=sorted(LINREG_LAYOUTS))
def linreg_program(topo, request):
    """``inv(t(X) * X) * t(X) * y`` over one chip's quarter of the 10M x
    1k table as the session plans and lowers it, compiled for ONE
    described v5e, the tables in either layout. The fixture stands in
    for the chip where the program asks the backend and the array
    (``on_tpu``, how a described table lies): as the chip lays them the
    Gram is ONE kernel over the table (PR 55), row-major it is the loop
    over panels (``why_not`` layout)."""
    from jax.experimental.layout import Format, Layout
    from matrel_tpu import config as config_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.parallel import planner
    from matrel_tpu.session import MatrelSession
    major_to_minor, bytes_a_row = LINREG_LAYOUTS[request.param]
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object).reshape(1, 1),
                ("x", "y"))
    whole = NamedSharding(mesh, P(None, None))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(config_lib, "on_tpu", lambda: True)
        patch.setattr(planner, "_lies_by_columns",
                      lambda leaf: major_to_minor == (1, 0))
        sess = MatrelSession(mesh=mesh)
        for name, shape in (("X", (LINREG_N, LINREG_K)),
                            ("y", (LINREG_N, 1))):
            sess.register(name, BlockMatrix.from_array(
                _sds(whole, shape, jnp.float32), shape, mesh, P(None, None)))
        plan = sess.compile(sess.sql("inv(t(X) * X) * t(X) * y"))
        lie = Format(Layout(major_to_minor=major_to_minor), whole)
        compiled = plan.jitted.lower(*[
            _sds(lie, leaf.attrs["matrix"].shape, jnp.float32)
            for leaf in plan.leaf_order]).compile()
    return plan, compiled, LINREG_N * bytes_a_row, \
        request.param == "as_the_chip_lays_them"


#: Operations that hand an array on and write none: the loop over the
#: contraction's panels carries the table through them.
_NO_WRITE = {"parameter", "tuple", "get-tuple-element", "while", "bitcast"}


def _arrays_written(text, dim):
    """(instruction, dims) of every array with ``dim`` among its
    dimensions that an instruction outside a fused computation yields
    (inside one nothing is written: the fusion's own result is)."""
    fused = set(re.findall(r"\sfusion\(.*calls=%([\w.\-]+)", text))
    out, current = [], None
    for line in text.splitlines():
        head = _COMPUTATION_HEAD.match(line)
        if head:
            current = head.group(1)
            continue
        inst = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?)\s([\w\-]+)\(",
                        line)
        if not inst or current in fused or inst.group(3) in _NO_WRITE:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", inst.group(2)):
            dims = [int(d) for d in dims.split(",")]
            if dim in dims:
                out.append((inst.group(1), dims))
    return out


def test_linreg_plan_fits_beside_the_table(linreg_program):
    """With the tables resident (10.2 GB as the chip lays them, 11.8 GB
    row-major), the program's temporaries are what the plan reckons —
    the k x k Gram, Xᵀy, the solve's copies: megabytes — and arguments
    and temporaries fit the planner's budget. Neither layout makes the
    loop over the contraction's panels copy the table, and there is ONE
    such loop (PR 34): t(X)·y rides t(X)·X's — or, as the chip lays the
    table, none: ONE kernel reads it (PR 55), its accumulator (4 MB, a
    tile after the other) in fast memory."""
    plan, compiled, tables, kernel = linreg_program
    mem = compiled.memory_analysis()
    n, k = LINREG_N, LINREG_K
    assert mem.argument_size_in_bytes == tables
    text = compiled.as_text()
    loops = [ln for ln in text.splitlines()
             if " while(" in ln and f"f32[{n},{k}]" in ln]
    # t(X)·X and t(X)·y: in panels, or in the kernel's tiles
    assert len(loops) == (0 if kernel else 1), loops
    assert text.count('custom_call_target="tpu_custom_call"') == kernel
    # described tables are shapes, reckoned at their logical bytes
    residents, answer = n * k * 4 + n * 4, k * 4
    reckoned = plan.meta["hbm_plan_bytes"] - residents - answer
    # the Gram and Xᵀy (the loop's accumulators: the last block column's
    # holds Xᵀy's column), and the solve's copies of both
    assert reckoned == 2 * (k * k * 4 + k * 4)
    assert mem.temp_size_in_bytes <= 1.05 * reckoned
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes <= MatrelConfig().hbm_budget_bytes


def test_linreg_plan_writes_no_n_shaped_array(linreg_program):
    """No transposed copy of X, no pad to 1024 columns, no bfloat16
    split of it, no N-wide solve: outside the two tables' parameters
    (and the loops over the contraction's panels, which carry them
    through) no array with 2,555,904 in its shape is written that is
    larger than y's column re-laid as a vector (10 MB)."""
    plan, compiled, _, _ = linreg_program
    assert plan.meta["rule_hits"]["chain_solve"] == 1
    written = _arrays_written(compiled.as_text(), LINREG_N)
    assert all(int(np.prod(dims)) == LINREG_N for _, dims in written), written


def _loop_bodies(text):
    """{body: the computations it calls, itself among them} of every
    ``while`` of the compiled program, and every computation's lines."""
    lines, current = {}, None
    for line in text.splitlines():
        head = _COMPUTATION_HEAD.match(line)
        if head:
            current = head.group(1)
            lines[current] = []
        elif current:
            lines[current].append(line)

    def reach(name, seen):
        if name in seen or name not in lines:
            return seen
        seen.add(name)
        for line in lines[name]:
            for callee in re.findall(r"calls=%([\w.\-]+)", line):
                reach(callee, seen)
        return seen

    return {body: reach(body, set())
            for body in re.findall(r"body=%([\w.\-]+)", text)}, lines


def test_linreg_gram_multiplies_the_triangle_only(linreg_program):
    """The loop over t(X)·X's panels multiplies the upper block
    triangle: its convolutions' operations add up to the triangle's
    share of the square a full-square dot of a panel costs (10 of 16
    blocks at 256: 62.5%), each block column one convolution whose
    slices of the table are read in place: no array of a panel's length
    is written in either layout, beside y's column of it. As the chip
    lays the table the triangle is the kernel's (PR 55): ONE
    ``tpu_custom_call`` named ``matrel_gram`` that takes the table's
    parameter through a bitcast, 36 of 64 tiles of 128 a row tile, no
    loop and no convolution over the table left, nothing with n or a
    tile's rows among its dimensions written."""
    plan, compiled, _, kernel = linreg_program
    text = compiled.as_text()
    gram = plan.meta["products"][0]
    if kernel:
        assert plan.meta["executors"] == ["pallas_gram", "xla"]
        assert gram["gram_kernel"] == {"one_read": True, "rider": 1,
                                       "tile_rows": 2048, "tiles": [36, 64]}
        assert gram["gram_tiles"] == [36, 64]
        (call,) = [ln for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        assert "%matrel_gram" in call
        table = re.search(r"custom-call\(%([\w.\-]+),", call).group(1)
        assert re.search(
            rf"%{re.escape(table)} = f32\[{LINREG_K},{LINREG_N}\]\S* "
            r"bitcast\(%args_[\w.]+\)", text)
        bodies, lines = _loop_bodies(text)
        assert not [ln for reached in bodies.values() for name in reached
                    for ln in lines[name] if " convolution(" in ln]
        assert not _arrays_written(text, LINREG_N)
        assert not _arrays_written(text, 2048)
        return
    assert gram["gram_kernel"]["why_not"] == "layout"
    assert gram["gram_tiles"] == [10, 16]
    rows, k = strategies.ACC_PANEL_ROWS, LINREG_K
    bodies, lines = _loop_bodies(text)
    ops = [[2 * rows * int(m) * int(w)
            for name in reached for line in lines[name]
            for m, w in re.findall(
                r"= f32\[(\d+),(\d+)\]\S*\s+convolution\(", line)]
           for reached in bodies.values()]
    (gram,) = [o for o in ops if o]      # the solve's loops hold none
    assert len(gram) == len(strategies.gram_blocks(k))
    assert 0.5 < sum(gram) / (2 * rows * k * k) <= 0.66
    assert [dims for _, dims in _arrays_written(text, rows)
            if max(d for d in dims if d != rows) > 1] == []


def test_linreg_rhs_rides_the_gram(linreg_program):
    """t(X)·y has no loop of its own (PR 34): the plan says the pair,
    the last block column's convolution is 233 wide (the table's 232
    columns and y's, selected into the slice inside the fusion), no
    multiply-reduce runs over a panel, and beside y's own slice of a
    panel no array of a panel's length is written or staged (a
    ``concatenate`` of the slice and y is fused as well, but stages the
    232 columns first: ``dynamic-slice f32[8192,232]``). As the chip
    lays the tables y rides the KERNEL (PR 55): its column is the
    custom call's second operand, through a bitcast as well (the rows
    of t(y) fill the ragged last block's spare rows), and no second
    n-shaped pass — no loop, no multiply-reduce — is left."""
    plan, compiled, _, kernel = linreg_program
    assert [(p.get("gram_rides"), p.get("rides_gram"))
            for p in plan.meta["products"]] == [
        (1, None), (None, True), (None, None)]
    text = compiled.as_text()
    if kernel:
        (call,) = [ln for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        _, column = re.search(
            r"custom-call\(%([\w.\-]+), %([\w.\-]+)\)", call).groups()
        assert re.search(
            rf"%{re.escape(column)} = f32\[1,{LINREG_N}\]\S* "
            r"bitcast\(%args_[\w.]+\)", text)
        assert "multiply_reduce" not in text and " while(" not in "".join(
            ln for ln in text.splitlines() if f"[{LINREG_N}," in ln)
        return
    rows, k = strategies.ACC_PANEL_ROWS, LINREG_K
    bodies, lines = _loop_bodies(text)
    (body,) = [reached for reached in bodies.values()
               if any(" convolution(" in line
                      for name in reached for line in lines[name])]
    in_loop = [line for name in body for line in lines[name]]
    start, end = strategies.gram_blocks(k)[-1]
    assert [int(w) for line in in_loop for w in re.findall(
        rf"= f32\[{k},(\d+)\]\S*\s+convolution\(", line)] \
        == [end - start + 1] == [233]
    assert not [line for line in in_loop if " reduce(" in line]
    assert "multiply_reduce" not in text
    staged = [dims for _, dims in _arrays_written(text, rows)]
    assert staged and all(sorted(dims) == [1, rows] for dims in staged)


# -- the WHOLE regression, by rows on the 2x2 mesh (cell linreg_10m_2x2) ------

WHOLE_N = 4 * LINREG_N


@pytest.fixture(scope="module")
def linreg_whole_program(mesh_2x2):
    """``inv(t(X) * X) * t(X) * y`` over all 10,223,616 rows, X and y cut
    by rows over the four devices (``P(('x', 'y'), None)``: 2,555,904
    whole rows a chip), as the session plans and lowers it, compiled for
    the described v5e 2x2."""
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession
    by_rows = P(("x", "y"), None)
    rows = NamedSharding(mesh_2x2, by_rows)
    sess = MatrelSession(mesh=mesh_2x2)
    for name, shape in (("X", (WHOLE_N, LINREG_K)), ("y", (WHOLE_N, 1))):
        sess.register(name, BlockMatrix.from_array(
            _sds(rows, shape, jnp.float32), shape, mesh_2x2, by_rows))
    plan = sess.compile(sess.sql("inv(t(X) * X) * t(X) * y"))
    compiled = plan.jitted.lower(*[
        _sds(rows, leaf.attrs["matrix"].shape, jnp.float32)
        for leaf in plan.leaf_order]).compile()
    return plan, compiled


def test_whole_linreg_fits_a_chip_beside_its_quarter(linreg_whole_program):
    """Every chip holds its 10.2 GB of rows and megabytes beside them:
    the program's temporaries stay inside what the plan reckons for the
    Gram's accumulators, the all-reduce's result, Xᵀy and the solve's
    copies, and arguments, output and temporaries fit the budget, at
    what ``linreg_10m_1c`` plans for one chip (60.6% of ``bytes_limit``)
    and a few megabytes."""
    plan, compiled = linreg_whole_program
    mem = compiled.memory_analysis()
    n, k = LINREG_N, LINREG_K
    residents = n * k * 4 + n * 4            # a device's rows of X and y
    assert mem.argument_size_in_bytes == residents
    reckoned = plan.meta["hbm_plan_bytes"] - residents
    assert 0 < reckoned <= 16 * k * k        # megabytes, not a table
    assert mem.temp_size_in_bytes <= reckoned
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes <= MatrelConfig().hbm_budget_bytes


def test_whole_linreg_reduces_once_and_moves_no_table(linreg_whole_program):
    """ONE all-reduce (the block columns' accumulators, Xᵀy's column
    riding the last) and no other collective: no all-gather of X, no
    collective-permute, nothing scattered; no array with a device's
    2,555,904 or the table's 10,223,616 rows is written beside y's own
    column (no transposed or re-laid X); one loop over the panels, the
    block triangle's four convolutions in it; and the solve has no
    collective inside it (it would be a second one)."""
    plan, compiled = linreg_whole_program
    text = compiled.as_text()
    assert [p["chosen"] for p in plan.meta["products"]] == [
        "cpmm_rows", "cpmm_rows", "solve"]
    gram = plan.meta["products"][0]
    assert (gram["operand_layout"], gram["devices"], gram["rows_a_device"],
            gram["gram_tiles"], gram["gram_rides"], gram["reduce_bytes"]) \
        == ("row", 4, LINREG_N, [10, 16], 1,
            strategies.gram_reduce_bytes(LINREG_K, 1))
    counts = {op: len(re.findall(rf"\s{op}(?:-start)?\(", text))
              for op in ("all-reduce", "all-gather", "collective-permute",
                         "reduce-scatter", "all-to-all")}
    assert counts == {"all-reduce": 1, "all-gather": 0,
                      "collective-permute": 0, "reduce-scatter": 0,
                      "all-to-all": 0}, counts
    for rows in (LINREG_N, WHOLE_N):
        written = _arrays_written(text, rows)
        assert all(int(np.prod(dims)) == rows for _, dims in written), \
            written
    loops = [ln for ln in text.splitlines()
             if " while(" in ln and f"f32[{LINREG_N},{LINREG_K}]" in ln]
    assert len(loops) == 1, loops
    bodies, lines = _loop_bodies(text)
    convs = [[line for name in reached for line in lines[name]
              if " convolution(" in line] for reached in bodies.values()]
    assert sorted(len(c) for c in convs if c) \
        == [len(strategies.gram_blocks(LINREG_K))]


# -- the fused chain t(X) * (w .* (X * v)), PR 54 -----------------------------
#
# Cell ``linregcg_10m_1c``: the regression cells' table, one chain a
# round of LinearRegCG. The kernel takes the table as it lies (the long
# dimension on the lanes): its ``x.T`` is a bitcast and the program
# holds ONE table.


def _mmchain_args(one_chip, major_to_minor, weighted):
    from jax.experimental.layout import Format, Layout
    lie = Format(Layout(major_to_minor=major_to_minor), one_chip)
    args = [_sds(lie, (LINREG_N, LINREG_K), jnp.float32),
            _sds(one_chip, (LINREG_K, 1), jnp.float32)]
    if weighted:
        args.append(_sds(one_chip, (LINREG_N, 1), jnp.float32))
    return args


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["t(X)(Xv)", "t(X)(w.Xv)"])
def test_mmchain_reads_one_table_where_it_lies(one_chip, weighted):
    """``matrel_mmchain`` at the cell's shapes, compiled by Mosaic for
    the described v5e: the table its only large argument (10.2 GB, under
    12: no transposed or re-laid copy), temporaries the (1000, 128)
    lanes of partial sums and ``v``'s broadcast, one kernel."""
    from matrel_tpu.ops import mmchain as mmchain_lib
    tile = mmchain_lib.tile_rows(LINREG_N)
    assert tile == 2048 and LINREG_N % tile == 0        # no ragged tail
    compiled = _compile(
        jax.jit(lambda *a: mmchain_lib.mmchain(*a, tile=tile)),
        *_mmchain_args(one_chip, (1, 0), weighted))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "matrel_mmchain" in text
    mem = compiled.memory_analysis()
    taken = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    table = LINREG_N * LINREG_K * 4
    assert table <= taken < 12e9
    assert mem.temp_size_in_bytes < 4 * LINREG_K * 128 * 4
    written = _arrays_written(text, LINREG_N)
    assert all(int(np.prod(dims)) == LINREG_N for _, dims in written), \
        written
    # the transposed view of the table is a bitcast, not a copy
    assert re.search(rf"f32\[{LINREG_K},{LINREG_N}\]\S* bitcast\(", text)


def test_mmchain_of_a_row_major_table_would_copy_it(one_chip):
    """Why ``planner.mmchain_plan`` declines a table that lies by rows
    (``why_not`` layout): the kernel's transpose is then a second table,
    which the described chip refuses or counts."""
    from matrel_tpu.ops import mmchain as mmchain_lib
    try:
        compiled = jax.jit(lambda *a: mmchain_lib.mmchain(
            *a, tile=2048)).lower(
            *_mmchain_args(one_chip, (0, 1), False)).compile()
    except Exception as e:  # noqa: BLE001 — the refusal is the result
        assert "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower()
        return
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0.9 * LINREG_N * LINREG_K * 4


def test_linregcg_chain_statement_as_the_session_plans_it(topo,
                                                          monkeypatch):
    """``t(X) * (X * p) + p * lam`` through ``session.sql`` + ``compile``
    on ONE described v5e: the rule fires, the planner says one read (the
    test stands in for the chip where the program asks the backend and
    the array: ``on_tpu``, how the described table lies), the reckoned
    peak is what the compiled program takes, and nothing N-shaped is
    written."""
    from jax.experimental.layout import Format, Layout
    from matrel_tpu import config as config_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.parallel import planner
    from matrel_tpu.session import MatrelSession
    monkeypatch.setattr(config_lib, "on_tpu", lambda: True)
    monkeypatch.setattr(planner, "_lies_by_columns", lambda leaf: True)
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object).reshape(1, 1),
                ("x", "y"))
    whole = NamedSharding(mesh, P(None, None))
    sess = MatrelSession(mesh=mesh, config=MatrelConfig())
    for name, shape in (("X", (LINREG_N, LINREG_K)), ("p", (LINREG_K, 1)),
                        ("lam", (1, 1))):
        sess.register(name, BlockMatrix.from_array(
            _sds(whole, shape, jnp.float32), shape, mesh, P(None, None)))
    plan = sess.compile(sess.sql("t(X) * (X * p) + p * lam"))
    (rec,) = plan.meta["mmchain"]
    assert rec == {"rows": LINREG_N, "cols": LINREG_K, "weighted": False,
                   "tile_rows": 2048, "one_read": True,
                   "bytes_read": LINREG_N * LINREG_K * 4}
    assert plan.meta["executors"] == ["pallas_mmchain", "xla"]
    lie = Format(Layout(major_to_minor=(1, 0)), whole)
    compiled = plan.jitted.lower(*[
        _sds(lie if leaf.shape[0] == LINREG_N else whole,
             leaf.attrs["matrix"].shape, jnp.float32)
        for leaf in plan.leaf_order]).compile()
    text = compiled.as_text()
    assert "matrel_mmchain" in text
    mem = compiled.memory_analysis()
    taken = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert taken <= plan.meta["hbm_plan_bytes"] * 1.001 < 12e9
    assert not _arrays_written(text, LINREG_N)


# -- the sliding window's programs (PR 56) ------------------------------------
# executor.rows_update and executor.rows_patch at the shapes of cell
# linreg_window_10m_1c: a batch of 8,192 rows replaces rows of the
# table where it lies (rows on the lanes, as the chip lays it), and the
# two views are corrected from the batch, the rows that left and the
# partner's same rows. ONE table: the update's output IS its argument.

WINDOW_BATCH = 8192


def _window_args(one_chip, cols):
    from jax.experimental.layout import Format, Layout
    lie = Format(Layout(major_to_minor=(1, 0)), one_chip)
    return (_sds(lie, (LINREG_N, cols), jnp.float32),
            _sds(one_chip, (WINDOW_BATCH, cols), jnp.float32),
            _sds(one_chip, (), jnp.int32))


@pytest.mark.parametrize("cols", [LINREG_K, 1], ids=["X", "y"])
def test_window_update_overwrites_the_table_in_place(one_chip, cols):
    """The donated table is aliased to the output whole, nothing
    table-sized is allocated beside it (temporaries: none), and the
    program holds one table, under 12 GB."""
    from matrel_tpu import executor
    compiled = executor.rows_update(True).lower(
        *_window_args(one_chip, cols)).compile()
    mem = compiled.memory_analysis()
    table = LINREG_N * cols * 4
    assert mem.alias_size_in_bytes == table
    assert mem.temp_size_in_bytes < WINDOW_BATCH * 1024 * 4
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert table <= held < min(12e9, table + 4 * WINDOW_BATCH * 1024 * 4
                               + (1 << 20))
    assert "dynamic-update-slice" in compiled.as_text()


@pytest.mark.parametrize("form,view,partner", [
    ("gram", (LINREG_K, LINREG_K), None),
    ("left", (LINREG_K, 1), (LINREG_N, 1)),
    ("right", (LINREG_K, 1), (LINREG_N, LINREG_K))])
def test_window_patches_touch_a_batch_of_rows(one_chip, form, view,
                                              partner):
    """A view's patch at the cell's shapes: its two words donated and
    aliased, the partner's table an argument that is sliced where it
    lies (no temporary of its size), the correction a contraction over
    8,192 rows."""
    from jax.experimental.layout import Format, Layout
    from matrel_tpu import executor
    lie = Format(Layout(major_to_minor=(1, 0)), one_chip)
    mine = LINREG_K if form != "right" else 1
    rows = _sds(one_chip, (WINDOW_BATCH, mine), jnp.float32)
    theirs = rows if partner is None else _sds(lie, partner, jnp.float32)
    word = _sds(one_chip, view, jnp.float32)
    compiled = executor.rows_patch(form, True, MatrelConfig()).lower(
        word, word, rows, rows, theirs, _sds(one_chip, (), jnp.int32)
    ).compile()
    mem = compiled.memory_analysis()
    # both words, as the chip pads them to its tiles
    assert 2 * view[0] * view[1] * 4 <= mem.alias_size_in_bytes \
        <= 2 * view[0] * 1024 * 4
    assert mem.temp_size_in_bytes < 64 << 20
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 12e9
    if partner is None:
        # the upper block triangle of each Gram: 4 + 4 dots of 8,192
        # rows, and the whole program well under a tenth of a refit
        cycles = sum(int(c) for c in re.findall(
            r'"estimated_cycles":"(\d+)"', compiled.as_text()))
        assert 0 < cycles / 1.45e9 < 0.010
