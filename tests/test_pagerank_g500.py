"""PageRank on a skewed (Graph500 Kronecker) graph, PR 33: the chunks
layout of ``build_spmv_plan``, the chunk-grid Pallas scatter, the
panelled matvec and the byte-reckoned gate, at Kronecker scale 10-12
with Pallas interpreted, against ``pagerank_reference_edges`` (LDBC
Graphalytics' equation in float64). PR 36: the hub chunks beside them
(at this scale a full hub table would hold every source, so the tests
of the chunks themselves build without one, ``no_hubs``, and the hub
tests with a table of two rows, ``two_hub_rows``)."""

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matrel_tpu import config as config_lib
from matrel_tpu.ops import pallas_spmv as pc
from matrel_tpu.ops import spmv as spmv_lib
from matrel_tpu.utils import native
from matrel_tpu.workloads import pagerank as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INITIATOR = (0.57, 0.19, 0.19)


@pytest.fixture(scope="module")
def g500():
    from benchmarks import run as harness
    return harness.load_module(os.path.join(
        ROOT, "benchmarks", "configs", "ldbc_graphalytics_g500_22.py"))


@pytest.fixture(scope="module")
def graphs(g500):
    """scale -> (src, dst, vertices): both directions of every edge."""
    out = {}
    for scale in (10, 12):
        lo, hi, v = g500.kronecker_graph(scale, 16, INITIATOR, 1)
        src, dst = g500.directed_in_seed_order(lo, hi, 2147483999)
        out[scale] = (src, dst, v)
    return out


@pytest.fixture
def no_hubs(monkeypatch):
    """Every edge in the plan's own chunks, as before PR 36."""
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 0)


@pytest.fixture
def two_hub_rows(monkeypatch):
    """A hub table of 256 sources: of a scale-12 graph's 3,335 they hold
    about half the edges, so both sets of chunks have work."""
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 2)


def _variant(graphs, kind):
    """The undirected graph, or a directed one with dangling vertices:
    every edge in one direction only, so the vertices that only ever
    appear as the larger endpoint have no out-edge."""
    src, dst, v = graphs[12]
    if kind == "directed_with_dangling":
        keep = src < dst
        src, dst = src[keep], dst[keep]
        assert (np.bincount(src, minlength=v) == 0).sum() > 100
    return src, dst, v


# -- the generator ------------------------------------------------------------


def test_generator_keeps_ldbcs_clean_up(g500):
    lo, hi, v = g500.kronecker_graph(12, 16, INITIATOR, 1)
    assert lo.dtype == np.int32 and np.all(lo < hi)           # no self-loop
    key = lo.astype(np.int64) << 32 | hi
    assert np.all(np.diff(key) > 0)                # sorted, no duplicate
    assert np.array_equal(np.unique(np.concatenate([lo, hi])),
                          np.arange(v))            # no isolated vertex
    assert 0.7 * 4096 < v < 4096 and 0.6 * 65536 < lo.size < 65536
    src, dst = g500.directed_in_seed_order(lo, hi, 7)
    assert src.dtype == dst.dtype == np.int32 and src.size == 2 * lo.size
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert len(fwd) == src.size                    # symmetric: (u, v) and
    assert all((d, s) in fwd for s, d in list(fwd)[:2000])         # (v, u)
    # the same graph whatever the order; another order for another seed
    other = g500.directed_in_seed_order(lo, hi, 8)
    assert set(zip(other[0].tolist(), other[1].tolist())) == fwd
    assert not np.array_equal(other[0], src)
    again = g500.directed_in_seed_order(lo, hi, 7)
    assert np.array_equal(again[0], src) and np.array_equal(again[1], dst)


def test_generator_draws_the_initiators_quadrants(g500, monkeypatch):
    """Before the labels are permuted, bit b of (i, j) falls into the
    quadrants with probabilities A, B, C, D: read off the top bit of an
    unpermuted draw (the permutation replaced by the identity)."""
    monkeypatch.setattr(jax.random, "permutation",
                        lambda key, n: jnp.arange(n))
    scale = 10
    lo, hi, v = g500.kronecker_graph(scale, 64, INITIATOR, 3)
    # an undirected edge (lo, hi): both top bits 0 is quadrant A; both 1
    # is D; one of each is B or C. Duplicates are dropped, which thins
    # the dense quadrant A most, so hold the order and D's rarity only.
    top = v // 2
    a = np.mean((lo < top) & (hi < top))
    d = np.mean((lo >= top) & (hi >= top))
    bc = 1.0 - a - d
    assert a > bc > d and d < 0.12
    deg = np.bincount(np.concatenate([lo, hi]), minlength=v)
    assert deg.max() > 20 * np.median(deg)          # skewed


# -- the layout ---------------------------------------------------------------


def _blocks_rule(cnt):
    """The parent's capacity rule: the 0.995 quantile of the non-empty
    blocks' edge counts, up to a multiple of 128."""
    q = int(np.quantile(cnt[cnt > 0], 0.995))
    return max(128, -(-q // 128) * 128)


def test_uniform_graph_keeps_the_blocks_layout(rng):
    """``layout="auto"`` on a uniform graph past the small-plan
    threshold: the parent's plan, shape and tables."""
    n, m = 120_000, 1_500_000
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    auto = spmv_lib.build_spmv_plan(dst, src, n_rows=n, n_cols=n,
                                    layout="auto")
    blocks = spmv_lib.build_spmv_plan(dst, src, n_rows=n, n_cols=n)
    cnt = np.bincount(dst // 512, minlength=-(-n // 512))
    assert auto.chunk_block is None and blocks.chunk_block is None
    assert auto.src8.shape == (cnt.size, _blocks_rule(cnt))
    assert auto.src8.size > spmv_lib._SMALL_PLAN_SLOTS
    for name in ("src8", "lane", "off", "val"):
        np.testing.assert_array_equal(getattr(auto, name),
                                      getattr(blocks, name))
    assert auto.padding_ratio == blocks.padding_ratio < 1.2


def test_auto_lays_a_skewed_graph_in_chunks(graphs, monkeypatch, no_hubs):
    src, dst, v = graphs[12]
    # at this size every plan is "small": the threshold is the only
    # thing that keeps a test-sized skewed graph in blocks
    assert spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v,
                                    layout="auto").chunk_block is None
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)
    plan = spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v,
                                    layout="auto")
    blocks = spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v)
    assert plan.chunk_block is not None and plan.overflow == ()
    assert plan.src8.size < blocks.src8.size
    assert plan.padding_ratio < 1.10 < blocks.padding_ratio


def test_chunks_layout_tables(graphs, no_hubs):
    src, dst, v = graphs[12]
    plan = spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v,
                                    layout="chunks")
    cnt = np.bincount(dst // 512, minlength=-(-v // 512))
    owned = np.maximum(-(-cnt // spmv_lib.CHUNK), 1)
    assert plan.capacity == spmv_lib.CHUNK
    assert plan.src8.shape == (owned.sum(), spmv_lib.CHUNK)
    assert plan.chunk_block.dtype == np.int32
    np.testing.assert_array_equal(plan.chunk_block,
                                  np.repeat(np.arange(cnt.size), owned))
    assert owned.max() >= 3                     # a hub block: many chunks
    assert plan.overflow == () and plan.ov_rows is None
    # every edge lies in a chunk of its own block, once
    real = plan.val != 0
    assert real.sum() == src.size
    full = plan.src8.astype(np.int64) * spmv_lib.WIDTH + plan.lane
    rows = plan.chunk_block[:, None] * 512 + plan.off
    got = np.stack([rows[real], full[real]], 1)
    want = np.stack([dst, src], 1).astype(np.int64)
    assert np.array_equal(got[np.lexsort(got.T[::-1])],
                          want[np.lexsort(want.T[::-1])])
    assert np.all(full[~real] == v)              # padded slots: sentinel


def test_hub_chunks_hold_the_edges_of_the_largest_sources(graphs,
                                                          two_hub_rows):
    src, dst, v = graphs[12]
    plan = spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v,
                                    layout="chunks")
    hub = plan.hubs
    deg = np.bincount(src, minlength=v)
    np.testing.assert_array_equal(
        hub.ids, np.argsort(-deg, kind="stable")[:2 * spmv_lib.HUB_ROW])
    assert hub.ids.dtype == hub.idx.dtype == hub.chunk_block.dtype == np.int32
    assert hub.idx.shape == hub.off.shape == hub.val.shape \
        == (hub.chunk_block.size, spmv_lib.CHUNK)
    # a block owns the hub chunks its hub edges need: none without any
    is_hub = np.isin(src, hub.ids)
    assert 0.3 < is_hub.mean() < 0.7
    cnt = np.bincount(dst[is_hub] // 512, minlength=-(-v // 512))
    np.testing.assert_array_equal(
        hub.chunk_block, np.repeat(np.arange(cnt.size),
                                   -(-cnt // spmv_lib.CHUNK)))
    # every edge lies in exactly one of the two sets, in a chunk of its
    # own block; a padded hub slot names no table row
    real, hub_real = plan.val != 0, hub.val != 0
    assert real.sum() + hub_real.sum() == src.size
    assert plan.padding_ratio == (plan.val.size + hub.val.size) / src.size
    full = plan.src8.astype(np.int64) * spmv_lib.WIDTH + plan.lane
    got = np.concatenate([
        np.stack([(plan.chunk_block[:, None] * 512 + plan.off)[real],
                  full[real]], 1),
        np.stack([(hub.chunk_block[:, None] * 512 + hub.off)[hub_real],
                  hub.ids[hub.idx[hub_real]]], 1)])
    want = np.stack([dst, src], 1).astype(np.int64)
    assert np.array_equal(got[np.lexsort(got.T[::-1])],
                          want[np.lexsort(want.T[::-1])])
    assert np.all(hub.idx[~hub_real] == hub.ids.size)
    assert not np.isin(full[real], hub.ids).any()


def test_a_block_without_hub_edges_keeps_the_main_sums(monkeypatch):
    """Blocks 0 and 2 take edges from the one table row (column 0 with
    300 edges, and columns 1..127, which win their ties by the smaller
    id), block 1 only from columns outside it: it owns no hub chunk, and
    the hub kernel, which starts from the main scatter's sums, leaves
    its tile as that left it."""
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", 1)
    monkeypatch.setattr(spmv_lib, "_HUB_ROW_EDGES", 0)  # so few edges pay
    rows = np.concatenate([np.arange(150), 1024 + np.arange(150),
                           200 + np.arange(127), 512 + np.arange(40)])
    cols = np.concatenate([np.zeros(300, np.int64), 1 + np.arange(127),
                           300 + np.arange(40)])
    plan = spmv_lib.build_spmv_plan(rows, cols, n_rows=1536, n_cols=400,
                                    layout="chunks")
    np.testing.assert_array_equal(plan.hubs.ids, np.arange(spmv_lib.HUB_ROW))
    np.testing.assert_array_equal(plan.hubs.chunk_block, [0, 2])
    np.testing.assert_array_equal(plan.chunk_block, [0, 1, 2])
    assert (plan.val != 0).sum() == 40
    x = np.arange(1, 401, dtype=np.float32)
    y = pc.spmv_compact(plan, jnp.asarray(x), interpret=True)
    want = np.zeros(1536, np.float32)
    want[rows] = x[cols]
    np.testing.assert_array_equal(np.asarray(y), want)


def test_an_empty_block_owns_one_chunk():
    rows = np.array([5, 5, 2000], np.int64)      # blocks 1 and 2 empty
    plan = spmv_lib.build_spmv_plan(rows, np.array([0, 1, 2]),
                                    n_rows=2048, n_cols=3, layout="chunks")
    np.testing.assert_array_equal(plan.chunk_block, [0, 1, 2, 3])
    y = pc.spmv_compact(plan, jnp.asarray([1.0, 2.0, 4.0]), interpret=True)
    want = np.zeros(2048, np.float32)
    want[5], want[2000] = 3.0, 4.0
    np.testing.assert_array_equal(np.asarray(y), want)


@pytest.mark.parametrize("hub_rows", [0, 2], ids=["no_hubs", "two_hub_rows"])
@pytest.mark.parametrize("weighted", [False, True])
def test_native_and_numpy_fills_agree_on_chunks(graphs, monkeypatch, rng,
                                                weighted, hub_rows):
    if native.spmv_counts(np.zeros(1, np.int64), 512, 1) is None:
        pytest.skip("native library unavailable")
    src, dst, v = graphs[12]
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", hub_rows)
    vals = rng.random(src.size).astype(np.float32) if weighted else None
    nat = spmv_lib.build_spmv_plan(dst, src, vals, v, v, layout="chunks")
    monkeypatch.setattr(native, "spmv_counts", lambda *a: None)
    ref = spmv_lib.build_spmv_plan(dst, src, vals, v, v, layout="chunks")
    np.testing.assert_array_equal(nat.chunk_block, ref.chunk_block)
    assert nat.src8.shape == ref.src8.shape
    # slot order within a block: the main chunks lie by row in both,
    # beside hub chunks too (PR 38; PR 51): the same tables; and the hub
    # chunks lie by table row into registers (PR 42) and by row inside a
    # register (PR 51), stable, in both: the same tables and the same
    # walks
    for name in ("src8", "lane", "off", "val"):
        np.testing.assert_array_equal(getattr(nat, name),
                                      getattr(ref, name), err_msg=name)
    assert (nat.hubs is not None) == (ref.hubs is not None) == bool(hub_rows)
    if hub_rows:        # the library splits the edges in one walk, numpy
        # takes each set out of the list: the same hubs, chunks and slots
        np.testing.assert_array_equal(nat.hubs.ids, ref.hubs.ids)
        np.testing.assert_array_equal(nat.hubs.chunk_block,
                                      ref.hubs.chunk_block)
        np.testing.assert_array_equal((nat.hubs.val != 0).sum(1),
                                      (ref.hubs.val != 0).sum(1))
        for name in ("idx", "off", "val", "first", "rows"):
            np.testing.assert_array_equal(getattr(nat.hubs, name),
                                          getattr(ref.hubs, name),
                                          err_msg=name)
    x = jnp.asarray(rng.random(v).astype(np.float32))
    a = np.asarray(pc.spmv_compact(nat, x, interpret=True))
    b = np.asarray(pc.spmv_compact(ref, x, interpret=True))
    np.testing.assert_allclose(a, b, rtol=2e-6, atol=0)


def test_native_ragged_fill_refuses_a_block_past_its_slots():
    if native.spmv_counts(np.zeros(1, np.int64), 512, 1) is None:
        pytest.skip("native library unavailable")
    rows = np.zeros(200, np.int64)
    first = np.array([0, 128], np.int64)         # 200 edges, 128 slots
    assert native.spmv_fill_ragged(rows, rows, None, 1, 512, first, 8) is None


def test_native_hub_fill_refuses_a_block_past_its_slots():
    if native.spmv_counts(np.zeros(1, np.int64), 512, 1) is None:
        pytest.skip("native library unavailable")
    rows = np.zeros(200, np.int64)
    cols = np.arange(200) % 2                    # column 1 is the hub
    rank = np.array([-1, 0], np.int32)
    np.testing.assert_array_equal(
        native.spmv_counts_hubs(rows, cols, rank, 512, 1), [100])
    room, tight = np.array([0, 128], np.int64), np.array([0, 64], np.int64)
    (src8, lane, off, val, *overflow), (idx, hub_off, hub_val) = \
        native.spmv_fill_ragged_hubs(rows, cols, None, rank, 128, 512, room,
                                     room, 8)
    assert [a.size for a in overflow] == [0, 0, 0]
    assert (val != 0).sum() == (hub_val != 0).sum() == 100
    assert set(idx[hub_val != 0]) == {0} and set(idx[hub_val == 0]) == {128}
    for first, hub_first in ((tight, room), (room, tight)):
        assert native.spmv_fill_ragged_hubs(rows, cols, None, rank, 128, 512,
                                            first, hub_first, 8) is None
    # a column past the rank table is out of range, not read
    assert native.spmv_counts_hubs(rows, cols + 1, rank, 512, 1) is None
    # nor a rank past the table the sort counts by (PR 42)
    assert native.spmv_fill_ragged_hubs(
        rows, cols, None, np.array([-1, 128], np.int32), 128, 512, room,
        room, 8) is None


# -- the matvec and the ranks ---------------------------------------------------


@pytest.mark.parametrize("kind", ["undirected", "directed_with_dangling"])
def test_chunked_pagerank_against_the_reference(graphs, kind):
    src, dst, v = _variant(graphs, kind)
    prepared = pr.prepare_pagerank_onehot(src, dst, v, layout="chunks")
    assert prepared[0].chunk_block is not None and not prepared[0].overflow
    got = np.asarray(pr.run_pagerank_compact(prepared, 10, 0.85, passes=3,
                                             interpret=True), np.float64)
    want = pr.pagerank_reference_edges(src, dst, v, 10, 0.85)
    assert abs(want.sum() - 1.0) < 1e-12
    assert np.max(np.abs(got - want)) / want.max() < 2e-6
    assert np.max(np.abs(got - want) / want) < 5e-6      # LDBC's, a vertex


def test_reference_is_graphalytics_equation():
    """Three vertices by hand: 0 -> 1, 0 -> 2, 1 -> 2; 2 dangles."""
    d, n = 0.85, 3
    r = np.full(n, 1 / 3)
    for _ in range(2):
        dang = r[2] / n
        r = (1 - d) / n + d * np.array([dang, r[0] / 2 + dang,
                                        r[0] / 2 + r[1] + dang])
    got = pr.pagerank_reference_edges([0, 0, 1], [1, 2, 2], n, 2, d)
    np.testing.assert_allclose(got, r, rtol=1e-15)


def test_chunked_matvec_matches_the_blocks_layout(graphs, rng):
    src, dst, v = graphs[10]
    vals = rng.standard_normal(src.size).astype(np.float32)
    x = jnp.asarray(rng.standard_normal(v).astype(np.float32))
    chunks = spmv_lib.build_spmv_plan(dst, src, vals, v, v, layout="chunks")
    blocks = spmv_lib.build_spmv_plan(dst, src, vals, v, v,
                                      capacity_quantile=1.0)
    assert not blocks.overflow
    a = np.asarray(pc.spmv_compact(chunks, x, interpret=True))
    b = np.asarray(pc.spmv_compact(blocks, x, interpret=True))
    scale = np.abs(b).max()
    assert np.abs(a - b).max() / scale < 1e-6


def _small_device(monkeypatch, panel_slots):
    """A device so small that a panel holds ``panel_slots``: through the
    config's HBM budget, which the panel count is reckoned from."""
    budget = int(panel_slots * pc._TEMP_BYTES_A_SLOT / pc._PANEL_SHARE) + 1
    monkeypatch.setattr(config_lib, "_default_config",
                        config_lib.MatrelConfig(hbm_budget_bytes=budget))


@pytest.mark.parametrize("layout", ["chunks", "blocks", "chunks_and_hubs"])
def test_panelled_matvec_is_the_unpanelled_one_bit_for_bit(
        graphs, monkeypatch, rng, layout):
    src, dst, v = graphs[12]
    # the hub chunks take no part in the panels: 5 main chunks a panel
    # of 29, or of the 20 left beside a hub table of two rows
    monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX",
                        2 if layout == "chunks_and_hubs" else 0)
    plan = spmv_lib.build_spmv_plan(dst, src, None, v, v,
                                    layout=layout.split("_")[0])
    assert (plan.hubs is not None) == (layout == "chunks_and_hubs")
    x = jnp.asarray(rng.random(v).astype(np.float32))
    rows, cap = plan.src8.shape
    assert pc.panel_rows(rows, cap) == rows
    whole = np.asarray(pc.spmv_compact(plan, x, interpret=True))
    # 5 chunks (or 2 blocks) a panel: a last panel moved back to overlap
    per = 2 if layout == "blocks" else 5 if layout == "chunks" else 3
    assert rows % per
    _small_device(monkeypatch, per * cap)
    assert pc.panel_rows(rows, cap) == per
    pc._compact_jitted.clear_cache()      # the panel count is the trace's
    panelled = np.asarray(pc.spmv_compact(plan, x, interpret=True))
    pc._compact_jitted.clear_cache()
    np.testing.assert_array_equal(panelled.view(np.uint32),
                                  whole.view(np.uint32))


def test_panel_rows_and_plan_bytes_follow_the_device(monkeypatch):
    """The g500-22 plan without hub chunks on a v5e: 64,976 chunks of
    2,048 slots; a quarter of 15.5 GiB at 224 B a slot holds 9,069 of
    them, so 8 panels, of 8,122 each, up to 8,128, a multiple of 64 (48
    chunks gathered twice). With hub chunks (PR 36) 45,033 are left: 5
    panels of 9,007, a prime, which XLA tiles a row at a time: 9,024."""
    rows, cap = 64_976, 2048
    per = pc.panel_rows(rows, cap)
    assert int(0.25 * (31 << 29) // (224 * 2048)) == 9069
    assert -(-rows // 8) == 8122 and per == 8128 and 8 * per - rows == 48
    assert pc.panel_rows(45_033, cap) == 9024 and -(-45_033 // 5) == 9007
    assert pc.panel_rows(9069 * 3 - 1, cap) == 9069     # no room to round
    assert pc.plan_bytes(rows, cap) == 17 * rows * cap + 224 * per * cap
    assert pc.plan_bytes(rows, cap) < 0.5 * (31 << 29)
    # the uniform 1M-node plan: one panel
    assert pc.panel_rows(1954, 5376) == 1954
    _small_device(monkeypatch, 1)
    assert pc.panel_rows(rows, cap) == 1          # never less than a row


# -- pagerank_edges, the gate and the cache -------------------------------------


@pytest.fixture
def compact_auto(monkeypatch):
    """pagerank_edges(impl="auto") as the TPU answers it: the compact
    executor (Pallas interpreted), every plan past the small-plan
    threshold, an empty cache."""
    monkeypatch.setattr(config_lib, "_default_config",
                        config_lib.MatrelConfig(pallas_interpret=True))
    monkeypatch.setattr(pr, "on_tpu", lambda: True)
    monkeypatch.setattr(pr, "_PLAN_CACHE", [])
    monkeypatch.setattr(spmv_lib, "_SMALL_PLAN_SLOTS", 0)


def test_pagerank_edges_auto_answers_a_skewed_graph_in_chunks(
        graphs, compact_auto, no_hubs):
    src, dst, v = graphs[12]
    before = pr.path_counts()["compact"]
    got = np.asarray(pr.pagerank_edges(src, dst, v, rounds=10, alpha=0.85),
                     np.float64)
    assert pr.path_counts()["compact"] == before + 1
    said = pr.last_plan()
    assert said["impl"] == "compact" and said["hit"] is False
    assert said["layout"] == "chunks" and said["overflow_edges"] == 0
    assert said["edges"] == src.size and said["chunk"] == spmv_lib.CHUNK
    assert said["slots"] == said["chunks"] * said["chunk"]
    assert said["slots"] / said["edges"] < 1.10
    assert said["row_values"] == 2 and said["panels"] == 1
    assert said["plan_bytes"] == pc.plan_bytes(said["chunks"], said["chunk"])
    assert said["build_s"] >= 0 and said["upload_s"] >= 0
    want = pr.pagerank_reference_edges(src, dst, v, 10, 0.85)
    assert np.max(np.abs(got - want) / want) < 5e-6
    # the same graph again: the cached plan, by comparison
    again = np.asarray(pr.pagerank_edges(src, dst, v, rounds=10, alpha=0.85))
    hit = pr.last_plan()
    assert hit["hit"] is True and len(pr._PLAN_CACHE) == 1
    assert (said["recognised"], hit["recognised"]) == ("compared_first",
                                                       "confirmed")
    told_once = ("hit", "recognised", "build_s", "upload_s")
    assert {k: hit[k] for k in said if k not in told_once} \
        == {k: said[k] for k in said if k not in told_once}
    np.testing.assert_array_equal(again, got.astype(np.float32))


@pytest.mark.parametrize("hub_rows", [2, None],
                         ids=["two_hub_rows", "as_chosen"])
def test_pagerank_edges_reports_the_hub_table(graphs, compact_auto,
                                              monkeypatch, hub_rows):
    """``last_plan()`` says what the build chose, on a build as on a
    hit, and the ranks hold the Graph500 configuration's two limits. At
    this scale the table the code chooses unpatched has 18 rows of the
    3,335 sources' 27: 17 pay, one more pays its entries of ``x[ids]``
    in the walk step they open."""
    src, dst, v = graphs[12]
    if hub_rows:
        monkeypatch.setattr(spmv_lib, "_HUB_ROWS_MAX", hub_rows)
    got = np.asarray(pr.pagerank_edges(src, dst, v, rounds=10, alpha=0.85),
                     np.float64)
    said = pr.last_plan()
    assert said["impl"] == "compact" and said["layout"] == "chunks"
    assert said["hubs"] == spmv_lib.HUB_ROW * (hub_rows or 18)
    assert said["hub_slots"] == said["hub_chunks"] * said["chunk"] > 0
    assert said["slots"] == (said["chunks"] + said["hub_chunks"]) \
        * said["chunk"]
    # PR 42: the table rows the hub chunks' registers walk a matvec: a
    # step for each of a chunk's two registers at the least, and (the
    # slots lie by table row) about the table once a block beside that,
    # not once a register
    step = spmv_lib.HUB_WALK
    blocks = -(-v // 512)
    table_rows = spmv_lib.hub_table_rows(said["hubs"] // 128)
    assert 2 * step * said["hub_chunks"] <= said["hub_walk_rows"] <= (
        blocks * table_rows + 2 * step * 2 * said["hub_chunks"])
    assert said["overflow_edges"] == 0 and said["edges"] == src.size
    assert said["plan_bytes"] == pc.plan_bytes(
        said["chunks"], said["chunk"], said["hub_slots"]) \
        == pc.plan_bytes(said["chunks"], said["chunk"]) \
        + 12 * said["hub_slots"]
    if not hub_rows:            # a main chunk a block, all but empty
        assert said["chunks"] == -(-v // 512)
    want = pr.pagerank_reference_edges(src, dst, v, 10, 0.85)
    assert np.max(np.abs(got - want)) / want.max() < 3e-6
    assert np.max(np.abs(got - want) / want) < 5e-6
    # cached at its own price: 17 B a main slot, 12 a hub slot
    assert [e.cost for e in pr._PLAN_CACHE] == [
        17 * said["chunks"] * said["chunk"] + 12 * said["hub_slots"]]
    again = np.asarray(pr.pagerank_edges(src, dst, v, rounds=10, alpha=0.85))
    hit = pr.last_plan()
    assert hit["hit"] is True
    counters = ("hubs", "hub_slots", "hub_chunks", "hub_walk_rows")
    assert [hit[k] for k in counters] == [said[k] for k in counters]
    np.testing.assert_array_equal(again, got.astype(np.float32))


def test_a_compact_plan_is_cached_at_its_own_price(graphs, compact_auto,
                                                   monkeypatch, no_hubs):
    """Counted as expanded tables (224 B a slot) this plan would pass
    the budget and be rebuilt in every call; at 17 B a slot it stays."""
    src, dst, v = graphs[10]
    slots = spmv_lib.build_spmv_plan(dst, src, n_rows=v, n_cols=v,
                                     layout="auto").src8.size
    monkeypatch.setattr(pr, "_PLAN_CACHE_MAX_BYTES", 20 * slots)
    pr.pagerank_edges(src, dst, v, rounds=2)
    assert [e.cost for e in pr._PLAN_CACHE] == [17 * slots]
    pr.pagerank_edges(src, dst, v, rounds=2)
    assert pr.last_plan()["hit"] is True
    # the expanded executor's plan of the same graph does not fit
    monkeypatch.setattr(config_lib, "_default_config",
                        config_lib.MatrelConfig(use_pallas=False))
    pr.pagerank_edges(src, dst, v, rounds=2, impl="onehot")
    assert pr.last_plan()["impl"] == "onehot" and len(pr._PLAN_CACHE) == 1


def test_auto_gate_is_reckoned_from_the_device(monkeypatch):
    monkeypatch.setattr(config_lib, "_default_config",
                        config_lib.MatrelConfig(pallas_interpret=True))
    limit = 31 << 29                              # the config's budget
    assert pr._auto_max_slots() == int(0.25 * limit // 17)
    assert 133_200_000 < pr._auto_max_slots() < 382_000_000   # g500-22: in
    monkeypatch.setattr(config_lib, "_default_config",       # blocks: out
                        config_lib.MatrelConfig(use_pallas=False))
    assert pr._auto_max_slots() == pr._PLAN_CACHE_MAX_SLOTS


@pytest.mark.parametrize("reason", ["bytes", "padding"])
def test_fallback_warning_names_the_refusal(graphs, compact_auto, caplog,
                                            monkeypatch, reason):
    src, dst, v = graphs[10]
    if reason == "bytes":
        monkeypatch.setattr(pr, "_auto_max_slots", lambda: 1000)
    else:       # an edge a block over a huge row space: 128 slots an edge
        v = 512 * 9000
        src = np.arange(9000) * 512
        dst = src[::-1].copy()
    before = pr.path_counts()["segment"]
    with caplog.at_level(logging.WARNING, logger="matrel_tpu.pagerank"):
        got = np.asarray(pr.pagerank_edges(src, dst, v, rounds=3), np.float64)
    assert pr.path_counts()["segment"] == before + 1
    assert pr.last_plan() == {"impl": "segment"}
    text = caplog.text
    assert f"({reason}: the " in text and "segment-sum path" in text
    assert ("padding" if reason == "bytes" else "bytes") not in text
    want = pr.pagerank_reference_edges(src, dst, v, 3, 0.85)
    assert np.max(np.abs(got - want)) / want.max() < 1e-5


# -- who takes the chunks layout, and who says not --------------------------------


@pytest.fixture(scope="module")
def chunked_plan(graphs):
    src, dst, v = graphs[10]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spmv_lib, "_HUB_ROWS_MAX", 0)
        return spmv_lib.build_spmv_plan(dst, src, None, v, v,
                                        layout="chunks")


@pytest.mark.parametrize("who", ["expanded", "spmm", "shard_plan",
                                 "sharded_compact"])
def test_executors_of_the_blocks_layout_refuse_chunks_by_name(
        chunked_plan, mesh8, who):
    plan = chunked_plan
    x = jnp.ones((plan.n_cols,), jnp.float32)
    with pytest.raises(ValueError, match="take only the blocks layout"):
        if who == "expanded":
            spmv_lib.spmv(plan, x)
        elif who == "spmm":
            spmv_lib.spmm(plan, jnp.ones((plan.n_cols, 2), jnp.float32))
        elif who == "shard_plan":
            spmv_lib.shard_plan(plan, mesh8)
        else:
            pc.spmv_compact_sharded(plan, x, mesh8, interpret=True)


def test_the_k_wide_compact_product_takes_chunks(chunked_plan, graphs, rng):
    """Since PR 37 the k-wide kernel walks chunks too (it refused them
    by name before): the same plan, two columns, against the dense
    product."""
    src, dst, v = graphs[10]
    X = rng.random((v, 2)).astype(np.float32)
    dense = np.zeros((v, v))
    np.add.at(dense, (dst, src), 1.0)
    got = np.asarray(pc.spmm_compact(chunked_plan, jnp.asarray(X),
                                     interpret=True), np.float64)
    want = dense @ X
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-6


def test_sharded_pagerank_keeps_the_blocks_layout(graphs, compact_auto,
                                                  mesh8):
    """A mesh's executors walk blocks: their build never asks for
    chunks, whatever the graph."""
    src, dst, v = graphs[10]
    got = np.asarray(pr.pagerank_edges(src, dst, v, rounds=4, mesh=mesh8),
                     np.float64)
    assert pr.last_plan()["impl"] == "compact_sharded"
    assert pr.last_plan()["layout"] == "blocks"
    want = pr.pagerank_reference_edges(src, dst, v, 4, 0.85)
    assert np.max(np.abs(got - want)) / want.max() < 1e-5


def test_save_and_load_keep_the_chunks(chunked_plan, tmp_path, rng):
    path = str(tmp_path / "plan.npz")
    spmv_lib.save_plan(path, chunked_plan)
    with np.load(path) as z:
        assert int(z["meta"][4]) == 2            # a version-1 reader stops
    loaded = spmv_lib.load_plan(path)
    np.testing.assert_array_equal(loaded.chunk_block,
                                  chunked_plan.chunk_block)
    x = jnp.asarray(rng.random(chunked_plan.n_cols).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pc.spmv_compact(loaded, x, interpret=True)),
        np.asarray(pc.spmv_compact(chunked_plan, x, interpret=True)))
    # the blocks layout still writes version 1
    blocks = spmv_lib.build_spmv_plan(np.array([3, 9]), np.array([0, 1]),
                                      n_rows=16, n_cols=2)
    spmv_lib.save_plan(path, blocks)
    with np.load(path) as z:
        assert int(z["meta"][4]) == 1 and "chunk_block" not in z.files
    assert spmv_lib.load_plan(path).chunk_block is None


def test_save_and_load_keep_the_hub_chunks(graphs, two_hub_rows, tmp_path,
                                           rng):
    src, dst, v = graphs[10]
    plan = spmv_lib.build_spmv_plan(dst, src, None, v, v, layout="chunks")
    assert plan.hubs is not None
    path = str(tmp_path / "plan.npz")
    spmv_lib.save_plan(path, plan)
    with np.load(path) as z:
        assert int(z["meta"][4]) == 3            # a version-2 reader stops
        payload = {k: z[k] for k in z.files}
    # the walks are not in the file: they are reckoned from its slots
    assert not {"hub_first", "hub_rows"} & set(payload)
    loaded = spmv_lib.load_plan(path)
    fields = ("ids", "idx", "off", "val", "chunk_block", "first", "rows")
    for name in fields:
        np.testing.assert_array_equal(getattr(loaded.hubs, name),
                                      getattr(plan.hubs, name))
    x = jnp.asarray(rng.random(v).astype(np.float32))
    want = np.asarray(pc.spmv_compact(plan, x, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(pc.spmv_compact(loaded, x, interpret=True)), want)
    # whatever order those lie in: a file of PR 36 (input order) loads
    # with the walks that order needs, and runs
    perm = np.argsort(rng.random(plan.hubs.idx.shape), axis=1)
    for name in ("hub_idx", "hub_off", "hub_val"):
        payload[name] = np.take_along_axis(payload[name], perm, axis=1)
    np.savez_compressed(path, **payload)
    old = spmv_lib.load_plan(path)
    np.testing.assert_array_equal(old.hubs.idx, payload["hub_idx"])
    assert old.hubs.rows.shape == plan.hubs.rows.shape
    assert old.hubs.rows.sum() >= plan.hubs.rows.sum()
    np.testing.assert_allclose(
        np.asarray(pc.spmv_compact(old, x, interpret=True)), want,
        rtol=2e-6, atol=0)


def test_unknown_layout_is_refused():
    with pytest.raises(ValueError, match="unknown layout"):
        spmv_lib.build_spmv_plan(np.array([0]), np.array([0]), layout="rows")
