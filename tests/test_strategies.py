"""Physical-strategy tests: each of BMM/CPMM/RMM/SUMMA must (a) match the
numpy oracle on a real multi-device mesh and (b) lower to the collectives
its reference analogue implies — the HLO-inspection analogue of the
reference's Catalyst plan assertions (SURVEY.md §4 "plan shape")."""

import jax
import numpy as np
import pytest

from matrel_tpu.config import MatrelConfig
from matrel_tpu.core.blockmatrix import BlockMatrix
from matrel_tpu.ir.expr import leaf, matmul
from matrel_tpu.parallel import planner, strategies
from matrel_tpu import executor


def _run(strategy, a, b, mesh):
    A = BlockMatrix.from_numpy(a, mesh=mesh)
    B = BlockMatrix.from_numpy(b, mesh=mesh)
    f = jax.jit(lambda x, y: strategies.run_matmul(strategy, x, y, mesh, None))
    out = np.asarray(f(A.data, B.data))
    return out[: a.shape[0], : b.shape[1]]


ALL = ["bmm_left", "bmm_right", "cpmm", "rmm", "xla"]


@pytest.mark.parametrize("strategy", ALL)
def test_strategy_numerics_2x4(strategy, mesh8, rng):
    a = rng.standard_normal((16, 24)).astype(np.float32)
    b = rng.standard_normal((24, 32)).astype(np.float32)
    np.testing.assert_allclose(_run(strategy, a, b, mesh8), a @ b,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ALL + ["summa"])
def test_strategy_numerics_square_mesh(strategy, mesh_square, rng):
    a = rng.standard_normal((12, 20)).astype(np.float32)
    b = rng.standard_normal((20, 8)).astype(np.float32)
    np.testing.assert_allclose(_run(strategy, a, b, mesh_square), a @ b,
                               rtol=1e-4, atol=1e-4)


def test_summa_on_rect_mesh_falls_back(mesh8, rng):
    a = rng.standard_normal((16, 16)).astype(np.float32)
    b = rng.standard_normal((16, 16)).astype(np.float32)
    np.testing.assert_allclose(_run("summa", a, b, mesh8), a @ b,
                               rtol=1e-4, atol=1e-4)


class TestHloCollectives:
    """CPMM must reduce-scatter; RMM must all-gather with no reduce-scatter;
    SUMMA must ride a ppermute ring (collective-permute)."""

    def _hlo(self, strategy, mesh, shape=(16, 16)):
        a = BlockMatrix.random(shape, mesh=mesh, seed=0)
        b = BlockMatrix.random(shape, mesh=mesh, seed=1)
        f = jax.jit(lambda x, y: strategies.run_matmul(strategy, x, y, mesh, None))
        return f.lower(a.data, b.data).compile().as_text()

    def test_cpmm_reduce_scatter(self, mesh8):
        hlo = self._hlo("cpmm", mesh8)
        assert "reduce-scatter" in hlo

    def test_rmm_all_gather_only(self, mesh8):
        hlo = self._hlo("rmm", mesh8)
        assert "all-gather" in hlo
        assert "reduce-scatter" not in hlo

    def test_summa_collective_permute(self, mesh_square):
        hlo = self._hlo("summa", mesh_square)
        assert "collective-permute" in hlo

    def test_bmm_no_execution_collectives_after_reshard(self, mesh8):
        # BMM: the only comm is the input broadcast (all-gather of B);
        # no reduce-scatter / collective-permute anywhere.
        hlo = self._hlo("bmm_right", mesh8)
        assert "reduce-scatter" not in hlo
        assert "collective-permute" not in hlo


class TestPlannerChoice:
    def _mk(self, n, k, m, mesh, nnz_a=None, nnz_b=None):
        """Planner only reads shapes/stats, so fabricate metadata-true,
        data-tiny leaves: a small zero matrix with an overridden shape."""
        import dataclasses
        a_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh)
        b_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh)
        a = dataclasses.replace(a_small, shape=(n, k), nnz=nnz_a)
        b = dataclasses.replace(b_small, shape=(k, m), nnz=nnz_b)
        return matmul(leaf(a), leaf(b))

    def test_small_rhs_broadcasts(self, mesh8):
        # Classic BMM case: big side already row-partitioned (co-partitioned
        # input — zero shuffle of it), tiny RHS broadcast. The reference's
        # canonical broadcast-join situation.
        import dataclasses
        from jax.sharding import PartitionSpec as P
        a_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh8,
            spec=P(("x", "y"), None))
        b_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh8)
        a = dataclasses.replace(a_small, shape=(100_000, 512))
        b = dataclasses.replace(b_small, shape=(512, 64))
        node = matmul(leaf(a), leaf(b))
        assert planner.choose_strategy(node, mesh8) == "bmm_right"

    def test_2d_input_large_output_prefers_cpmm_over_bmm(self, mesh8):
        # With A in canonical 2D layout, broadcasting would pay to reshard
        # the big side row-wise; CPMM leaves A in place and reduce-scatters
        # the (smaller) output — the cost model must see that.
        node = self._mk(100_000, 512, 64, mesh8)
        assert planner.choose_strategy(node, mesh8) == "cpmm"

    def test_large_contraction_uses_cpmm(self, mesh8):
        cfg = MatrelConfig(broadcast_threshold_bytes=1024)
        node = self._mk(4096, 65536, 4096, mesh8)
        assert planner.choose_strategy(node, mesh8, cfg) == "cpmm"

    def test_square_large_not_bmm(self, mesh8):
        cfg = MatrelConfig(broadcast_threshold_bytes=1024)
        s = planner.choose_strategy(self._mk(8192, 8192, 8192, mesh8), mesh8, cfg)
        assert s in ("rmm", "cpmm", "summa")

    def test_single_device_is_xla(self):
        import jax as j
        from matrel_tpu.core import mesh as mesh_lib
        m1 = mesh_lib.make_mesh((1, 1), devices=j.devices()[:1])
        node = self._mk(1024, 1024, 1024, m1)
        assert planner.choose_strategy(node, m1) == "xla"

    def test_override(self, mesh8):
        cfg = MatrelConfig(strategy_override="rmm")
        node = self._mk(512, 512, 512, mesh8)
        assert planner.choose_strategy(node, mesh8, cfg) == "rmm"

    def test_annotation_recorded_in_plan(self, mesh8):
        node = self._mk(100_000, 512, 64, mesh8)
        plan = executor.compile_expr(node, mesh8)
        assert "strategy" in plan.optimized.attrs


def test_compiled_plan_collectives_summary(mesh8):
    import dataclasses
    cfg = MatrelConfig(broadcast_threshold_bytes=1024, strategy_override="cpmm")
    a = BlockMatrix.random((64, 64), mesh=mesh8, seed=0)
    b = BlockMatrix.random((64, 64), mesh=mesh8, seed=1)
    plan = executor.compile_expr(matmul(leaf(a), leaf(b)), mesh8, cfg)
    cols = plan.collectives()
    assert cols.get("reduce-scatter", 0) >= 1
    assert "strategy=cpmm" in plan.explain()


class TestBmmLeft:
    def test_bmm_left_hlo_no_reduce_scatter(self, mesh8):
        # near-symmetric pin to the bmm_right HLO test: with the LEFT
        # operand replicated there is no contraction-time
        # reduce-scatter. (B's 2d→col reshard MAY lower to a
        # collective-permute — input movement, not execution comm — so
        # only the reduce-scatter absence is pinned.)
        import jax
        rng = np.random.default_rng(3)
        a = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32), mesh=mesh8)
        b = BlockMatrix.from_numpy(
            rng.standard_normal((16, 16)).astype(np.float32), mesh=mesh8)
        f = jax.jit(lambda x, y: strategies.run_matmul(
            "bmm_left", x, y, mesh8, MatrelConfig()))
        hlo = f.lower(a.data, b.data).compile().as_text()
        assert "reduce-scatter" not in hlo
        got = np.asarray(f(a.data, b.data))[:16, :16]
        np.testing.assert_allclose(got, a.to_numpy() @ b.to_numpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_small_lhs_broadcasts_left(self, mesh8):
        # mirror of test_small_rhs_broadcasts: tiny LEFT operand against
        # a big col-partitioned RHS → the planner must flip to bmm_left
        import dataclasses
        from jax.sharding import PartitionSpec as P
        a_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh8)
        b_small = BlockMatrix.from_numpy(
            np.zeros((8, 8), dtype=np.float32), mesh=mesh8,
            spec=P(None, ("x", "y")))
        a = dataclasses.replace(a_small, shape=(64, 512))
        b = dataclasses.replace(b_small, shape=(512, 100_000))
        node = matmul(leaf(a), leaf(b))
        assert planner.choose_strategy(node, mesh8) == "bmm_left"


def _fab(mesh, n, m, spec=None):
    """Metadata-true, data-tiny leaf (see TestPlannerChoice._mk)."""
    import dataclasses
    small = BlockMatrix.from_numpy(np.zeros((8, 8), dtype=np.float32),
                                   mesh=mesh, spec=spec)
    return leaf(dataclasses.replace(small, shape=(n, m)))


class TestLayoutInference:
    """infer_layout (VERDICT r4 "what's missing" #2): the bottom-up
    layout pass mirroring the executor's actual sharding behaviour, so
    the co-partitioning credit reaches INTERIOR nodes — the analogue of
    the reference's partitioner-aware planning (SURVEY.md §2
    "Partitioners")."""

    def test_leaf_layouts(self, mesh8):
        from jax.sharding import PartitionSpec as P
        assert planner.infer_layout(
            _fab(mesh8, 64, 64), mesh8) == "2d"
        assert planner.infer_layout(
            _fab(mesh8, 64, 64, spec=P(("x", "y"), None)), mesh8) == "row"
        assert planner.infer_layout(
            _fab(mesh8, 64, 64, spec=P(None, ("x", "y"))), mesh8) == "col"
        assert planner.infer_layout(
            _fab(mesh8, 64, 64, spec=P(None, None)), mesh8) == "rep"

    def test_matmul_layout_follows_strategy(self, mesh8):
        # the strategies' shard_map out_specs (strategies.py): bmm_right
        # emits P((x,y), None), bmm_left P(None, (x,y)), the rest P(x,y)
        node = matmul(_fab(mesh8, 64, 64), _fab(mesh8, 64, 64))
        for strat, want in (("bmm_right", "row"), ("bmm_left", "col"),
                            ("cpmm", "2d"), ("rmm", "2d"),
                            ("summa", "2d"), ("xla", "2d")):
            stamped = node.with_attrs(strategy=strat)
            assert planner.infer_layout(stamped, mesh8) == want, strat
        # un-annotated: conservative 2d
        assert planner.infer_layout(node, mesh8) == "2d"

    def test_transpose_swaps_elemwise_preserves(self, mesh8):
        from jax.sharding import PartitionSpec as P
        from matrel_tpu.ir.expr import elemwise, scalar_op, transpose
        row = _fab(mesh8, 64, 64, spec=P(("x", "y"), None))
        rep = _fab(mesh8, 64, 64, spec=P(None, None))
        two_d = _fab(mesh8, 64, 64)
        assert planner.infer_layout(transpose(row), mesh8) == "col"
        assert planner.infer_layout(
            transpose(transpose(row)), mesh8) == "row"
        assert planner.infer_layout(
            scalar_op("mul", row, 2.0), mesh8) == "row"
        assert planner.infer_layout(
            elemwise("add", row, row), mesh8) == "row"
        # one replicated operand: XLA computes on the other's layout
        assert planner.infer_layout(
            elemwise("add", row, rep), mesh8) == "row"
        # disagreeing layouts: conservative 2d
        assert planner.infer_layout(
            elemwise("add", row, two_d), mesh8) == "2d"

    def test_agg_layouts(self, mesh8):
        from jax.sharding import PartitionSpec as P
        from matrel_tpu.ir.expr import agg
        row = _fab(mesh8, 64, 64, spec=P(("x", "y"), None))
        assert planner.infer_layout(agg(row, "sum", "all"), mesh8) == "rep"
        assert planner.infer_layout(agg(row, "sum", "row"), mesh8) == "row"
        assert planner.infer_layout(agg(row, "sum", "col"), mesh8) == "2d"

    def test_align_join_layout(self, mesh8):
        from matrel_tpu.relational import ops as R
        a = BlockMatrix.random((64, 8), mesh=mesh8, seed=0)
        b = BlockMatrix.random((64, 8), mesh=mesh8, seed=1)
        je = R.join_on_rows(a, b, "mul").with_attrs(replicate="align")
        assert planner.infer_layout(je, mesh8) == "row"
        jl = R.join_on_rows(a, b, "mul").with_attrs(replicate="left")
        # left replicated -> output inherits the kept (right) side: 2d
        assert planner.infer_layout(jl, mesh8) == "2d"


class TestInteriorLayoutCredit:
    """The round-5 flip tests: a producer's output layout changes its
    consumer's pick (chain interior) and a join consumes a bmm output's
    layout in place."""

    # shapes tuned for the (2,4) grid: with the producer's output
    # assumed canonical-2D the model picks cpmm/rmm for the outer
    # multiply (bmm_right pays an extra a/8 * 3/4 reshard); with the
    # producer KNOWN row-sharded that reshard is free and bmm_right
    # wins (7b/8 = 0.875 MB vs 0.969 MB for cpmm/rmm at these dims)
    N, K, M = 1152, 512, 512

    def test_interior_pick_flips_on_producer_layout(self, mesh8):
        inner = matmul(_fab(mesh8, self.N, self.K),
                       _fab(mesh8, self.K, self.K))
        outer_ctl = matmul(inner.with_attrs(strategy="rmm"),
                           _fab(mesh8, self.K, self.M))
        outer_row = matmul(inner.with_attrs(strategy="bmm_right"),
                           _fab(mesh8, self.K, self.M))
        ctl = planner.choose_strategy(outer_ctl, mesh8)
        got = planner.choose_strategy(outer_row, mesh8)
        assert ctl in ("cpmm", "rmm"), ctl
        assert got == "bmm_right", got

    def test_end_to_end_chain_credit(self, mesh8):
        # no planted strategies: A row-sharded makes the inner multiply
        # bmm_right naturally, and its row-sharded OUTPUT then flips the
        # outer multiply to bmm_right too — the credit firing on an
        # interior node through annotate_strategies. N2 puts k/n in the
        # band where bmm survives the ROOT canonical-output reshard
        # charge too (1/4 < k/n < 3/8 on the (2,4) grid)
        from jax.sharding import PartitionSpec as P
        N2 = 1600
        a = _fab(mesh8, N2, self.K, spec=P(("x", "y"), None))
        chain = matmul(matmul(a, _fab(mesh8, self.K, self.K)),
                       _fab(mesh8, self.K, self.M))
        ann = planner.annotate_strategies(chain, mesh8)
        assert ann.children[0].attrs["strategy"] == "bmm_right"
        assert ann.attrs["strategy"] == "bmm_right"

    def test_join_consumes_interior_bmm_output(self, mesh8):
        # join_rows(bmm_right output, small 2d): with the producer
        # assumed 2D the align scheme pays to re-lay BOTH operands and
        # replicating the small side wins; with the producer KNOWN
        # row-sharded its reshard term is zero and align wins
        from matrel_tpu.relational import ops as R
        inner = matmul(_fab(mesh8, self.N, self.K),
                       _fab(mesh8, self.K, self.K))
        other = _fab(mesh8, self.N, 32)
        j_ctl = R.join_on_rows(inner.with_attrs(strategy="rmm"), other,
                               "mul")
        j_row = R.join_on_rows(inner.with_attrs(strategy="bmm_right"),
                               other, "mul")
        assert planner.choose_join_scheme(j_ctl, mesh8) == "right"
        assert planner.choose_join_scheme(j_row, mesh8) == "align"


class TestConsumerAwareJoinTiebreak:
    """VERDICT r4 #7: among near-tie schemes, prefer the one whose
    output layout the PARENT consumes in place."""

    def test_matmul_parent_flips_zero_cost_tie_to_align(self, mesh8):
        # both operands replicated: left/right/align all cost 0. A
        # standalone join resolves the tie to "left" (argmin order);
        # under a matmul parent the hint ("row" for its left operand)
        # picks align, whose row-sharded output bmm_right consumes free
        from jax.sharding import PartitionSpec as P
        from matrel_tpu.relational import ops as R
        a = _fab(mesh8, 64, 8, spec=P(None, None))
        b = _fab(mesh8, 64, 4, spec=P(None, None))
        je = R.join_on_rows(a, b, "mul")
        standalone = planner.annotate_strategies(je, mesh8)
        assert standalone.attrs["replicate"] == "left"
        consumed = planner.annotate_strategies(
            matmul(R.join_on_rows(a, b, "mul"), _fab(mesh8, 32, 16)),
            mesh8)
        assert consumed.children[0].attrs["replicate"] == "align"

    def test_hint_never_overrides_clear_winner(self, mesh8):
        # a >10% cost gap must ignore the hint: big 2d left operand vs
        # tiny right — replicating the tiny side wins outright even
        # under a matmul parent
        from matrel_tpu.relational import ops as R
        big = _fab(mesh8, 4096, 512)
        tiny = _fab(mesh8, 4096, 1, spec=None)
        node = matmul(R.join_on_rows(big, tiny, "mul"),
                      _fab(mesh8, 512, 16))
        ann = planner.annotate_strategies(node, mesh8)
        assert ann.children[0].attrs["replicate"] == "right"


class TestAutotuneLayoutGate:
    """VERDICT r4 "what's missing" #3: the measured table is consulted
    only for canonically-2D operands — the layouts it measures. A
    non-2D operand falls back to the byte model's per-layout credit."""

    def _planted(self, mesh, tmp_path, node):
        import json
        from matrel_tpu.parallel import autotune
        from matrel_tpu.core import mesh as mesh_lib
        gx, gy = mesh_lib.mesh_grid_shape(mesh)
        path = str(tmp_path / "tuned.json")
        json.dump({autotune._table_key(64, gx, gy, "float32"):
                   {"best": "rmm", "times": {"rmm": 1e-6}}},
                  open(path, "w"))
        autotune._CACHE.clear()
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        return planner.choose_strategy_ex(node, mesh, cfg)

    def test_2d_operands_consult_table(self, mesh8, tmp_path):
        node = matmul(_fab(mesh8, 64, 64), _fab(mesh8, 64, 64))
        strat, source = self._planted(mesh8, tmp_path, node)
        assert (strat, source) == ("rmm", "measured")

    def test_row_sharded_operand_skips_table(self, mesh8, tmp_path):
        from jax.sharding import PartitionSpec as P
        node = matmul(_fab(mesh8, 64, 64, spec=P(("x", "y"), None)),
                      _fab(mesh8, 64, 64))
        _, source = self._planted(mesh8, tmp_path, node)
        assert source == "model"

    def test_interior_bmm_output_skips_table(self, mesh8, tmp_path):
        inner = matmul(_fab(mesh8, 64, 64),
                       _fab(mesh8, 64, 64)).with_attrs(
                           strategy="bmm_right")
        node = matmul(inner, _fab(mesh8, 64, 64))
        _, source = self._planted(mesh8, tmp_path, node)
        assert source == "model"


def test_explain_prints_interior_layouts(mesh8):
    # observability: the physical EXPLAIN shows infer_layout's verdicts
    # next to the strategy provenance they drive
    from jax.sharding import PartitionSpec as P
    import dataclasses
    rng = np.random.default_rng(7)
    a = BlockMatrix.from_numpy(
        rng.standard_normal((64, 16)).astype(np.float32), mesh=mesh8,
        spec=P(("x", "y"), None))
    b = BlockMatrix.from_numpy(
        rng.standard_normal((16, 16)).astype(np.float32), mesh=mesh8)
    node = matmul(leaf(a), leaf(b)).with_attrs(strategy="bmm_right",
                                               strategy_source="model")
    plan = executor.compile_expr(node, mesh8)
    text = plan.explain()
    assert "layout=row" in text          # the row-sharded leaf AND the
    assert "strategy=bmm_right" in text  # bmm output both annotated


def test_infer_layout_matches_compiled_output_shardings(mesh8):
    # the ground-truth pin for infer_layout's matmul rule: classify the
    # REAL compiled output sharding of every strategy and compare with
    # the planner's claim (summa needs a square grid — covered by the
    # mapping test at the out_specs level)
    a = BlockMatrix.random((16, 16), mesh=mesh8, seed=0)
    b = BlockMatrix.random((16, 16), mesh=mesh8, seed=1)
    node = matmul(leaf(a), leaf(b))

    def classify(spec):
        row = spec[0] if len(spec) > 0 else None
        col = spec[1] if len(spec) > 1 else None
        flat = ("x", "y")
        if row in (flat, ("y", "x")) and col is None:
            return "row"
        if row is None and col in (flat, ("y", "x")):
            return "col"
        if row is None and col is None:
            return "rep"
        return "2d"

    for s in strategies.STRATEGIES:
        if s == "summa":
            continue
        f = jax.jit(lambda x, y, s=s: strategies.run_matmul(
            s, x, y, mesh8, None))
        (out,) = f.lower(a.data, b.data).compile().output_shardings,
        got = classify(out.spec)
        want = planner.infer_layout(node.with_attrs(strategy=s), mesh8)
        assert got == want, (s, out.spec, want)


class TestLayoutOtherAndCooRep:
    """Review r5 follow-ups: partial shardings classify as "other" (real
    placements the autotune table never measured), and the COO matmul's
    "rep" claim holds only where the lowering pins it."""

    def test_partial_sharding_is_other_not_2d(self, mesh8):
        from jax.sharding import PartitionSpec as P
        # P(x, None) on a matrix whose canonical spec is P(x, y): a real
        # non-canonical placement
        n = _fab(mesh8, 64, 64, spec=P("x", None))
        assert planner.infer_layout(n, mesh8) == "other"
        # but P(x, None) IS canonical for a column vector — still "2d"
        v = _fab(mesh8, 64, 1, spec=P("x", None))
        assert planner.infer_layout(v, mesh8) == "2d"

    def test_other_layout_skips_measured_winner(self, mesh8, tmp_path):
        import json
        from jax.sharding import PartitionSpec as P
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        json.dump({autotune._table_key(64, 2, 4, "float32"):
                   {"best": "rmm", "times": {"rmm": 1e-6}}},
                  open(path, "w"))
        autotune._CACHE.clear()
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        node = matmul(_fab(mesh8, 64, 64, spec=P("x", None)),
                      _fab(mesh8, 64, 64))
        _, source = planner.choose_strategy_ex(node, mesh8, cfg)
        assert source == "model"

    def test_coo_rep_only_where_pinned(self, mesh8):
        from matrel_tpu.core.coo import COOMatrix
        rng = np.random.default_rng(0)
        A = COOMatrix.from_edges(rng.integers(0, 64, 100),
                                 rng.integers(0, 64, 100), shape=(64, 64))
        x = BlockMatrix.from_numpy(
            rng.standard_normal((64, 2)).astype(np.float32), mesh=mesh8)
        e = A.multiply(x.expr())
        # pallas interpret on: the compact sharded path (out_specs=P())
        # really runs -> "rep"
        cfg_p = MatrelConfig(pallas_interpret=True)
        assert planner.infer_layout(e, mesh8, config=cfg_p) == "rep"
        # pallas off on a multi-device mesh: expanded XLA path, GSPMD
        # decides -> no replication claim
        cfg_np = MatrelConfig(use_pallas=False)
        assert planner.infer_layout(e, mesh8, config=cfg_np) == "2d"
        # autotune on: a measured "expanded" winner could reroute the
        # dispatch onto the GSPMD-decided XLA path -> no claim either
        cfg_at = MatrelConfig(pallas_interpret=True, autotune=True)
        assert planner.infer_layout(e, mesh8, config=cfg_at) == "2d"


class TestRootOutputReshardTerm:
    """Round 5: the executor re-lays ROOT outputs to the canonical
    sharding (Lowerer.lower_multi), so a root-level bmm pays a
    row/col->2d move the interior never does. The model charges it for
    the root only."""

    def test_root_pick_flips_away_from_bmm(self, mesh8):
        # k/n = 0.32 on the (2,4) grid: bmm_right wins as an interior
        # (7b/8 + 3a/32 beats rmm/cpmm) but the extra 3c/32 root charge
        # flips the ROOT pick to a 2d-emitting strategy
        node = matmul(_fab(mesh8, 1600, 512), _fab(mesh8, 512, 512))
        interior, _ = planner.choose_strategy_ex(node, mesh8)
        root, _ = planner.choose_strategy_ex(node, mesh8,
                                             root_output=True)
        assert interior == "bmm_right", interior
        assert root in ("rmm", "cpmm"), root

    def test_rootness_flows_through_entrywise_wrappers(self, mesh8):
        # a scalar wrapper does NOT shield the multiply from the root
        # charge (the canonical constraint re-lays the scalar's output,
        # whose layout is the multiply's); a consuming MATMUL does —
        # its own cost model sees the producer's layout instead
        from matrel_tpu.ir.expr import scalar_op
        inner = matmul(_fab(mesh8, 1600, 512), _fab(mesh8, 512, 512))
        wrapped = planner.annotate_strategies(
            scalar_op("mul", inner, 2.0), mesh8)
        assert wrapped.children[0].attrs["strategy"] in ("rmm", "cpmm")
        chain = planner.annotate_strategies(
            matmul(matmul(_fab(mesh8, 1600, 512),
                          _fab(mesh8, 512, 512)),
                   _fab(mesh8, 512, 64)), mesh8)
        assert chain.children[0].attrs["strategy"] == "bmm_right"


class TestReviewR5FollowUps:
    """Third review pass: plan-refusal honoured in the COO layout
    claim, transpose-swapped root charge, config-faithful EXPLAIN."""

    def test_coo_plan_refusal_drops_rep_claim(self, mesh8, monkeypatch):
        from matrel_tpu import executor as ex
        from matrel_tpu.core.coo import COOMatrix
        rng = np.random.default_rng(0)
        A = COOMatrix.from_edges(rng.integers(0, 64, 100),
                                 rng.integers(0, 64, 100), shape=(64, 64))
        x = BlockMatrix.from_numpy(
            rng.standard_normal((64, 2)).astype(np.float32), mesh=mesh8)
        e = A.multiply(x.expr())
        cfg = MatrelConfig(pallas_interpret=True)
        assert planner.infer_layout(e, mesh8, config=cfg) == "rep"
        # the executor refusing the plan (densify fallback, 2d output)
        # must drop the replication claim — the predicate is shared
        monkeypatch.setattr(ex, "_coo_dispatch_plan", lambda n: None)
        assert planner.infer_layout(e, mesh8, config=cfg) == "2d"

    def test_transpose_swaps_root_charge_axis(self, mesh8):
        # k/n = 512/1896 = 0.27 on the (2,4) grid: the row->2d re-lay
        # (factor 3/4) sinks bmm at a bare root, but under a root
        # TRANSPOSE the output arrives col-sharded and re-lays along
        # the cheaper axis (factor 1/2) — bmm survives
        from matrel_tpu.ir.expr import transpose
        bare = planner.annotate_strategies(
            matmul(_fab(mesh8, 1896, 512), _fab(mesh8, 512, 512)),
            mesh8)
        assert bare.attrs["strategy"] in ("rmm", "cpmm")
        under_t = planner.annotate_strategies(
            transpose(matmul(_fab(mesh8, 1896, 512),
                             _fab(mesh8, 512, 512))), mesh8)
        assert under_t.children[0].attrs["strategy"] == "bmm_right"

    def test_explain_uses_plan_config_for_layouts(self, mesh8):
        from matrel_tpu.core.coo import COOMatrix
        rng = np.random.default_rng(1)
        A = COOMatrix.from_edges(rng.integers(0, 64, 100),
                                 rng.integers(0, 64, 100), shape=(64, 64))
        x = BlockMatrix.from_numpy(
            rng.standard_normal((64, 2)).astype(np.float32), mesh=mesh8)
        cfg = MatrelConfig(pallas_interpret=True)
        plan = executor.compile_expr(A.multiply(x.expr()), mesh8, cfg)
        # the plan's config claims "rep" (compact sharded path); the
        # DEFAULT config on this CPU backend would claim nothing —
        # explain must print the planner's view, not default_config's
        assert "layout=rep" in plan.explain()


class TestSymmetricLayoutTerms:
    """Round 5: every comm_cost branch reads operand layouts, not just
    the bmm ones — a replicated operand gathers for free under rmm/cpmm
    too, and a 1D-sharded operand pays its way back to the 2D tiling
    cpmm consumes."""

    def test_replicated_A_flips_cpmm_to_rmm(self, mesh8):
        # big replicated A (over the bcast threshold, so bmm_left is
        # out), k > m: rmm's A-gather is now free and beats cpmm's
        # C reduce-scatter; with the old layout-blind rmm term cpmm won
        from jax.sharding import PartitionSpec as P
        cfg = MatrelConfig(broadcast_threshold_bytes=1024)
        a_rep = _fab(mesh8, 4096, 4096, spec=P(None, None))
        b = _fab(mesh8, 4096, 1024)
        got = planner.choose_strategy(matmul(a_rep, b), mesh8, cfg)
        assert got == "rmm", got
        ctl = planner.choose_strategy(
            matmul(_fab(mesh8, 4096, 4096), b), mesh8, cfg)
        assert ctl == "cpmm", ctl

    def test_row_sharded_A_charges_cpmm_relay(self, mesh8):
        # 3a/4 < c < a band on the (2,4) grid: cpmm wins for 2D A, but
        # a row-sharded A must pay its re-lay to P(x, y) and rmm takes
        # over (bmm excluded by the threshold)
        from jax.sharding import PartitionSpec as P
        cfg = MatrelConfig(broadcast_threshold_bytes=1024)
        b = _fab(mesh8, 1024, 896)
        ctl = planner.choose_strategy(
            matmul(_fab(mesh8, 8192, 1024), b), mesh8, cfg)
        assert ctl == "cpmm", ctl
        got = planner.choose_strategy(
            matmul(_fab(mesh8, 8192, 1024, spec=P(("x", "y"), None)), b),
            mesh8, cfg)
        assert got == "rmm", got


class TestConsumerAwareStrategyTiebreak:
    """The matmul analogue of the join-scheme tiebreak (round 5): a
    near-tied strategy pick flips toward the output layout the parent
    consumes in place."""

    def _inner(self, mesh, m):
        # (2048x512)·(512xm) on the (2,4) grid: at m=800 rmm beats
        # bmm_right by ~4% (within the tie band); at m=1024 by ~21%
        return matmul(_fab(mesh, 2048, 512), _fab(mesh, 512, m))

    def test_left_child_hint_flips_to_bmm_right(self, mesh8):
        standalone, _ = planner.choose_strategy_ex(self._inner(mesh8,
                                                               800),
                                                   mesh8)
        assert standalone == "rmm", standalone
        ann = planner.annotate_strategies(
            matmul(self._inner(mesh8, 800), _fab(mesh8, 800, 64)),
            mesh8)
        assert ann.children[0].attrs["strategy"] == "bmm_right"

    def test_hint_never_overrides_clear_winner(self, mesh8):
        ann = planner.annotate_strategies(
            matmul(self._inner(mesh8, 1024), _fab(mesh8, 1024, 64)),
            mesh8)
        assert ann.children[0].attrs["strategy"] == "rmm"


def test_hint_gated_by_parent_bmm_admissibility(mesh8):
    # review r5: a parent whose broadcast side exceeds the threshold
    # can never run the bmm that would consume the hinted layout — no
    # hint is emitted, so a near-tied child keeps its cheapest pick
    cfg = MatrelConfig(broadcast_threshold_bytes=1024)
    inner = matmul(_fab(mesh8, 2048, 512), _fab(mesh8, 512, 800))
    ann = planner.annotate_strategies(
        matmul(inner, _fab(mesh8, 800, 800)), mesh8, cfg)
    assert ann.children[0].attrs["strategy"] == "rmm"


def test_measured_bmm_winner_not_applied_at_root(mesh8, tmp_path):
    # review r5: autotune probes never pay the root canonical-output
    # re-lay, so a measured 1D-emitting winner doesn't cover the root
    # context — the model (which charges _root_reshard_cost) decides;
    # a 2d-emitting measured winner still applies at the root
    import json
    from matrel_tpu.parallel import autotune
    node = matmul(_fab(mesh8, 64, 64), _fab(mesh8, 64, 64))
    for planted, want_src in (("bmm_right", "model"), ("rmm", "measured")):
        path = str(tmp_path / f"t_{planted}.json")
        json.dump({autotune._table_key(64, 2, 4, "float32"):
                   {"best": planted, "times": {planted: 1e-6}}},
                  open(path, "w"))
        autotune._CACHE.clear()
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        _, src = planner.choose_strategy_ex(node, mesh8, cfg,
                                            root_output=True)
        assert src == want_src, (planted, src)
        _, src_int = planner.choose_strategy_ex(node, mesh8, cfg)
        assert src_int == "measured", planted   # interior: always applies


def test_no_hint_for_sparse_dispatch_parents(mesh8):
    # review r5: a parent matmul dispatching the COO SpMV path cannot
    # consume any hinted layout — no hint reaches its children
    from matrel_tpu.core.coo import COOMatrix
    rng = np.random.default_rng(0)
    A = COOMatrix.from_edges(rng.integers(0, 64, 100),
                             rng.integers(0, 64, 100), shape=(64, 64))
    parent = A.multiply(matmul(_fab(mesh8, 64, 32), _fab(mesh8, 32, 2)))
    assert planner._child_layout_hints(parent) == (None, None)
    dense = matmul(_fab(mesh8, 64, 64), _fab(mesh8, 64, 2))
    assert planner._child_layout_hints(dense) == ("row", "col")


def test_planner_works_with_custom_axis_names(rng):
    # robustness: nothing in the layout machinery may assume the
    # default ("x", "y") axis names — infer_layout, the strategies'
    # shard_map specs and the align lowering all read mesh.axis_names
    import jax
    from jax.sharding import PartitionSpec as P
    from matrel_tpu.core import mesh as mesh_lib
    mesh = mesh_lib.make_mesh((2, 4), axis_names=("rows", "cols"))
    a = rng.standard_normal((64, 32)).astype(np.float32)
    b = rng.standard_normal((32, 16)).astype(np.float32)
    A = BlockMatrix.from_numpy(a, mesh=mesh,
                               spec=P(("rows", "cols"), None))
    B = BlockMatrix.from_numpy(b, mesh=mesh)
    node = matmul(leaf(A), leaf(B))
    assert planner.infer_layout(node.children[0], mesh) == "row"
    ann = planner.annotate_strategies(node, mesh)
    assert "strategy" in ann.attrs
    plan = executor.compile_expr(node, mesh)
    np.testing.assert_allclose(plan.run().to_numpy(), a @ b,
                               rtol=1e-4, atol=1e-4)


# -- topology-weighted comm model (round 7) ---------------------------------


def _legacy_comm_cost(strategy, n, k, m, da, db, gx, gy, itemsize=4,
                      a_layout="2d", b_layout="2d", alpha_bytes=0.0):
    """VERBATIM copy of the pre-topology flat comm_cost — the round-7
    acceptance oracle: weights (1.0, 1.0) must reproduce these floats
    bit for bit (same closed forms, same summation order)."""
    def _b(shape, density, isz=4):
        return shape[0] * shape[1] * isz * max(density, 0.0)

    def _to2d(bytes_, layout):
        p_ = max(gx * gy, 1)
        if layout == "rep":
            return 0.0
        if layout == "row":
            return (bytes_ / p_) * (1 - 1 / gy)
        if layout == "col":
            return (bytes_ / p_) * (1 - 1 / gx)
        return 0.0

    a_bytes = _b((n, k), da, itemsize)
    b_bytes = _b((k, m), db, itemsize)
    c_bytes = _b((n, m), 1.0, itemsize)
    p = gx * gy

    def total(*terms, extra_steps=0):
        steps = sum(1 for t in terms if t > 0.0) + extra_steps
        return sum(terms) + alpha_bytes * steps

    if strategy == "bmm_right":
        bcast = 0.0 if b_layout == "rep" else b_bytes * (p - 1) / p
        reshard_a = (0.0 if a_layout in ("row", "rep")
                     else (a_bytes / p) * (1 - 1 / gy))
        return total(bcast, reshard_a)
    if strategy == "bmm_left":
        bcast = 0.0 if a_layout == "rep" else a_bytes * (p - 1) / p
        reshard_b = (0.0 if b_layout in ("col", "rep")
                     else (b_bytes / p) * (1 - 1 / gx))
        return total(bcast, reshard_b)
    if strategy == "cpmm":
        reshard_a = _to2d(a_bytes, a_layout)
        reshard_b = (0.0 if b_layout == "rep"
                     else (b_bytes / gy) * (gx - 1) / gx)
        rs_c = (c_bytes / gx) * (gy - 1) / gy
        return total(reshard_a, reshard_b, rs_c)
    if strategy in ("rmm", "xla"):
        ag_a = (0.0 if a_layout == "rep"
                else (a_bytes / gx) * (gy - 1) / gy)
        ag_b = (0.0 if b_layout == "rep"
                else (b_bytes / gy) * (gx - 1) / gx)
        return total(ag_a, ag_b)
    if strategy == "summa":
        g = max(gx, gy)
        ring = (a_bytes / p + b_bytes / p) * (g - 1)
        return ring + total(_to2d(a_bytes, a_layout),
                            _to2d(b_bytes, b_layout),
                            extra_steps=2 * (g - 1))
    if strategy == "spgemm":
        return 0.0
    raise ValueError(strategy)


class TestTopologyWeightedModel:
    """Round 7: per-axis inverse-bandwidth weights (core/mesh.
    MeshTopology) thread through every costing path — default weights
    are bit-identical to the flat model, non-uniform weights bill each
    collective leg on the axis it rides."""

    def test_default_weights_bit_identical_across_vocabulary(self):
        # the round-7 acceptance oracle: comm_cost at (1.0, 1.0) ==
        # the pre-topology flat model, EXACTLY, for every strategy x
        # shape x layout x grid x alpha on a grid of shapes
        rng = np.random.default_rng(23)
        layouts = ("2d", "row", "col", "rep", "other")
        for _ in range(50):
            n, k, m = (int(rng.integers(1, 3000)) for _ in range(3))
            da = float(rng.choice([1.0, 1.0, 0.3, 0.02]))
            db = float(rng.choice([1.0, 1.0, 0.3, 0.02]))
            gx, gy = [int(v) for v in
                      rng.choice([(1, 8), (8, 1), (2, 4), (4, 2),
                                  (2, 2), (4, 4)])]
            la = str(rng.choice(layouts))
            lb = str(rng.choice(layouts))
            al = float(rng.choice([0.0, 200_000.0]))
            for s in ("bmm_right", "bmm_left", "cpmm", "rmm", "xla",
                      "summa", "spgemm"):
                want = _legacy_comm_cost(s, n, k, m, da, db, gx, gy,
                                         a_layout=la, b_layout=lb,
                                         alpha_bytes=al)
                got = planner.comm_cost(s, n, k, m, da, db, gx, gy,
                                        a_layout=la, b_layout=lb,
                                        alpha_bytes=al,
                                        weights=(1.0, 1.0))
                assert got == want, (s, n, k, m, la, lb, gx, gy, al)

    def test_axes_decomposition_sums_to_flat_bill(self):
        # per-axis bytes are a DECOMPOSITION of the flat bill, not a
        # second model: x + y must equal the alpha-free flat cost
        rng = np.random.default_rng(29)
        for _ in range(30):
            n, k, m = (int(rng.integers(1, 2000)) for _ in range(3))
            gx, gy = [int(v) for v in
                      rng.choice([(2, 4), (4, 2), (2, 2), (1, 8)])]
            la = str(rng.choice(("2d", "row", "col", "rep")))
            lb = str(rng.choice(("2d", "row", "col", "rep")))
            for s in ("bmm_right", "bmm_left", "cpmm", "rmm", "summa"):
                flat = planner.comm_cost(s, n, k, m, 1.0, 1.0, gx, gy,
                                         a_layout=la, b_layout=lb)
                bx, by = planner.comm_cost_axes(
                    s, n, k, m, 1.0, 1.0, gx, gy,
                    a_layout=la, b_layout=lb)
                assert bx + by == pytest.approx(flat, rel=1e-12), \
                    (s, la, lb, gx, gy)

    def test_weighted_cost_is_weighted_sum_of_axes(self):
        # with alpha 0 the weighted scalar is exactly wx*x + wy*y of
        # the recorded decomposition — the auditability contract
        wts = (3.0, 5.0)
        for s in ("bmm_right", "bmm_left", "cpmm", "rmm", "summa"):
            gx, gy = (2, 2) if s == "summa" else (2, 4)
            cw = planner.comm_cost(s, 512, 128, 256, 1.0, 1.0, gx, gy,
                                   weights=wts)
            bx, by = planner.comm_cost_axes(s, 512, 128, 256, 1.0, 1.0,
                                            gx, gy, weights=wts)
            assert cw == pytest.approx(wts[0] * bx + wts[1] * by,
                                       rel=1e-12), s

    def test_alpha_steps_weighted_per_axis(self):
        # rmm pays one y-gather step at wy and one x-gather step at wx
        al = 1e6
        base = planner.comm_cost("rmm", 512, 512, 512, 1.0, 1.0, 2, 4,
                                 weights=(3.0, 5.0))
        got = planner.comm_cost("rmm", 512, 512, 512, 1.0, 1.0, 2, 4,
                                alpha_bytes=al, weights=(3.0, 5.0))
        assert got == pytest.approx(base + al * (3.0 + 5.0))

    def test_strategy_flip_avoids_slow_axis(self, mesh8):
        # THE acceptance flip (VERDICT Next #4 "done when"): in the
        # 3a/8 < b < 3a/4 band on the (2,4) grid the beta-only argmin
        # is rmm, whose A all-gather rides y; pricing y 8x (the DCN
        # axis) provably routes to bmm_right, whose broadcast's
        # expensive stage stays on x
        node = matmul(_fab(mesh8, 8192, 2048), _fab(mesh8, 2048, 4096))
        flat, src0 = planner.choose_strategy_ex(node, mesh8,
                                                MatrelConfig())
        assert (flat, src0) == ("rmm", "model")
        cfg_w = MatrelConfig(axis_cost_weights=(1.0, 8.0))
        weighted, srcw = planner.choose_strategy_ex(node, mesh8, cfg_w)
        assert (weighted, srcw) == ("bmm_right", "model")
        # and the flip is the slow axis's doing: rmm really is y-heavy
        bx, by = planner.comm_cost_axes("rmm", 8192, 2048, 4096,
                                        1.0, 1.0, 2, 4)
        assert by > 5 * bx

    def test_weighted_join_scheme_avoids_slow_broadcast(self, mesh8):
        # join analogue: replicate schemes all-gather over the whole
        # mesh (their big stage rides one axis); weighting can flip a
        # broadcast win to align. Similar-sized operands on (2,4):
        # align already wins flat (stage-11 dryrun); shrink b so
        # "right" wins flat, then weight y to flip it back to align,
        # whose row-reshards ride only y at 1/p the volume
        from matrel_tpu.relational import ops as R
        e = R.join_on_rows(_fab(mesh8, 1024, 512),
                           _fab(mesh8, 1024, 96), "mul")
        flat = planner.choose_join_scheme(e, mesh8, MatrelConfig())
        w = planner.choose_join_scheme(
            e, mesh8, MatrelConfig(axis_cost_weights=(1.0, 64.0)))
        # the weighted pick never moves MORE weighted bytes than the
        # flat pick would under the weighted model
        def wcost(scheme):
            gx, gy = 2, 4
            wts = (1.0, 64.0)
            ab = planner._bytes((1024, 512), 1.0)
            bb = planner._bytes((1024, 96), 1.0)
            if scheme == "left":
                return planner._split_full_mesh(ab, gx, gy, *wts)[0]
            if scheme == "right":
                return planner._split_full_mesh(bb, gx, gy, *wts)[0]
            return (planner._reshard_to_axis(ab, "2d", "row", gx, gy,
                                             weights=wts)
                    + planner._reshard_to_axis(bb, "2d", "row", gx, gy,
                                               weights=wts))
        assert wcost(w) <= wcost(flat)

    def test_mesh_topology_resolution(self, mesh8):
        from matrel_tpu.core import mesh as mesh_lib
        topo = mesh_lib.mesh_topology(mesh8, MatrelConfig())
        assert topo.axis_weights == (1.0, 1.0)
        assert topo.source == "default" and topo.uniform
        topo_c = mesh_lib.mesh_topology(
            mesh8, MatrelConfig(axis_cost_weights=(1.0, 8.0)))
        assert topo_c.axis_weights == (1.0, 8.0)
        assert topo_c.source == "config" and not topo_c.uniform
        # CPU devices expose no slice_index: detection must stay flat
        assert mesh_lib.detect_slice_axes(mesh8) == (False, False)

    def test_slice_detection_on_fake_multislice(self):
        # detection only reads mesh.devices — drive it with fake
        # slice-indexed device objects (a 2-slice (2,4) mesh laid out
        # slice-per-row: the x axis crosses DCN, y stays in-slice)
        import types
        from matrel_tpu.core import mesh as mesh_lib

        def dev(s):
            return types.SimpleNamespace(slice_index=s)

        two_slice = types.SimpleNamespace(
            devices=[[dev(0)] * 4, [dev(1)] * 4])
        assert mesh_lib.detect_slice_axes(two_slice) == (True, False)
        topo = mesh_lib.mesh_topology(two_slice, MatrelConfig())
        assert topo.source == "detected"
        assert topo.axis_weights == (mesh_lib.DCN_AXIS_WEIGHT, 1.0)
        # explicit config stays the calibration override
        topo_c = mesh_lib.mesh_topology(
            two_slice, MatrelConfig(axis_cost_weights=(16.0, 1.0)))
        assert (topo_c.source, topo_c.axis_weights) == ("config",
                                                        (16.0, 1.0))
        # single-slice: homogeneous however the ids read
        one = types.SimpleNamespace(devices=[[dev(0)] * 4] * 2)
        assert mesh_lib.detect_slice_axes(one) == (False, False)

    def test_matmul_decisions_record_axis_bytes(self, mesh8):
        cfg = MatrelConfig(axis_cost_weights=(1.0, 8.0))
        ann = planner.annotate_strategies(
            matmul(_fab(mesh8, 512, 128), _fab(mesh8, 128, 256)),
            mesh8, cfg)
        (rec,) = planner.matmul_decisions(ann, mesh8, cfg)
        assert len(rec["est_axis_bytes"]) == 2
        assert all(v >= 0 for v in rec["est_axis_bytes"])
        assert rec["axis_weights"] == [1.0, 8.0]
        assert rec["topology_source"] == "config"
        # unit discipline (review r7): est_ici_bytes stays RAW bytes
        # (flat weights — the unit history sums as MiB, comparable
        # across sessions); the weighted ranking quantity is its own
        # field. With alpha excluded the axes sum to the raw bill.
        flat_beta = planner.comm_cost(rec["strategy"], 512, 128, 256,
                                      1.0, 1.0, 2, 4,
                                      a_layout=rec["layouts"][0],
                                      b_layout=rec["layouts"][1])
        assert sum(rec["est_axis_bytes"]) == pytest.approx(flat_beta,
                                                           rel=1e-12)
        assert rec["est_weighted_cost"] > rec["est_ici_bytes"]
        # uniform mesh: decomposition recorded, weight fields omitted
        (rec0,) = planner.matmul_decisions(ann, mesh8, MatrelConfig())
        assert "axis_weights" not in rec0
        assert "est_weighted_cost" not in rec0
        assert "est_axis_bytes" in rec0
        assert rec0["est_ici_bytes"] == rec["est_ici_bytes"]

    def test_weighted_plan_cache_key_never_collides(self, mesh8):
        from matrel_tpu.session import MatrelSession
        a = BlockMatrix.from_numpy(
            np.random.default_rng(0).standard_normal(
                (64, 64)).astype(np.float32), mesh=mesh8)
        e = a.expr().multiply(a.expr())
        s0 = MatrelSession(mesh=mesh8, config=MatrelConfig())
        sw = MatrelSession(mesh=mesh8, config=MatrelConfig(
            axis_cost_weights=(1.0, 8.0)))
        _, _, k0 = s0._compile_entry(e)
        _, _, kw = sw._compile_entry(e)
        assert k0 != kw and kw.startswith("axisw:1x8|")


# -- a long Gram's panel loop carries the right-hand sides (PR 34) ------------


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("k, m, tail, ca", [
    (k, m, tail, 0)
    for k in (1000, 520, 300) for m in (1, 8, 24) for tail in (0, 77)
] + [(520, 8, 77, 1), (300, 1, 0, 1)])
def test_gram_in_panels_carries_riders(k, m, tail, ca):
    """One whole panel and a ragged tail (or none), the last block
    column 232, 8 and 44 wide, ``m`` right-hand sides riding it. The
    Gram's other block columns are the very same dots and equal the
    rider-less Gram's bit for bit; the last one is a wider dot of the
    same panels, equal to 1e-6 of the largest entry (on this CPU
    backend bit for bit too in all but k = 300, m = 24, where a dot 68
    wide is blocked otherwise than one 44 wide: 2.6e-7), and the result
    stays symmetric to the last bit. The riders' columns are the
    float64 product to 1e-6 of the largest entry (2.6e-7 at most here)
    and ``dot_in_panels``' to 5e-6: alone, one column is a matrix-vector
    product that the CPU adds up plainly, 2.5e-6 from float64 itself."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1000 * k + 10 * m + tail + ca)
    rows = strategies.ACC_PANEL_ROWS + tail
    a = rng.uniform(-1, 1, (rows, k) if ca == 0 else (k, rows)) \
        .astype(np.float32)
    # the regression's right-hand sides: y = X theta* + noise
    rhs = ((a if ca == 0 else a.T) @ rng.standard_normal((k, m))
           + 0.1 * rng.standard_normal((rows, m))).astype(np.float32)
    assert m <= strategies.gram_rider_room(k)
    plain = np.asarray(jax.jit(
        lambda u: strategies.gram_in_panels(u, ca))(jnp.asarray(a)))
    gram, rode = jax.jit(
        lambda u, v: strategies.gram_in_panels(u, ca, rhs=v))(
            jnp.asarray(a), jnp.asarray(rhs))
    assert gram.dtype == rode.dtype == jnp.float32
    assert gram.shape == (k, k) and rode.shape == (k, m)
    gram, rode = np.asarray(gram), np.asarray(rode)
    below = strategies.gram_blocks(k)[-1][0]
    assert np.array_equal(gram[:below, :below], plain[:below, :below])
    assert _rel(gram, plain.astype(np.float64)) < 1e-6
    assert np.array_equal(gram, gram.T)
    alone = np.asarray(jax.jit(
        lambda u, v: strategies.dot_in_panels(u, ca, v, 0))(
            jnp.asarray(a), jnp.asarray(rhs)))
    a64 = a.astype(np.float64)
    want = (a64.T if ca == 0 else a64) @ rhs.astype(np.float64)
    assert _rel(rode, want) < 1e-6
    assert _rel(rode, alone.astype(np.float64)) < 5e-6


def test_gram_rider_room_is_the_spare_lanes():
    """The lanes the last block column leaves spare in its tiles of
    128, as far as columns lie below it: none for one block, none where
    the last block fills its tiles."""
    assert [strategies.gram_rider_room(k)
            for k in (1000, 520, 300, 257, 256, 130, 512, 896, 1024)] \
        == [24, 120, 84, 127, 0, 0, 0, 0, 0]


_RIDER_ROWS = strategies.LONG_CONTRACTION + 40
_REGRESSION = "inv(t(X) * X) * t(X) * y"


def _regression_plan(k, m, sql, dtype="float32", rows=_RIDER_ROWS,
                     tall=True, **config):
    """(the stamps of ``plan.meta["products"]``, the ``dot_general``s
    and the ``while``s of the lowered text) of ``sql`` over described
    tables X and Z (rows x k, or k x rows) and y (rows x m) on one CPU
    device: shapes alone, nothing is allocated."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.session import MatrelSession
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    whole = NamedSharding(mesh, P(None, None))
    sess = MatrelSession(mesh=mesh, config=MatrelConfig(**config))
    table = (rows, k) if tall else (k, rows)
    for name, shape in (("X", table), ("Z", table), ("y", (rows, m))):
        sess.register(name, BlockMatrix.from_array(
            jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=whole),
            shape, mesh, P(None, None)))
    plan = sess.compile(sess.sql(sql))
    text = plan.jitted.lower(*[
        jax.ShapeDtypeStruct(leaf.attrs["matrix"].shape, jnp.dtype(dtype),
                             sharding=whole)
        for leaf in plan.leaf_order]).as_text()
    stamps = [(p.get("gram_tiles") is not None, p.get("gram_rides"),
               p.get("rides_gram")) for p in plan.meta["products"]]
    return stamps, text.count("dot_general"), text.count("stablehlo.while")


@pytest.mark.parametrize("case, k, m, sql, tall, config, rides, program", [
    ("one_column", 1000, 1, _REGRESSION, True, {}, 1, (8, 1)),
    ("the_last_spare_lane", 1000, 24, _REGRESSION, True, {}, 24, (8, 1)),
    ("one_column_too_many", 1000, 25, _REGRESSION, True, {}, None, (10, 2)),
    ("no_spare_lane", 512, 1, _REGRESSION, True, {}, None, (6, 2)),
    ("one_block", 200, 1, _REGRESSION, True, {}, None, (4, 2)),
    ("side_AAt", 1000, 1, "inv(X * t(X)) * X * y", False, {}, None,
     (10, 2)),
    ("another_table", 1000, 1, "inv(t(X) * X) * t(Z) * y", True, {}, None,
     (10, 2)),
    ("bfloat16", 1000, 1, _REGRESSION, True, {"dtype": "bfloat16"}, None,
     (2, 0)),
    ("the_two_pass_split", 1000, 1, _REGRESSION, True,
     {"matmul_precision": "high"}, None, (4, 1)),
])
def test_what_rides_a_long_gram(case, k, m, sql, tall, config, rides,
                                program):
    """The planner's test is of shapes: ``t(X) * y`` rides ``t(X) *
    X``'s loop iff its columns fit the lanes the last block column
    leaves spare (k = 1000: 24) over the very same float32 table. Where
    it rides, the two products are ONE loop and the Gram's dots (a block
    column each, in the loop and in the tail: 8 at k = 1000); where it
    does not, both lower as they did: two loops and ``t(X) * y``'s own
    two dots (a bfloat16 table: one dot each, no loop; under
    ``matmul_precision`` "high" the Gram is the two-pass split's two
    dots, no triangle, and ``t(X) * y`` keeps its loop). The solve's own
    loops are counted on a short table."""
    dtype = config.get("dtype", "float32")
    config = {k: v for k, v in config.items() if k != "dtype"}
    stamps, dots, loops = _regression_plan(k, m, sql, dtype, tall=tall,
                                           **config)
    _, _, solve_loops = _regression_plan(k, m, sql, dtype, rows=4096,
                                         tall=tall, **config)
    triangle = dtype == "float32" and "matmul_precision" not in config
    if rides:
        assert stamps == [(True, rides, None), (False, None, True),
                          (False, None, None)]
    else:
        assert stamps == [(triangle, None, None)] + [(False, None, None)] * 2
    assert (dots, loops - solve_loops) == program
