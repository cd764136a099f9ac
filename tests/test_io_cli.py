"""IO formats, CLI, and autotune smoke tests."""

import json
import subprocess
import sys

import numpy as np
import pytest

from matrel_tpu import io as mio
from matrel_tpu.core.blockmatrix import BlockMatrix


class TestIO:
    def test_npy_roundtrip(self, mesh8, rng, tmp_path):
        a = rng.standard_normal((12, 9)).astype(np.float32)
        p = str(tmp_path / "a.npy")
        np.save(p, a)
        m = mio.load_npy(p, mesh=mesh8)
        np.testing.assert_allclose(m.to_numpy(), a, rtol=1e-6)
        p2 = str(tmp_path / "b.npy")
        mio.save_npy(p2, m)
        np.testing.assert_allclose(np.load(p2), a, rtol=1e-6)

    def test_coo_csv_dense_and_sparse(self, mesh8, tmp_path):
        p = str(tmp_path / "m.csv")
        with open(p, "w") as f:
            f.write("0,0,1.5\n2,3,-2.0\n0,0,0.5\n")  # duplicate sums
        m = mio.load_coo_csv(p, (4, 5), mesh=mesh8, dense=True)
        got = m.to_numpy()
        assert got[0, 0] == pytest.approx(2.0)
        assert got[2, 3] == pytest.approx(-2.0)
        s = mio.load_coo_csv(p, (4, 5), mesh=mesh8, block_size=2)
        np.testing.assert_allclose(s.to_numpy(), got, rtol=1e-6)

    def test_mtx(self, mesh8, tmp_path):
        import scipy.io, scipy.sparse
        dense = np.zeros((6, 6), np.float32)
        dense[1, 2] = 3.25
        dense[5, 0] = -1.0
        p = str(tmp_path / "m.mtx")
        scipy.io.mmwrite(p, scipy.sparse.coo_matrix(dense))
        s = mio.load_mtx(p, mesh=mesh8, block_size=4)
        np.testing.assert_allclose(s.to_numpy(), dense, rtol=1e-6)

    def test_mtx_coo(self, mesh8, rng, tmp_path):
        import scipy.io, scipy.sparse
        r = rng.integers(0, 300, 2000)
        c = rng.integers(0, 200, 2000)
        v = rng.standard_normal(2000).astype(np.float32)
        S = scipy.sparse.coo_matrix((v, (r, c)), shape=(300, 200))
        p = str(tmp_path / "g.mtx")
        scipy.io.mmwrite(p, S)
        A = mio.load_mtx_coo(p)
        assert A.shape == (300, 200)
        x = rng.standard_normal(200).astype(np.float32)
        np.testing.assert_allclose(np.asarray(A.matvec(x)),
                                   S.tocsr() @ x, rtol=3e-4, atol=3e-4)
        # symmetric file: native reader must expand the mirror entries
        Ssym = scipy.sparse.coo_matrix(
            np.array([[2.0, 1.0, 0], [1.0, 0, 0], [0, 0, 3.0]],
                     np.float32))
        p2 = str(tmp_path / "sym.mtx")
        scipy.io.mmwrite(p2, Ssym, symmetry="symmetric")
        B = mio.load_mtx_coo(p2)
        np.testing.assert_allclose(B.to_dense(), Ssym.toarray())

    def test_tiled_roundtrip(self, mesh8, rng, tmp_path):
        a = rng.standard_normal((20, 13)).astype(np.float32)
        m = BlockMatrix.from_numpy(a, mesh=mesh8)
        d = str(tmp_path / "tiles")
        mio.save_tiled(d, m, tile=8)
        m2 = mio.load_tiled(d, mesh=mesh8)
        np.testing.assert_allclose(m2.to_numpy(), a, rtol=1e-6)


class TestAutotune:
    def test_returns_admissible_best(self, mesh8):
        from matrel_tpu.parallel.autotune import autotune_matmul
        best, table = autotune_matmul(64, 64, 64, mesh=mesh8)
        # best may be None under the tie rule (noisy host); when named
        # it must be a measured admissible strategy
        assert best is None or best in table
        assert len(table) >= 3
        assert all(t > 0 for t in table.values())
        # cached second call
        best2, _ = autotune_matmul(64, 64, 64, mesh=mesh8)
        assert best2 == best

    def test_pick_winner_tie_rule(self):
        from matrel_tpu.parallel.autotune import _pick_winner
        # clear winner (runner-up >10% slower)
        assert _pick_winner({"rmm": 1.0, "cpmm": 1.2}) == "rmm"
        # tie within 10%: no measured winner — byte model decides
        assert _pick_winner({"rmm": 1.0, "cpmm": 1.05}) is None
        assert _pick_winner({}) is None
        # one-variant "comparison" proves nothing (review r5: the gate
        # moved INSIDE _pick_winner — one policy for both loops)
        assert _pick_winner({"xla": 0.5}) is None


class TestAutotuneLoop:
    """Closed autotune loop (VERDICT r2 #4): config.autotune lets a
    MEASURED winner override the cost model's matmul pick, and the
    table persists across sessions (process-cache clears)."""

    def _choose(self, mesh, cfg, n=64, k=64, m=64, rng=None):
        import numpy as np
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.parallel import planner
        rng = rng or np.random.default_rng(7)
        A = BlockMatrix.from_numpy(
            rng.standard_normal((n, k)).astype(np.float32), mesh=mesh)
        B = BlockMatrix.from_numpy(
            rng.standard_normal((k, m)).astype(np.float32), mesh=mesh)
        node = A.expr().multiply(B.expr())
        return planner.choose_strategy(node, mesh, cfg)

    def test_measured_winner_overrides_model(self, mesh8, tmp_path):
        import json
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        base = self._choose(mesh8, MatrelConfig())
        # plant a measured table naming a DIFFERENT admissible strategy
        forced = "rmm" if base != "rmm" else "cpmm"
        json.dump({autotune._table_key(64, 2, 4, "float32"): {"best": forced,
                                      "times": {forced: 1e-6}}},
                  open(path, "w"))
        autotune._CACHE.clear()
        assert self._choose(mesh8, cfg) == forced
        assert base != forced

    def test_table_persists_measurement(self, mesh8, tmp_path,
                                        monkeypatch):
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        # deterministic timings (>10% apart) so the winner is stable
        # regardless of host noise
        fake = {"bmm_left": 5.0, "bmm_right": 4.0, "cpmm": 1.0,
                "rmm": 2.0, "summa": 3.0, "xla": 6.0}
        monkeypatch.setattr(
            autotune, "measure_strategy",
            lambda s, A, B, cfg, **kw: fake[s])
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        best = autotune.lookup_or_measure(64, 64, 64, mesh8,
                                          "float32", cfg)
        assert best == "cpmm"
        table = autotune.load_table(path)
        assert table[autotune._table_key(64, 2, 4, "float32")]["best"] == best
        # a fresh process (cache cleared) reads the file, no re-measure
        autotune._CACHE.clear()
        monkeypatch.setattr(autotune, "measure_strategy",
                            lambda *a, **kw: 1 / 0)
        assert autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32", cfg) == best

    def test_interior_chain_multiply_consults_table(self, mesh8,
                                                    tmp_path):
        # VERDICT r3 #3: the measured table must cover every matmul
        # node, not just leaf×leaf — an operand that is ITSELF a matmul
        # (the interior product of a chain) now has an inferred dtype
        # and consults the table
        import json

        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.parallel import autotune, planner
        rng = np.random.default_rng(3)

        def mk(n, m):
            return BlockMatrix.from_numpy(
                rng.standard_normal((n, m)).astype(np.float32),
                mesh=mesh8).expr()

        A, B, C = mk(64, 64), mk(64, 64), mk(64, 64)
        outer = A.multiply(B.multiply(C))
        base = planner.choose_strategy(outer, mesh8, MatrelConfig())
        forced = "rmm" if base != "rmm" else "cpmm"
        path = str(tmp_path / "tuned.json")
        json.dump({autotune._table_key(64, 2, 4, "float32"): {"best": forced,
                                      "times": {forced: 1e-6}}},
                  open(path, "w"))
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        annotated = planner.annotate_strategies(outer, mesh8, cfg)
        assert annotated.attrs["strategy"] == forced          # leaf×interior
        assert annotated.children[1].attrs["strategy"] == forced

    def test_infer_dtype_propagation(self, mesh8):
        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.parallel.planner import infer_dtype
        rng = np.random.default_rng(5)

        def mk(dtype):
            return BlockMatrix.from_numpy(
                rng.standard_normal((16, 16)).astype(np.float32),
                mesh=mesh8, dtype=dtype).expr()

        f32, bf16 = mk("float32"), mk("bfloat16")
        cfg = MatrelConfig()          # keep_input_dtype=True
        assert infer_dtype(bf16.multiply(bf16), cfg) == np.dtype("bfloat16")
        assert infer_dtype(bf16.t().multiply(bf16), cfg) == np.dtype(
            "bfloat16")
        # mixed-dtype multiply accumulates (and stays) f32
        assert infer_dtype(f32.multiply(bf16), cfg) == np.dtype("float32")
        # promotion through elementwise; preservation through agg/scalar
        assert infer_dtype(f32.add(bf16), cfg) == np.dtype("float32")
        assert infer_dtype(bf16.row_sum().multiply_scalar(2.0),
                           cfg) == np.dtype("bfloat16")
        # interior product feeds dtype upward
        assert infer_dtype(bf16.multiply(bf16).multiply(bf16),
                           cfg) == np.dtype("bfloat16")
        # keep_input_dtype=False: bf16 matmul accumulates f32
        nc = MatrelConfig(keep_input_dtype=False)
        assert infer_dtype(bf16.multiply(bf16), nc) == np.dtype("float32")
        # unknown: user-callable join merge; structured merges promote
        assert infer_dtype(f32.join_on_index(f32, lambda a, b: a > b),
                           cfg) is None
        assert infer_dtype(f32.join_on_index(bf16, "add"),
                           cfg) == np.dtype("float32")
        from matrel_tpu.relational import ops as R
        assert infer_dtype(R.join_on_rows(bf16, bf16, "mul"),
                           cfg) == np.dtype("bfloat16")

    def test_empty_persisted_entry_remeasures(self, mesh8, tmp_path,
                                              monkeypatch):
        # review r4: a persisted entry with EMPTY times (e.g. from a
        # transiently broken backend) must not read as a measurement —
        # the shape class is re-measured on a healthy process, and an
        # empty result set is never persisted in the first place
        import json
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        json.dump({autotune._table_key(64, 2, 4, "float32"): {"best": None, "times": {}}},
                  open(path, "w"))
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        called = {}

        def fake_measure(s, A, B, c, **kw):
            called[s] = True
            return {"cpmm": 1.0}.get(s, 2.0)

        monkeypatch.setattr(autotune, "measure_strategy", fake_measure)
        assert autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32", cfg) == "cpmm"
        assert called
        # the healthy measurement replaced the empty entry on disk
        assert autotune.load_table(path)[autotune._table_key(64, 2, 4, "float32")]["times"]

    def test_strategy_source_annotation(self, mesh8, tmp_path):
        # round-4 observability: EXPLAIN records WHY a strategy was
        # chosen — override / measured / model / default
        import json

        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.ir.expr import pretty
        from matrel_tpu.parallel import autotune, planner
        rng = np.random.default_rng(21)
        A = BlockMatrix.from_numpy(
            rng.standard_normal((64, 64)).astype(np.float32), mesh=mesh8)
        e = A.expr().multiply(A.expr())
        assert planner.choose_strategy_ex(e, mesh8,
                                          MatrelConfig())[1] == "model"
        assert planner.choose_strategy_ex(
            e, mesh8, MatrelConfig(strategy_override="rmm")) == (
                "rmm", "override")
        path = str(tmp_path / "tuned.json")
        with open(path, "w") as f:
            json.dump({autotune._table_key(64, 2, 4, "float32"):
                       {"best": "cpmm", "times": {"cpmm": 1e-6}}}, f)
        autotune._CACHE.clear()
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        assert planner.choose_strategy_ex(e, mesh8, cfg) == ("cpmm",
                                                             "measured")
        ann = planner.annotate_strategies(e, mesh8, cfg)
        assert ann.attrs["strategy_source"] == "measured"
        assert "strategy=cpmm[measured]" in pretty(ann)
        autotune._CACHE.clear()

    def test_spmv_choice_measured_and_persisted(self, mesh8, tmp_path,
                                                monkeypatch):
        # VERDICT r3 #8: the SpMV executor choice (compact Pallas vs
        # expanded XLA) joins the measured-table loop — same discipline
        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.parallel import autotune
        rng = np.random.default_rng(11)
        A = COOMatrix.from_edges(rng.integers(0, 300, 4000),
                                 rng.integers(0, 300, 4000),
                                 shape=(300, 300))
        plan = A._get_plan()
        assert plan is not None
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path,
                           pallas_interpret=True)
        fake = {"compact": 2.0, "expanded": 1.0}
        monkeypatch.setattr(autotune, "measure_spmv_variant",
                            lambda v, p, m, c, **kw: fake[v])
        autotune._SPMV_CACHE.clear()
        best = autotune.lookup_or_measure_spmv(plan, mesh8, cfg)
        assert best == "expanded"
        key = autotune._spmv_key(plan, 2, 4)
        entry = autotune.load_table(path)[key]
        assert entry["best"] == "expanded" and entry["times"]
        # fresh process reads the table, no re-measure
        autotune._SPMV_CACHE.clear()
        monkeypatch.setattr(autotune, "measure_spmv_variant",
                            lambda *a, **kw: 1 / 0)
        assert autotune.lookup_or_measure_spmv(plan, mesh8,
                                               cfg) == "expanded"

    def test_spmv_single_variant_not_persisted(self, mesh8, tmp_path,
                                               monkeypatch):
        # review r4: admissibility depends on config (use_pallas) that
        # the key does not encode — a one-variant "comparison" must
        # resolve to None and never be written to a shared table
        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.parallel import autotune
        rng = np.random.default_rng(13)
        A = COOMatrix.from_edges(rng.integers(0, 300, 3000),
                                 rng.integers(0, 300, 3000),
                                 shape=(300, 300))
        plan = A._get_plan()
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path,
                           use_pallas=False)    # compact inadmissible
        monkeypatch.setattr(autotune, "measure_spmv_variant",
                            lambda v, p, m, c, **kw: 1.0)
        autotune._SPMV_CACHE.clear()
        assert autotune.lookup_or_measure_spmv(plan, mesh8, cfg) is None
        assert autotune.load_table(path) == {}

    def test_spmv_probe_does_not_pin_expanded_tables(self, mesh8,
                                                     tmp_path):
        # review r4: the expanded probe must not leave the ~224 B/slot
        # expanded tables cached on the plan when the session moves on
        import numpy as np
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.parallel import autotune
        rng = np.random.default_rng(14)
        A = COOMatrix.from_edges(rng.integers(0, 300, 3000),
                                 rng.integers(0, 300, 3000),
                                 shape=(300, 300))
        plan = A._get_plan()
        assert plan._tables is None
        cfg = MatrelConfig(autotune=True, pallas_interpret=True,
                           autotune_table_path=str(tmp_path / "t.json"))
        autotune._SPMV_CACHE.clear()
        best = autotune.lookup_or_measure_spmv(plan, mesh8, cfg)
        assert best is not None         # both variants measured
        assert plan._tables is None     # probe caches were dropped
        assert plan._spmm_tables is None

    def test_spmv_measured_choice_drives_executor(self, mesh8, tmp_path,
                                                  monkeypatch):
        # a persisted "expanded" winner must actually route the COO
        # dispatch off the compact Pallas path, with oracle numerics
        import json

        import numpy as np
        import scipy.sparse as sp
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core.coo import COOMatrix
        from matrel_tpu.ops import pallas_spmv as pc
        from matrel_tpu.parallel import autotune
        from matrel_tpu import executor as executor_lib
        rng = np.random.default_rng(12)
        r = rng.integers(0, 300, 4000)
        c = rng.integers(0, 300, 4000)
        A = COOMatrix.from_edges(r, c, shape=(300, 300))
        plan = A._get_plan()
        path = str(tmp_path / "tuned.json")
        key = autotune._spmv_key(plan, 2, 4)
        json.dump({key: {"best": "expanded",
                         "times": {"expanded": 1.0, "compact": 2.0}}},
                  open(path, "w"))
        cfg = MatrelConfig(autotune=True, autotune_table_path=path,
                           pallas_interpret=True)
        autotune._SPMV_CACHE.clear()

        def boom(*a, **kw):
            raise AssertionError("compact path used despite measured "
                                 "expanded winner")

        for name in ("compact_apply", "compact_matmat_apply",
                     "compact_sharded_apply",
                     "compact_sharded_matmat_apply"):
            monkeypatch.setattr(pc, name, boom)
        x = BlockMatrix.from_numpy(
            rng.standard_normal((300, 2)).astype(np.float32), mesh=mesh8)
        got = executor_lib.execute(A.multiply(x.expr()), mesh8,
                                   cfg).to_numpy()
        want = sp.coo_matrix(
            (np.ones(len(r), np.float32), (r, c)),
            shape=(300, 300)).toarray() @ x.to_numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_all_strategies_failing_not_persisted(self, mesh8, tmp_path,
                                                  monkeypatch):
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        monkeypatch.setattr(
            autotune, "measure_strategy",
            lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("down")))
        best, times = autotune.autotune_matmul(64, 64, 64, mesh=mesh8,
                                               config=cfg)
        assert best is None and times == {}
        assert autotune._table_key(64, 2, 4, "float32") not in autotune.load_table(path)

    def test_persisted_tie_not_remeasured(self, mesh8, tmp_path,
                                          monkeypatch):
        # a persisted {"best": null} IS a measurement: the planner gets
        # None (model decides) and no re-measure happens on each compile
        import json
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        json.dump({autotune._table_key(64, 2, 4, "float32"):
                   {"best": None, "times": {"rmm": 1.0, "cpmm": 1.01}}},
                  open(path, "w"))
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        monkeypatch.setattr(autotune, "autotune_matmul",
                            lambda *a, **kw: 1 / 0)
        assert autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32", cfg) is None

    def test_rectangular_shapes_gated_out(self, mesh8, tmp_path,
                                          monkeypatch):
        # advisor r3: square-probe winners don't transfer to strongly
        # rectangular multiplies — and the probe itself would allocate
        # two side^2 operands at compile time
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        cfg = MatrelConfig(autotune=True,
                           autotune_table_path=str(tmp_path / "t.json"))
        monkeypatch.setattr(autotune, "autotune_matmul",
                            lambda *a, **kw: 1 / 0)
        assert autotune.lookup_or_measure(
            64, 64, 8192, mesh8, "float32", cfg) is None

    def test_persist_lock_skips_on_contention(self, tmp_path):
        import json
        import os
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "t.json")
        # current-format keys: load_table prunes legacy un-suffixed
        # entries (advisor r5 low), so the lock semantics under test
        # need keys that survive a round-trip
        keep = autotune._table_key(64, 2, 4, "float32")
        new = autotune._table_key(128, 2, 4, "float32")
        json.dump({keep: {"best": "rmm", "times": {}}}, open(path, "w"))
        # fresh lock held by a live writer: persist must skip, not clobber
        open(path + ".lock", "w").close()
        autotune._persist(path, new, "cpmm", {})
        assert new not in autotune.load_table(path)
        # stale lock (>60s) is broken and the merge proceeds, keeping
        # existing entries
        os.utime(path + ".lock", (0, 0))
        autotune._persist(path, new, "cpmm", {})
        t = autotune.load_table(path)
        assert t[new]["best"] == "cpmm" and keep in t
        assert not os.path.exists(path + ".lock")

    def test_inadmissible_persisted_winner_falls_back(self, mesh8,
                                                      tmp_path):
        import json
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "tuned.json")
        # summa needs a square grid: inadmissible on the 2x4 mesh, so
        # the planner must ignore the planted winner and use the model
        json.dump({autotune._table_key(64, 2, 4, "float32"): {"best": "summa", "times": {}}},
                  open(path, "w"))
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        got = self._choose(mesh8, cfg)
        assert got != "summa"

    def test_oversize_shapes_never_measured_inline(self, mesh8,
                                                   tmp_path):
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        cfg = MatrelConfig(autotune=True, autotune_max_dim=32,
                           autotune_table_path=str(tmp_path / "t.json"))
        assert autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32", cfg) is None


class TestCLI:
    def _run(self, *args):
        import os
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        return subprocess.run(
            [sys.executable, "-m", "matrel_tpu", *args],
            capture_output=True, text=True, cwd="/root/repo", env=env,
            timeout=240)

    def test_info(self):
        r = self._run("info")
        assert r.returncode == 0, r.stderr
        out = json.loads(r.stdout)
        assert out["platform"] == "cpu" and "mesh" in out
        assert out["device_kind"] and out["device_count"] >= 1
        assert out["compile_cache_dir"]

    def test_pagerank_cli(self, tmp_path, capsys):
        import json
        from matrel_tpu.__main__ import main
        p = str(tmp_path / "edges.csv")
        with open(p, "w") as f:
            # star graph into node 0 + a 1->2 edge
            f.write("1,0,1\n2,0,1\n3,0,1\n1,2,1\n")
        main(["pagerank", p, "--rounds", "20", "--top", "2"])
        out = json.loads(capsys.readouterr().out)
        assert out["nodes"] == 4 and out["edges"] == 4
        assert out["top"][0]["node"] == 0          # the hub wins
        assert abs(out["rank_sum"] - 1.0) < 1e-3

    def test_sql_oneshot(self, tmp_path):
        p = str(tmp_path / "x.npy")
        np.save(p, np.eye(3, dtype=np.float32) * 2)
        r = self._run("sql", "trace(X)", "--table", f"X={p}")
        assert r.returncode == 0, r.stderr
        assert "6." in r.stdout


def test_sql_explain_flag(tmp_path, capsys):
    import numpy as np
    from matrel_tpu.__main__ import main as cli_main
    from matrel_tpu.session import reset_session
    reset_session()
    a = np.eye(6, dtype=np.float32)
    p = str(tmp_path / "a.npy")
    np.save(p, a)
    cli_main(["sql", "rowsum(A * A)", "--table", f"A={p}", "--explain"])
    out = capsys.readouterr().out
    assert "== Optimized plan ==" in out and "matmul" in out


def test_plain_autotune_call_leaves_no_table_file(mesh8, tmp_path,
                                                  monkeypatch):
    # review r3: a one-off measurement (autotune flag off, no explicit
    # path) must not drop a hidden JSON into the working directory
    import os
    from matrel_tpu.parallel import autotune
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(autotune, "_DEFAULT_TABLE",
                        str(tmp_path / ".matrel_autotune.json"))
    autotune._CACHE.clear()
    autotune.autotune_matmul(64, 64, 64, mesh=mesh8)
    assert not os.path.exists(tmp_path / ".matrel_autotune.json")


def test_cached_measurement_persists_when_loop_enabled_later(mesh8,
                                                             tmp_path):
    # review r3: shape measured with persistence OFF, then requested
    # with the closed loop ON in the same process -> table gains it
    import os
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.parallel import autotune
    autotune._CACHE.clear()
    best, _ = autotune.autotune_matmul(64, 64, 64, mesh=mesh8)  # no persist
    path = str(tmp_path / "t.json")
    assert not os.path.exists(path)
    cfg = MatrelConfig(autotune=True, autotune_table_path=path)
    got = autotune.lookup_or_measure(64, 64, 64, mesh8, "float32", cfg)
    assert got == best
    assert autotune.load_table(path)[autotune._table_key(64, 2, 4, "float32")]["best"] == best


class TestAutotuneOneVariantGate:
    def test_lone_survivor_not_a_winner(self, mesh8, monkeypatch,
                                        tmp_path):
        # advisor r4: when every strategy but one fails to compile or
        # measures as noise, the lone survivor must be recorded
        # best=None (times persisted for observability), mirroring the
        # SpMV loop's len(results) >= 2 gate
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune

        def fake(s, A, B, cfg, **kw):
            if s != "xla":
                raise RuntimeError("compile failed")
            return 1.0
        monkeypatch.setattr(autotune, "measure_strategy", fake)
        path = str(tmp_path / "tuned.json")
        cfg = MatrelConfig(autotune=True, autotune_table_path=path)
        autotune._CACHE.clear()
        best, results = autotune.autotune_matmul(32, 32, 32, mesh=mesh8,
                                                 config=cfg)
        assert best is None
        assert list(results) == ["xla"]
        entry = autotune.load_table(path)[
            autotune._table_key(32, 2, 4, "float32")]
        assert entry["best"] is None and entry["times"]


class TestWeightedTableKeys:
    """Round 7 cache-key hygiene: weighted (topology) measurements get
    their own autotune-table rows; load_table's prune keeps both the
    historical 4/7-field keys AND the new w-suffixed forms while still
    dropping true legacy entries."""

    def test_weighted_key_formats_survive_prune(self, tmp_path):
        import json
        from matrel_tpu.parallel import autotune
        uk = autotune._table_key(64, 2, 4, "float32")
        wk = autotune._table_key(64, 2, 4, "float32", (1.0, 8.0))
        assert wk == uk + "|w1x8" and wk != uk
        path = str(tmp_path / "t.json")
        legacy_mm = "64|2x4|float32"          # pre-backend-suffix
        legacy_spmv = "spmv|cpu|100x100|nb1|cap8|blk128"
        spmv_w = legacy_spmv + "|2x4|w1x8"    # current 7-field + weights
        json.dump({uk: {"best": "rmm", "times": {"rmm": 1.0}},
                   wk: {"best": "bmm_right", "times": {"bmm_right": 1.0}},
                   legacy_mm: {"best": "cpmm", "times": {}},
                   legacy_spmv: {"best": "compact", "times": {}},
                   spmv_w: {"best": "expanded", "times": {}}},
                  open(path, "w"))
        t = autotune.load_table(path)
        assert set(t) == {uk, wk, spmv_w}

    def test_weighted_mesh_reads_its_own_row(self, mesh8, tmp_path,
                                             monkeypatch):
        # a winner measured on the flat mesh must NOT serve a weighted
        # session (and vice versa): lookup under weights misses the
        # unweighted row and returns the weighted one
        import json
        from matrel_tpu.config import MatrelConfig
        from matrel_tpu.parallel import autotune
        path = str(tmp_path / "t.json")
        json.dump(
            {autotune._table_key(64, 2, 4, "float32"):
                 {"best": "rmm", "times": {"rmm": 1e-6, "cpmm": 1.0}},
             autotune._table_key(64, 2, 4, "float32", (1.0, 8.0)):
                 {"best": "cpmm",
                  "times": {"rmm": 1.0, "cpmm": 1e-6}}},
            open(path, "w"))
        autotune._CACHE.clear()
        flat = autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32",
            MatrelConfig(autotune=True, autotune_table_path=path))
        weighted = autotune.lookup_or_measure(
            64, 64, 64, mesh8, "float32",
            MatrelConfig(autotune=True, autotune_table_path=path,
                         axis_cost_weights=(1.0, 8.0)))
        autotune._CACHE.clear()
        assert (flat, weighted) == ("rmm", "cpmm")

    def test_spmv_key_weight_suffix(self, mesh8):
        import types
        from matrel_tpu.parallel import autotune
        plan = types.SimpleNamespace(
            src8=np.zeros((2, 8), np.int32), n_rows=100, n_cols=100,
            block=128)
        k0 = autotune._spmv_key(plan, 2, 4)
        kw = autotune._spmv_key(plan, 2, 4, (2.0, 1.0))
        assert kw == k0 + "|w2x1"
        assert autotune._current_key_format(k0)
        assert autotune._current_key_format(kw)
