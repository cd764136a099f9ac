"""Randomized soak harness — many more cases than the pytest suite runs.

Three batteries, all oracle-checked against numpy/scipy:
  fuzz   random mixed-leaf expression trees (dense/block-sparse/COO)
         through optimizer + executor            (tests/test_fuzz.py gen)
  spmv   random graphs (uniform/hub/banded/degenerate) through the
         one-hot SpMV/SpMM plans
  all    both

Run on the CPU mesh (default) or the real chip:
  python tools/soak.py all --seeds 150
  JAX_PLATFORMS= python tools/soak.py fuzz --seeds 25 --tpu

Exit code = number of failing cases (0 = clean).
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(tpu: bool):
    if not tpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        # a process that imported jax before this ran has already read
        # the environment: the config update pins the CPU either way
        import jax
        jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tests"))


def soak_fuzz(n_seeds: int, base: int, tol: float):
    import importlib.util
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.executor import compile_expr

    spec = importlib.util.spec_from_file_location(
        "fuzzmod", os.path.join(REPO, "tests", "test_fuzz.py"))
    fuzz = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fuzz)
    mesh = mesh_lib.make_mesh()
    fails = []
    for seed in range(base, base + n_seeds):
        rng = np.random.default_rng(seed)
        env = {}
        try:
            e = fuzz.gen_expr(rng, env, mesh,
                              depth=int(rng.integers(2, 5)),
                              leaf_kinds=("dense", "dense", "sparse",
                                          "coo"),
                              rand_specs=(seed % 2 == 1))
            oracle = fuzz.np_eval(e, env)
            # half the seeds force the Pallas paths (interpret mode
            # off TPU): the compact COO executor dispatch and Pallas
            # SpMM get soaked alongside the XLA lowerings. The OTHER
            # half randomise leaf PartitionSpecs (round-5 layout net:
            # the planner's per-layout credits must never move
            # numerics). A third sweep runs
            # matmul_precision="high" — the generator's gram nodes then
            # take the symmetric 2-pass split (round-3) and every f32
            # matmul runs bf16x3-class, so tolerance widens with it
            prec = "high" if seed % 3 == 0 else "highest"
            cfg = MatrelConfig(pallas_interpret=(seed % 2 == 0),
                               matmul_precision=prec)
            t = 10 * tol if prec == "high" else tol
            got = compile_expr(e, mesh, cfg).run().to_numpy()
            np.testing.assert_allclose(got, oracle, rtol=t, atol=t)
        except Exception as ex:  # noqa: BLE001 — soak collects everything
            fails.append((seed, type(ex).__name__, str(ex)[:200]))
        done = seed - base + 1
        if done % 30 == 0:
            print(f"  fuzz {done}/{n_seeds}, {len(fails)} failures",
                  flush=True)
    return fails


def soak_deep(n_seeds: int, base: int, tol: float):
    """Deep expression trees (depth 5-7): heavier rewrite/CSE/planner
    pressure than the default battery's depth 2-4."""
    import importlib.util
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.executor import compile_expr

    spec = importlib.util.spec_from_file_location(
        "fuzzmod", os.path.join(REPO, "tests", "test_fuzz.py"))
    fuzz = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fuzz)
    mesh = mesh_lib.make_mesh()
    fails = []
    for seed in range(base, base + n_seeds):
        rng = np.random.default_rng(seed)
        env = {}
        try:
            e = fuzz.gen_expr(rng, env, mesh,
                              depth=int(rng.integers(5, 8)),
                              leaf_kinds=("dense", "dense", "sparse",
                                          "coo"),
                              rand_specs=(seed % 2 == 1))
            oracle = fuzz.np_eval(e, env)
            cfg = MatrelConfig(pallas_interpret=(seed % 2 == 0))
            got = compile_expr(e, mesh, cfg).run().to_numpy()
            np.testing.assert_allclose(got, oracle, rtol=tol, atol=tol)
        except Exception as ex:  # noqa: BLE001
            fails.append(("deep", seed, type(ex).__name__, str(ex)[:200]))
    return fails


def soak_spmv(n_trials: int, base: int, tol: float):
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp
    from matrel_tpu.ops import spmv as spmv_lib

    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        n_r = int(rng.integers(1, 5000))
        n_c = int(rng.integers(1, 5000))
        m = int(rng.integers(0, 30_000))
        style = rng.choice(["uniform", "hub", "banded", "single-col"])
        if style == "uniform" or n_r < 4 or n_c < 4:
            rows = rng.integers(0, n_r, m)
            cols = rng.integers(0, n_c, m)
        elif style == "hub":
            rows = np.where(rng.random(m) < 0.5,
                            rng.integers(0, max(n_r // 100, 1)),
                            rng.integers(0, n_r, m))
            cols = rng.integers(0, n_c, m)
        elif style == "banded":
            rows = rng.integers(0, n_r, m)
            cols = np.clip(rows * n_c // n_r + rng.integers(-3, 4, m),
                           0, n_c - 1)
        else:
            rows = rng.integers(0, n_r, m)
            cols = np.zeros(m, np.int64)
        vals = rng.standard_normal(m).astype(np.float32)
        try:
            S = sp.coo_matrix((vals, (rows, cols)),
                              shape=(n_r, n_c)).tocsr()
            plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                            n_rows=n_r, n_cols=n_c)
            if plan is None:
                continue
            x = rng.standard_normal(n_c).astype(np.float32)
            want = S @ x
            scale = max(float(np.abs(want).max()), 1.0)
            got = np.asarray(spmv_lib.spmv(plan, jnp.asarray(x)))
            np.testing.assert_allclose(got / scale, want / scale,
                                       rtol=tol, atol=tol)
            k = int(rng.integers(1, 9))
            X = rng.standard_normal((n_c, k)).astype(np.float32)
            got2 = np.asarray(spmv_lib.spmm(plan, jnp.asarray(X)))
            np.testing.assert_allclose(got2 / scale, (S @ X) / scale,
                                       rtol=tol, atol=tol)
            # compact-table Pallas scatter (interpret off-TPU)
            from matrel_tpu.ops import pallas_spmv as pc
            from matrel_tpu.config import on_tpu
            interp = not on_tpu()
            got3 = np.asarray(pc.spmv_compact(plan, jnp.asarray(x),
                                              interpret=interp))
            np.testing.assert_allclose(got3 / scale, want / scale,
                                       rtol=tol, atol=tol)
        except Exception as ex:  # noqa: BLE001
            fails.append((trial, style, n_r, n_c, m,
                          type(ex).__name__, str(ex)[:150]))
    return fails


def soak_sharded(n_trials: int, base: int, tol: float):
    """Mesh-sharded sparse paths vs scipy oracles: tile-stack SpMM
    (spmm_sharded) and one-hot sharded SpMV (spmv_sharded)."""
    import numpy as np
    import scipy.sparse as sp
    import jax.numpy as jnp
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.ops import spmv as spmv_lib

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            # tile-stack SpMM over the mesh
            bs = int(rng.choice([4, 8, 16]))
            gr = int(rng.integers(1, 12))
            gc = int(rng.integers(1, 12))
            n, k = gr * bs, gc * bs
            dens = float(rng.uniform(0.05, 0.9))
            a = np.zeros((n, k), np.float32)
            for f in range(gr * gc):
                if rng.random() < dens:
                    bi, bj = f // gc, f % gc
                    a[bi*bs:(bi+1)*bs, bj*bs:(bj+1)*bs] = \
                        rng.standard_normal((bs, bs))
            w = int(rng.integers(1, 33))
            d = rng.standard_normal((k, w)).astype(np.float32)
            S = BlockSparseMatrix.from_numpy(a, block_size=bs, mesh=mesh)
            if S.nnzb:
                got = S.shard().multiply(
                    BlockMatrix.from_numpy(d, mesh=mesh)).to_numpy()
                np.testing.assert_allclose(got, a @ d, rtol=tol, atol=tol)

            # tile-intersection SpGEMM (plain + sharded) vs oracle
            from matrel_tpu.ops import spgemm as spgemm_lib
            gm = int(rng.integers(1, 12))
            b = np.zeros((k, gm * bs), np.float32)
            for f in range(gc * gm):
                if rng.random() < dens:
                    bi, bj = f // gm, f % gm
                    b[bi*bs:(bi+1)*bs, bj*bs:(bj+1)*bs] = \
                        rng.standard_normal((bs, bs))
            B2 = BlockSparseMatrix.from_numpy(b, block_size=bs,
                                              mesh=mesh)
            want = a @ b
            got = spgemm_lib.spgemm(S, B2).to_numpy()
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
            got = spgemm_lib.spgemm_sharded(S, B2).to_numpy()
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

            # sharded one-hot SpMV
            n_r = int(rng.integers(64, 4000))
            n_c = int(rng.integers(64, 4000))
            m = int(rng.integers(1, 20_000))
            rows = rng.integers(0, n_r, m)
            cols = rng.integers(0, n_c, m)
            vals = rng.standard_normal(m).astype(np.float32)
            plan = spmv_lib.build_spmv_plan(rows, cols, vals,
                                            n_rows=n_r, n_cols=n_c)
            if plan is not None:
                plan_s = spmv_lib.shard_plan(plan, mesh)
                x = rng.standard_normal(n_c).astype(np.float32)
                want = sp.coo_matrix((vals, (rows, cols)),
                                     shape=(n_r, n_c)) @ x
                scale = max(float(np.abs(want).max()), 1.0)
                got = np.asarray(spmv_lib.spmv_sharded(plan_s, x, mesh))
                np.testing.assert_allclose(got / scale, want / scale,
                                           rtol=tol, atol=tol)

            # topology-weighted planning (round 7): random per-axis
            # weights re-route strategy choices — whatever the weighted
            # pick, execution must stay oracle-exact, and the verifier
            # (incl. MV106's slow-axis pass) must find nothing to flag
            # on the planner's own output
            from matrel_tpu import analysis
            from matrel_tpu.config import MatrelConfig
            from matrel_tpu.executor import execute
            from matrel_tpu.parallel import planner as pl
            wcfg = MatrelConfig(
                axis_cost_weights=(float(rng.choice([1.0, 2.0, 16.0])),
                                   float(rng.choice([1.0, 8.0, 32.0]))),
                comm_alpha_bytes=float(rng.choice([0.0, 200_000.0])))
            wn = int(rng.integers(2, 9)) * 8
            wk = int(rng.integers(2, 9)) * 8
            wm = int(rng.integers(2, 9)) * 8
            wa = rng.standard_normal((wn, wk)).astype(np.float32)
            wb = rng.standard_normal((wk, wm)).astype(np.float32)
            wc = rng.standard_normal((wm, wn)).astype(np.float32)
            wexpr = (BlockMatrix.from_numpy(wa, mesh=mesh).expr()
                     .multiply(BlockMatrix.from_numpy(wb, mesh=mesh)
                               .expr())
                     .multiply(BlockMatrix.from_numpy(wc, mesh=mesh)
                               .expr()))
            wann = pl.annotate_strategies(wexpr, mesh, wcfg)
            diags = analysis.verify_plan(wann, mesh, wcfg)
            assert not [d for d in diags if d.code == "MV106"], diags
            got_w = execute(wann, mesh, wcfg).to_numpy()
            np.testing.assert_allclose(got_w, wa @ wb @ wc,
                                       rtol=5e-3, atol=5e-3)
        except Exception as ex:  # noqa: BLE001
            fails.append(("sharded", trial, type(ex).__name__,
                          str(ex)[:150]))
    return fails


def soak_sparse_kernels(n_trials: int, base: int, tol: float):
    """Sparse kernel-registry battery (round 11): random matrices
    drawn PER structure class × EVERY registered kernel forced via the
    config override, each checked against the numpy oracle; one
    rotating kernel per trial additionally runs the full
    executor/planner path — annotated plan verified clean (MV104 +
    MV110) and the structural no-densify guarantee re-asserted with a
    poisoned ``to_dense`` (the test_spgemm acceptance idiom, per
    variant)."""
    import numpy as np
    from matrel_tpu import analysis, executor as executor_lib
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.ops import kernel_registry as kr
    from matrel_tpu.ops import spgemm as spgemm_lib
    from matrel_tpu.parallel import planner

    mesh = mesh_lib.make_mesh()
    fails = []
    structures = ("row_band", "clustered_tile", "powerlaw_coo",
                  "generic")
    kids = kr.kernel_ids()
    for trial in range(base, base + n_trials):
        rng = np.random.default_rng(trial)
        try:
            structure = structures[trial % len(structures)]
            bs = int(rng.choice([8, 16]))
            n = bs * int(rng.integers(48, 72))
            A = kr.synthesize_structure(structure, n, bs, mesh,
                                        seed=trial)
            B = kr.synthesize_structure(structure, n, bs, mesh,
                                        seed=trial + 17)
            ref = A.to_numpy() @ B.to_numpy()
            scale = max(float(np.abs(ref).max()), 1.0)
            for kid in kids:
                cfg = MatrelConfig(pallas_interpret=True, block_size=bs,
                                   spgemm_kernel_override=kid)
                got = spgemm_lib.spgemm(A, B, cfg).to_numpy()
                np.testing.assert_allclose(got / scale, ref / scale,
                                           rtol=tol, atol=tol)
            # full executor path for one rotating kernel per trial
            # (compiles are the expensive part of this battery)
            kid = kids[trial % len(kids)]
            cfg = MatrelConfig(pallas_interpret=True, block_size=bs,
                               spgemm_kernel_override=kid)
            e = A.multiply(B)
            if not executor_lib._spgemm_dispatch(e, cfg):
                continue
            ann = planner.annotate_strategies(e, mesh, cfg)
            assert ann.attrs.get("spgemm_kernel") == kid, \
                (kid, ann.attrs.get("spgemm_kernel"))
            bad = [d for d in analysis.verify_plan(ann, mesh, cfg)
                   if d.code in ("MV104", "MV110")]
            assert not bad, bad
            orig = BlockSparseMatrix.to_dense

            def _boom(self, *a, **k):
                raise AssertionError(
                    "SpGEMM kernel variant densified an operand")

            BlockSparseMatrix.to_dense = _boom
            try:
                out = executor_lib.execute(ann, mesh, cfg)
            finally:
                BlockSparseMatrix.to_dense = orig
            np.testing.assert_allclose(
                out.to_numpy()[:n, :n] / scale, ref / scale,
                rtol=tol, atol=tol)
        except Exception as ex:  # noqa: BLE001 — soak collects all
            fails.append(("spk", trial, type(ex).__name__,
                          str(ex)[:200]))
    return fails


def soak_fusion(n_trials: int, base: int, tol: float):
    """Whole-plan fusion battery (round 12): random elementwise/
    reduction chains over DENSE, S×S (block-sparse) and COO producers
    executed with fusion FORCED ON against numpy oracles, per
    precision tier on the dense trials — and, every trial, the fused
    run compared tightly against the staged (fusion-off) run of the
    SAME expression, which must agree to float noise (identical member
    lowerings, one program boundary apart). A rotating
    fusion-boundary pass additionally compiles one trial per round
    under ``verify_plans="error"`` so a boundary MV111 would reject
    can never reach execution."""
    import numpy as np
    from matrel_tpu import analysis, executor as executor_lib
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.coo import COOMatrix
    from matrel_tpu.ops import kernel_registry as kr
    from matrel_tpu.parallel import planner

    mesh = mesh_lib.make_mesh()
    fails = []
    producers = ("dense", "sxs", "coo")
    tiers = ("default", "float32", "high", "fast")
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            producer = producers[trial % len(producers)]
            sla = tiers[trial % len(tiers)] if producer == "dense" \
                else "default"
            n = int(rng.choice([24, 32, 48]))
            if producer == "dense":
                a = rng.standard_normal((n, n)).astype(np.float32)
                b = rng.standard_normal((n, n)).astype(np.float32)
                A = BlockMatrix.from_numpy(a, mesh=mesh)
                B = BlockMatrix.from_numpy(b, mesh=mesh)
                e = A.expr().multiply(B.expr())
                ref = a.astype(np.float64) @ b.astype(np.float64)
            elif producer == "sxs":
                bs = int(rng.choice([8, 16]))
                n = bs * int(rng.integers(16, 32))
                SA = kr.synthesize_structure("row_band", n, bs, mesh,
                                             seed=base + trial)
                SB = kr.synthesize_structure("row_band", n, bs, mesh,
                                             seed=base + trial + 9)
                e = SA.multiply(SB)
                ref = (SA.to_numpy().astype(np.float64)
                       @ SB.to_numpy().astype(np.float64))
            else:
                nnz = max(8, 3 * n)
                flat = rng.choice(n * n, size=min(nnz, n * n),
                                  replace=False)
                rows, cols = flat // n, flat % n
                vals = rng.standard_normal(rows.size).astype(
                    np.float32)
                C = COOMatrix.from_edges(rows, cols, vals, (n, n))
                d = rng.standard_normal((n, 4)).astype(np.float32)
                D = BlockMatrix.from_numpy(d, mesh=mesh)
                e = C.expr().multiply(D.expr())
                cd = np.zeros((n, n), np.float64)
                cd[rows, cols] = vals.astype(np.float64)
                ref = cd @ d.astype(np.float64)
            # random fusable chain over the producer (the oracle
            # follows along in float64)
            for _ in range(int(rng.integers(2, 6))):
                op = int(rng.integers(0, 5))
                if op == 0:
                    s = float(rng.uniform(-2, 2))
                    e, ref = e.multiply_scalar(s), ref * s
                elif op == 1:
                    s = float(rng.uniform(-1, 1))
                    e, ref = e.add_scalar(s), ref + s
                elif op == 2:
                    w = rng.standard_normal(ref.shape).astype(
                        np.float32)
                    W = BlockMatrix.from_numpy(w, mesh=mesh)
                    e = e.add(W.expr())
                    ref = ref + w.astype(np.float64)
                elif op == 3:
                    w = rng.standard_normal(ref.shape).astype(
                        np.float32)
                    W = BlockMatrix.from_numpy(w, mesh=mesh)
                    e = e.elem_multiply(W.expr())
                    ref = ref * w.astype(np.float64)
                else:
                    if ref.shape[0] > 1:
                        e, ref = e.row_sum(), ref.sum(
                            axis=1, keepdims=True)
            cfg_on = MatrelConfig(fusion_enable=True,
                                  precision_sla=sla)
            cfg_off = cfg_on.replace(fusion_enable=False)
            out_on = executor_lib.execute(e, mesh, cfg_on).to_numpy()
            out_off = executor_lib.execute(e, mesh,
                                           cfg_off).to_numpy()
            lr, lc = ref.shape
            scale = max(float(np.abs(ref).max()), 1.0)
            # bf16 tiers carry their documented looser bound; the
            # fused-vs-staged comparison below stays TIGHT per tier
            tier_tol = {"high": 2 * tol, "fast": 2e-2}.get(sla, tol)
            np.testing.assert_allclose(
                out_on[:lr, :lc] / scale, ref / scale,
                rtol=tier_tol, atol=tier_tol)
            np.testing.assert_allclose(
                out_on / scale, out_off / scale,
                rtol=1e-5, atol=1e-5)
            if trial % 3 == 0:
                # rotating fusion-boundary pass: the annotated fused
                # plan verifies clean and compiles under the error
                # gate (nothing MV111 rejects may execute)
                opt = planner.annotate_strategies(
                    __import__("matrel_tpu.ir.rules",
                               fromlist=["optimize"]).optimize(
                        e, cfg_on), mesh, cfg_on)
                from matrel_tpu.ir import fusion as fusion_lib
                opt = fusion_lib.annotate_fusion(opt, mesh, cfg_on)
                bad = [d for d in analysis.verify_plan(opt, mesh,
                                                       cfg_on)
                       if d.code == "MV111"
                       and d.severity == "error"]
                assert not bad, bad
                executor_lib.compile_expr(
                    e, mesh, cfg_on.replace(verify_plans="error"))
        except Exception as ex:  # noqa: BLE001 — soak collects all
            fails.append(("fusion", trial, type(ex).__name__,
                          str(ex)[:200]))
    return fails


def soak_serve(n_trials: int, base: int, tol: float):
    """Serving-layer battery: a random query stream (with heavy
    repetition, so the result cache and the MultiPlan plan cache both
    get real traffic) served through session.run_many / session.run
    with the cross-query result cache ON must match the numpy oracle
    QUERY-FOR-QUERY — reuse may never change an answer. Mid-stream a
    catalog rebind exercises invalidation under load."""
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            n = int(rng.choice([16, 24, 32]))
            mats_np = [rng.standard_normal((n, n)).astype(np.float32)
                       for _ in range(3)]
            mats = [BlockMatrix.from_numpy(a, mesh=mesh)
                    for a in mats_np]

            def rand_query(depth=0):
                """(expr, numpy oracle) pairs over the shared mats."""
                kind = int(rng.integers(0, 6 if depth < 2 else 3))
                if kind in (0, 1, 2) or depth >= 2:
                    i = int(rng.integers(0, len(mats)))
                    return mats[i].expr(), mats_np[i]
                a, na = rand_query(depth + 1)
                b, nb = rand_query(depth + 1)
                if kind == 3:
                    return a.multiply(b), na @ nb
                if kind == 4:
                    return a.add(b), na + nb
                s = float(rng.uniform(-2, 2))
                return a.multiply_scalar(s).t(), (na * s).T

            pool = [rand_query() for _ in range(int(rng.integers(3, 7)))]
            stream = [pool[int(rng.integers(0, len(pool)))]
                      for _ in range(3 * len(pool))]
            sess = MatrelSession(mesh=mesh, config=MatrelConfig(
                result_cache_max_bytes=32 << 20))
            sess.register("t0", mats[0])
            i = 0
            rebound = False
            while i < len(stream):
                if rng.random() < 0.5:
                    bs = int(rng.integers(1, 5))
                    chunk = stream[i:i + bs]
                    outs = sess.run_many([e for e, _ in chunk])
                else:
                    chunk = stream[i:i + 1]
                    outs = [sess.run(chunk[0][0])]
                for (e, want), out in zip(chunk, outs):
                    scale = max(float(np.abs(want).max()), 1.0)
                    np.testing.assert_allclose(
                        out.to_numpy() / scale, want / scale,
                        rtol=tol, atol=tol)
                i += len(chunk)
                if not rebound and i >= len(stream) // 2:
                    # rebind under load: dependent entries must drop.
                    # Crossed-midpoint flag, not equality — variable
                    # chunk sizes jump over any exact index, and an
                    # equality check would silently skip the very
                    # behaviour this battery claims to soak
                    sess.register("t0", mats[1])
                    rebound = True
        except Exception as ex:  # noqa: BLE001
            fails.append(("serve", trial, type(ex).__name__,
                          str(ex)[:150]))
    return fails


def soak_cse(n_trials: int, base: int, tol: float):
    """Multi-query-optimization battery (serve/mqo.py;
    docs/SERVING.md): every trial builds batches with SEEDED shared
    interiors — a dense Gram polynomial, an S×S block-sparse product,
    a COO SpMV — under a random precision tier, runs them through a
    ``cse_enable`` session, and checks every answer against the numpy
    oracle query-for-query (sharing may never change an answer).
    Also per trial: at least one interior actually HOISTS (a battery
    that never shares proves nothing); MV116's dynamic pass proves
    every remembered substitution against unshared execution; a
    catalog rebind mid-trial invalidates the hoisted node's cached
    result and the same structural batch over the NEW binding must
    answer from fresh data (a stale hoist is a wrong answer the
    oracle catches); and a fleet-routed repeat (fleet_slices=2) runs
    a shared-interior batch through placement."""
    import numpy as np
    from matrel_tpu.analysis import cse_pass
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.coo import COOMatrix
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            n = int(rng.choice([16, 24, 32]))
            k = int(rng.integers(3, 6))
            sla = str(rng.choice(["default", "high", "exact"]))
            x_np = rng.standard_normal((n, n)).astype(np.float32)
            y_np = rng.standard_normal((n, n)).astype(np.float32)
            X = BlockMatrix.from_numpy(x_np, mesh=mesh)
            Y = BlockMatrix.from_numpy(y_np, mesh=mesh)
            sess = MatrelSession(mesh=mesh, config=MatrelConfig(
                cse_enable=True, precision_sla=sla,
                result_cache_max_bytes=16 << 20))
            sess.register("src", X)

            def check(outs, oracles):
                for out, want in zip(outs, oracles):
                    scale = max(float(np.abs(want).max()), 1.0)
                    np.testing.assert_allclose(
                        out.to_numpy().astype(np.float64) / scale,
                        want / scale, rtol=tol, atol=tol)

            def gram_batch(M, m_np):
                g = M.expr().t().multiply(M.expr())
                go = m_np.astype(np.float64).T @ m_np.astype(
                    np.float64)
                ss = [float(rng.uniform(0.5, 2.0)) for _ in range(k)]
                return ([g.multiply_scalar(s) for s in ss],
                        [go * s for s in ss])

            # dense Gram interior, shared across k scalar variants
            qs, oracles = gram_batch(X, x_np)
            check(sess.run_many(qs), oracles)

            # S×S block-sparse product interior (SpGEMM output feeds
            # every variant)
            sp = __import__("scipy.sparse", fromlist=["random"])
            s_sp = sp.random(n, n, density=0.3, random_state=int(
                rng.integers(1 << 30)), dtype=np.float32)
            S = BlockSparseMatrix.from_scipy(s_sp, block_size=8,
                                             mesh=mesh)
            s_np = s_sp.toarray().astype(np.float64)
            gs = S.expr().multiply(S.expr())
            so = s_np @ s_np
            sqs = [gs.multiply_scalar(1.0 + i) for i in range(k)]
            check(sess.run_many(sqs), [so * (1.0 + i)
                                       for i in range(k)])

            # COO SpMV interior: A_coo · X dense, shared by variants
            c_sp = sp.random(n, n, density=0.05, random_state=int(
                rng.integers(1 << 30)), dtype=np.float32)
            C = COOMatrix.from_scipy(c_sp.tocoo()).shard(mesh)
            c_np = c_sp.toarray().astype(np.float64)
            gc = C.expr().multiply(X.expr())
            co = c_np @ x_np.astype(np.float64)
            cqs = [gc.multiply_scalar(2.0 + i) for i in range(k)]
            check(sess.run_many(cqs), [co * (2.0 + i)
                                       for i in range(k)])

            info = sess.mqo_info()
            assert info["cse_hoisted"] >= 1, info
            diags = cse_pass.verify_cse_executions(sess)
            assert diags == [], [d.render() for d in diags]

            # rebind invalidation: the hoisted Gram's source rebinds;
            # the same STRUCTURE over the new binding must answer
            # from fresh data, never the stale hoisted result
            sess.register("src", Y)
            qs2, oracles2 = gram_batch(Y, y_np)
            check(sess.run_many(qs2), oracles2)

            # fleet-routed repeat: the shared-interior batch through
            # placement over 2 slices, same oracle contract
            fsess = MatrelSession(mesh=mesh, config=MatrelConfig(
                cse_enable=True, precision_sla=sla, fleet_slices=2,
                result_cache_max_bytes=16 << 20))
            fq, fo = gram_batch(X, x_np)
            check(fsess.run_many(fq), fo)
        except Exception as ex:  # noqa: BLE001
            fails.append(("cse", trial, type(ex).__name__,
                          str(ex)[:150]))
    return fails


def soak_stream(n_trials: int, base: int, tol: float):
    """Streaming-graph IVM battery (docs/IVM.md): a sliding-window
    edge stream (workloads/streaming.py) drives register_delta ticks
    over the dashboard query set, and EVERY tick's every answer is
    checked against the numpy oracle — the integer queries (degrees,
    label counts, common neighbors, trace(A³)) BIT-EXACTLY, so a
    wrong patch can never hide in a tolerance. Also covered per
    trial: an INELIGIBLE query (select_value — no delta rule) rides
    the stream and must fall back to kill-and-recompute correctly;
    MV113's dynamic check proves every surviving patched entry
    against fresh execution; the PageRank warm restart lands on the
    cold-start fixed point; and at least one entry actually PATCHED
    (a battery that silently recomputed everything proves nothing)."""
    import numpy as np
    from matrel_tpu.analysis import delta_pass
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.ir.delta import pagerank_warm_restart
    from matrel_tpu.session import MatrelSession
    from matrel_tpu.workloads.streaming import StreamingGraph

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            n = int(rng.choice([96, 128, 160]))
            batch = int(rng.choice([2, 3, 4]))
            sess = MatrelSession(mesh=mesh, config=MatrelConfig(
                result_cache_max_bytes=256 << 20))
            g = StreamingGraph(sess, n=n, batch_edges=batch,
                               window=int(rng.integers(3, 7)),
                               feature_k=16, seed=base + trial)
            thresh = float(rng.uniform(0.5, 1.5))
            def ineligible():
                # select_value has no delta rule — this entry MUST
                # fall back to the transitive kill and recompute
                return sess.table(g.name).expr().select_value(
                    lambda v: v > thresh).sum()
            g.run_all()
            sess.run(ineligible())
            g.pagerank()        # seed the cached vector: the check
            total_patched = 0   # after the ticks must be a WARM call
            for _tick in range(int(rng.integers(3, 6))):
                s = g.step_delta()
                total_patched += s["patched"]
                got = g.run_all()
                want = g.oracle()
                for k in got:
                    w = np.asarray(want[k], np.float32).reshape(
                        got[k].shape)
                    err = float(np.abs(got[k] - w).max())
                    exact = k != "feature_product"
                    if (err != 0.0) if exact else (err > tol):
                        raise AssertionError(
                            f"tick answer wrong: {k} err={err}")
                ineo = sess.run(ineligible()).to_numpy()
                wo = (g.adj * (g.adj > thresh)).sum()
                if abs(float(ineo[0, 0]) - float(wo)) > tol * max(
                        abs(wo), 1.0):
                    raise AssertionError(
                        "ineligible-query fallback answered wrong")
                diags = delta_pass.verify_patched_entries(sess)
                if diags:
                    raise AssertionError(
                        f"MV113: {diags[0].render()[:140]}")
            if total_patched == 0:
                raise AssertionError(
                    "stream never patched a single entry — the "
                    "battery exercised nothing")
            assert g._pr is not None   # seeded above — this IS warm
            pr = g.pagerank(rounds=80)
            cold = pagerank_warm_restart(
                g.adj.astype(np.float64),
                np.full(g.n, 1.0 / g.n), rounds=300)
            if float(np.abs(pr - cold).sum()) > 1e-5:
                raise AssertionError("pagerank warm restart drifted "
                                     "off the cold fixed point")
        except Exception as ex:  # noqa: BLE001
            fails.append(("stream", trial, type(ex).__name__,
                          str(ex)[:150]))
    return fails


def soak_fleet(n_trials: int, base: int, tol: float):
    """Multi-slice fleet battery (docs/FLEET.md): a randomized
    catalog + query stream served through a 2-/3-slice fleet with a
    random slice KILLED mid-stream. Every resolved answer is checked
    against its numpy oracle (ZERO wrong answers — a failover that
    rebinds onto the wrong replica would show up here, not as a
    crash), every failure must be TYPED (ResilienceError family), the
    directory must have answered repeats (hits > 0), and the stream
    must COMPLETE: at least one post-kill answer resolves on a
    survivor. Randomized per trial: slice count, replication
    threshold, stream composition, kill point and victim."""
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.resilience.errors import ResilienceError
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        sess = None
        try:
            n = int(rng.choice([48, 64, 96]))
            n_slices = int(rng.choice([2, 3]))
            cfg = MatrelConfig(
                fleet_slices=n_slices,
                result_cache_max_bytes=128 << 20,
                serve_max_batch=1,
                fleet_replicate_hits=int(rng.choice([0, 1, 3])))
            sess = MatrelSession(mesh=mesh, config=cfg)
            mats = {}
            for nm in ("A", "B", "C"):
                arr = rng.standard_normal((n, n)).astype(np.float32)
                mats[nm] = arr
                sess.register(nm, sess.from_numpy(arr))
            A = sess.table("A").expr()
            B = sess.table("B").expr()
            C = sess.table("C").expr()
            oAB = mats["A"] @ mats["B"]
            templates = [
                (A.multiply(B), oAB),
                (A.multiply(B).multiply_scalar(2.0), 2.0 * oAB),
                (A.multiply(B.multiply(C)),
                 mats["A"] @ (mats["B"] @ mats["C"])),
                (A.add(B).multiply(C),
                 (mats["A"] + mats["B"]) @ mats["C"]),
                (A.t().multiply(B).add_scalar(1.0),
                 mats["A"].T @ mats["B"] + 1.0),
            ]
            stream_len = int(rng.integers(20, 36))
            picks = rng.integers(0, len(templates), size=stream_len)
            kill_at = int(rng.integers(stream_len // 4,
                                       3 * stream_len // 4))
            victim = int(rng.integers(0, n_slices))
            futs = []
            for i, p in enumerate(picks):
                futs.append((int(p), sess.submit(templates[p][0])))
                if i % 6 == 5:
                    # paced bursts: every sixth submission waits, so
                    # directory inserts land mid-stream and later
                    # repeats exercise the hit-anywhere protocol
                    # (a fully-async stream would outrun every
                    # insert and prove nothing about the directory)
                    try:
                        futs[-1][1].result(timeout=120)
                    except ResilienceError:
                        pass
                if i == kill_at:
                    sess._fleet.kill_slice(victim)
            sess.serve_drain(timeout=120)
            wrong = untyped = 0
            post_kill_ok = 0
            for j, (p, fut) in enumerate(futs):
                try:
                    out = fut.result(timeout=120)
                    got = np.asarray(out.to_numpy())
                    want = templates[p][1]
                    err = float(np.abs(got - want).max())
                    if err > tol * max(float(np.abs(want).max()),
                                       1.0):
                        wrong += 1
                    elif j > kill_at:
                        post_kill_ok += 1
                except ResilienceError:
                    pass                  # typed — the contract
                except Exception:
                    untyped += 1
            info = sess.fleet_info()
            if wrong:
                raise AssertionError(f"{wrong} wrong answers")
            if untyped:
                raise AssertionError(f"{untyped} untyped failures")
            if post_kill_ok == 0:
                raise AssertionError(
                    "stream did not complete past the kill")
            if info["failovers"] != 1:
                raise AssertionError(
                    f"failovers={info['failovers']} (expected 1)")
            if info["directory"]["hits"] == 0:
                raise AssertionError("directory never answered")
            alive = [sl for sl in info["slices"] if sl["alive"]]
            if len(alive) != n_slices - 1:
                raise AssertionError("wrong surviving-slice census")
            sess.serve_close(timeout=60)
            print(f"  fleet trial {trial + 1}/{n_trials} ok")
        except Exception as e:  # noqa: BLE001 — tally and continue
            fails.append(f"fleet trial {trial}: {type(e).__name__} {e}")
            print(f"  FAIL {fails[-1]}")
        finally:
            # a FAILED trial must still tear its fleet down — leaked
            # slice sessions (worker threads + replicated catalogs)
            # would distort every later trial on the shared host
            if sess is not None:
                try:
                    sess.serve_close(timeout=60)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
    return fails


def soak_race(n_trials: int, base: int, tol: float):
    """Concurrency battery (docs/CONCURRENCY.md): the race_drill
    schedules — submit/close/drain, kill-during-replication,
    rebind-vs-template-probes, delta-under-load — each run n_trials
    seeds with runtime lockdep armed. A trial fails on a wrong
    answer, an untyped failure, a recorded lock-order inversion, or a
    cyclic order graph; failures reproduce by (schedule, seed)."""
    from matrel_tpu.utils import lockdep
    from tools import race_drill

    fails = []
    for name, fn in race_drill.SCHEDULES.items():
        for trial in range(n_trials):
            seed = base + trial
            lockdep.reset()
            try:
                res = fn(seed, 10)
                diags = lockdep.diagnostics()
                bad = []
                if res["wrong"]:
                    bad.append(f"{res['wrong']} wrong")
                if res["untyped"]:
                    bad.append(f"{res['untyped']} untyped")
                inv = sum(1 for d in diags
                          if d["diag"] in ("inversion",
                                           "self_deadlock"))
                if inv:
                    bad.append(f"{inv} lockdep inversion(s)")
                if not lockdep.is_acyclic():
                    bad.append("cyclic lock-order graph")
                if bad:
                    raise AssertionError("; ".join(bad))
                print(f"  race {name} trial {trial + 1}/{n_trials} ok")
            except Exception as e:  # noqa: BLE001 — tally and continue
                fails.append(f"race {name} seed {seed}: "
                             f"{type(e).__name__} {e}")
                print(f"  FAIL {fails[-1]}")
    lockdep.reset()
    lockdep.disable()
    return fails


def soak_precision(n_trials: int, base: int, tol: float):
    """Precision-SLA battery: random matmul-shaped queries executed at
    every SLA tier against an f64 numpy oracle, asserting the
    DOCUMENTED per-tier error bound (planner.tier_error_bound — the
    docs/PRECISION.md table: bf16x3 within ~f32 tolerance, bf16x1
    within the single-pass bf16 bound, int paths EXACT), including
    under the sharded 8-device mesh and with result-cache tier
    isolation live (a "fast" entry must never answer an "exact"
    probe — checked by running the same stream at two SLAs through one
    cache-on session and oracle-checking both)."""
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.executor import compile_expr
    from matrel_tpu.parallel import planner
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(n_trials):
        rng = np.random.default_rng(base + trial)
        try:
            n = int(rng.integers(2, 12)) * 8
            k = int(rng.integers(2, 12)) * 8
            m = int(rng.integers(2, 12)) * 8
            a = rng.uniform(-1.0, 1.0, (n, k)).astype(np.float32)
            b = rng.uniform(-1.0, 1.0, (k, m)).astype(np.float32)
            c = rng.uniform(-1.0, 1.0, (m, n)).astype(np.float32)
            A = BlockMatrix.from_numpy(a, mesh=mesh)
            B = BlockMatrix.from_numpy(b, mesh=mesh)
            C = BlockMatrix.from_numpy(c, mesh=mesh)
            # two chained contractions: error bounds must hold through
            # the composition, not just one product
            want = (a.astype(np.float64) @ b.astype(np.float64)
                    @ c.astype(np.float64))
            for sla, tiers in (("exact", ("f32",)),
                               ("high", ("bf16x3", "f32")),
                               ("fast", ("bf16x1",)),
                               ("bfloat16", ("bf16x1",)),
                               ("bf16x3", ("bf16x3",))):
                cfg = MatrelConfig(precision_sla=sla)
                expr = A.expr().multiply(B.expr()).multiply(C.expr())
                plan = compile_expr(expr, mesh, cfg)
                got = plan.run().to_numpy().astype(np.float64)
                # documented bound, composed over both contractions:
                # bound(A·B) propagates through the second multiply
                # (× m·max|C|) and the second contraction adds its own
                worst = max(planner.TIER_EPS[t] for t in tiers)
                bound = (worst * k * 1.0 * 1.0) * m * 1.0 \
                    + worst * m * (k * 1.0) * 1.0
                err = float(np.abs(got - want).max())
                assert err <= max(bound, 64 * tol), \
                    (sla, err, bound)
            # integer-exact path, sharded: "exact" on integral inputs
            # must be EXACT, not merely close
            ai = rng.integers(-3, 4, (n, k))
            bi = rng.integers(-3, 4, (k, m))
            Ai = BlockMatrix.from_numpy(ai, mesh=mesh)
            Bi = BlockMatrix.from_numpy(bi, mesh=mesh)
            cfg = MatrelConfig(precision_sla="exact")
            plan = compile_expr(Ai.expr().multiply(Bi.expr()), mesh,
                                cfg)
            got_i = plan.run().to_numpy()
            assert got_i.dtype == np.int32, got_i.dtype
            assert np.array_equal(got_i, ai @ bi)
            # result-cache tier isolation under load: one cache-on
            # session serves the same query at "fast" then "exact" —
            # the exact answer must be exact (a cross-tier hit would
            # hand back the bf16 result)
            sess = MatrelSession(mesh=mesh, config=MatrelConfig(
                result_cache_max_bytes=16 << 20))
            qi = Ai.expr().multiply(Bi.expr())
            fast = sess.run(qi, precision="fast")
            assert fast.dtype == np.float32       # bf16x1 path ran
            exact = sess.run(qi, precision="exact")
            # dtype is the non-vacuous discriminator: small-int bf16
            # products are VALUE-exact, so a cross-tier hit would
            # still match the oracle — but it could never be int32
            assert exact.dtype == np.int32, "cross-tier rc hit"
            assert np.array_equal(exact.to_numpy(), ai @ bi)
        except Exception as ex:  # noqa: BLE001
            fails.append(("precision", trial, type(ex).__name__,
                          str(ex)[:150]))
    return fails


def soak_chaos(n_trials: int, base: int, tol: float):
    """Randomized chaos: each trial builds a session with a RANDOM
    seeded fault schedule (random sites, kinds, probabilities) and
    runs a small mixed query stream against numpy oracles. The
    resilience contract under soak: every query either converges to
    the correct answer (retries + degradation ladder) or fails with a
    TYPED error attributable to a deterministic fault — never a wrong
    answer, never an unclassified crash, never a hang."""
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.resilience import errors as rerrors, faults
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(base, base + n_trials):
        rng = np.random.default_rng(trial)
        # total transient fire budget (sum of max=) stays STRICTLY
        # below retry_max_attempts: the stream must be able to absorb
        # every transient even if one query eats the whole budget —
        # otherwise "transient escaped the retry loop" would be a
        # legitimate outcome and the battery seed-flaky, not a check
        sites = list(rng.choice(faults.SITES,
                                size=int(rng.integers(1, 4)),
                                replace=False))
        has_fatal = bool(rng.random() < 0.3)
        rules = [f"{s}:transient:p={float(rng.uniform(0.05, 0.3)):.3f}"
                 f":max=1" for s in sites]
        if has_fatal:
            # one deterministic one-shot fault somewhere in the stream
            rules.append(
                f"{str(rng.choice(faults.SITES))}:fatal"
                f":n={int(rng.integers(1, 20))}")
        try:
            faults.reset()
            cfg = MatrelConfig(
                fault_inject=";".join(rules),
                fault_inject_seed=trial,
                retry_max_attempts=6, retry_backoff_ms=1.0,
                result_cache_max_bytes=(1 << 24
                                        if trial % 2 else 0))
            sess = MatrelSession(mesh=mesh, config=cfg)
            n = int(rng.choice([16, 32, 48]))
            an = rng.standard_normal((n, n)).astype(np.float32)
            bn = rng.standard_normal((n, n)).astype(np.float32)
            A, B = sess.from_numpy(an), sess.from_numpy(bn)
            for q in range(6):
                e = (A.expr().multiply(B.expr())
                     .multiply_scalar(float(q + 1)))
                want = an @ bn * (q + 1)
                try:
                    got = sess.run(e).to_numpy()
                except rerrors.InjectedFault as ex:
                    # only a DETERMINISTIC injected fault may surface
                    if ex.transient:
                        raise AssertionError(
                            f"transient fault escaped the retry "
                            f"loop: {ex}") from ex
                    continue
                np.testing.assert_allclose(got, want, rtol=tol,
                                           atol=tol)
            # batch surface too, same contract
            try:
                outs = sess.run_many(
                    [A.expr().multiply(B.expr()),
                     B.expr().multiply(A.expr())])
                np.testing.assert_allclose(outs[0].to_numpy(), an @ bn,
                                           rtol=tol, atol=tol)
                np.testing.assert_allclose(outs[1].to_numpy(), bn @ an,
                                           rtol=tol, atol=tol)
            except rerrors.InjectedFault as ex:
                if ex.transient:
                    raise AssertionError(
                        f"transient fault escaped run_many: "
                        f"{ex}") from ex
        except Exception as ex:  # noqa: BLE001 — soak collects all
            fails.append(("chaos", trial, type(ex).__name__,
                          str(ex)[:200]))
    faults.reset()
    return fails


def soak_overload(n_trials: int, base: int, tol: float):
    """Randomized overload-control soak (docs/OVERLOAD.md): each trial
    drives seeded open-loop-ish bursts of tenant-tagged submissions
    through a session with weighted-fair admission, tight quotas, an
    aggressive brownout controller, circuit breakers AND a PR 8 fault
    schedule (capped transient fires + fatal execute fires). The
    contract under soak: every admitted query either matches its
    numpy oracle or fails TYPED (shed / deadline / circuit / injected
    — never a wrong answer, never an unclassified crash), and after
    the fault window every breaker closes again (a probe success must
    re-admit the class)."""
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.resilience import errors as rerrors, faults
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    typed_kinds = (rerrors.ResilienceError,)
    for trial in range(base, base + n_trials):
        rng = np.random.default_rng(trial)
        try:
            faults.reset()
            # fatal fires are CAPPED so the fault window provably
            # ends; transient budget stays strictly below the retry
            # budget (the soak_chaos discipline)
            rules = ["execute:fatal:p=0.25:max=3",
                     "serve_admit:transient:p=0.1:max=2"]
            cfg = MatrelConfig(
                serve_tenant_weights="a:3,b:1",
                serve_tenant_queue_max=4,
                serve_queue_max=10,
                serve_max_batch=int(rng.integers(1, 4)),
                brownout_enable=True,
                brownout_window=8, brownout_dwell=2,
                brownout_wait_high_ms=5.0, brownout_wait_low_ms=1.0,
                brownout_depth_high=6, brownout_depth_low=1,
                breaker_threshold=2, breaker_cooldown_ms=30.0,
                retry_max_attempts=4, retry_backoff_ms=1.0,
                fault_inject=";".join(rules),
                fault_inject_seed=trial,
                result_cache_max_bytes=(1 << 24 if trial % 2 else 0))
            sess = MatrelSession(mesh=mesh, config=cfg)
            n = int(rng.choice([16, 32]))
            an = rng.standard_normal((n, n)).astype(np.float32)
            bn = rng.standard_normal((n, n)).astype(np.float32)
            A, B = sess.from_numpy(an), sess.from_numpy(bn)
            pool = [(A.expr().multiply(B.expr())
                     .multiply_scalar(float(s + 1)),
                     an @ bn * (s + 1)) for s in range(3)]
            futs = []
            # seeded bursts: submit without waiting (open loop), gaps
            # from an exponential draw — admission pressure is the
            # point, so most trials overrun the tiny quotas
            for q in range(28):
                e, want = pool[q % len(pool)]
                tenant = "a" if rng.random() < 0.5 else "b"
                try:
                    futs.append(
                        (sess.submit(e, tenant=tenant,
                                     deadline_ms=5_000.0), want))
                except rerrors.AdmissionShed:
                    continue       # typed refusal IS the contract
                if rng.random() < 0.3:
                    __import__("time").sleep(
                        float(rng.exponential(0.004)))
            sess.serve_drain(timeout=120)
            for fut, want in futs:
                ex = fut.exception(timeout=60)
                if ex is None:
                    got = fut.result().to_numpy()
                    # brownout rung 1 legitimately runs default-SLA
                    # queries at the bf16 fast tier: the oracle bound
                    # is the FAST tier's documented max-norm error,
                    # not f32's (docs/PRECISION.md / OVERLOAD.md)
                    scale = max(1.0, float(np.max(np.abs(want))))
                    np.testing.assert_allclose(got, want, rtol=0,
                                               atol=2e-2 * scale)
                elif not isinstance(ex, typed_kinds):
                    raise AssertionError(
                        f"untyped failure escaped: "
                        f"{type(ex).__name__}: {ex}") from ex
            # the fault window is over (max= caps reached): the
            # breaker must close again — settle with single queries,
            # waiting out cooldowns on typed CircuitOpen refusals
            e, want = pool[0]
            for _ in range(12):
                try:
                    got = sess.run(e)
                    scale = max(1.0, float(np.max(np.abs(want))))
                    np.testing.assert_allclose(got.to_numpy(), want,
                                               rtol=0,
                                               atol=2e-2 * scale)
                    break
                except rerrors.CircuitOpen:
                    __import__("time").sleep(0.04)
                except rerrors.InjectedFault as ex:
                    if ex.transient:
                        raise AssertionError(
                            "transient escaped the retry loop") from ex
                    __import__("time").sleep(0.01)
            else:
                raise AssertionError(
                    "breaker never re-admitted the class after the "
                    "fault window")
            snap = sess._breakers.snapshot()
            assert not snap["open"], (
                f"breaker still open after settle: {snap}")
        except Exception as ex:  # noqa: BLE001 — soak collects all
            fails.append(("overload", trial, type(ex).__name__,
                          str(ex)[:200]))
    faults.reset()
    return fails


def soak_coeffs(n_trials: int, base: int, tol: float):
    """Cost-model closed-loop battery (parallel/coeffs.py,
    serve/replan.py; docs/COST_MODEL.md): seeded-miscalibration
    convergence. Per trial, the drift table is POISONED >=4x off — the
    shape class's cheapest-by-bytes strategy (the one the analytic
    byte model loves) claims coefficients far below reality while its
    TRUE cost is 4x the worst candidate — so the coefficient-ranked
    planner provably mispicks it on first contact. Replay traffic then
    flows a ReplanController wired to a live session: per round, the
    planner's current pick plus (round 1 only) a canary sweep of every
    candidate, each sample's execute_ms drawn from a deterministic
    per-strategy ground-truth model with seeded noise. The checks:

      * the poison takes (initial pick == the decoy),
      * a DRIFT rank flag fires and the controller re-calibrates,
        converging the pick to the TRUE winner within <=3 re-plan
        rounds (count-weighted blend: poisoned priors wash out),
      * ZERO wrong answers: a real query runs on the session every
        round — including the rounds where the coefficient epoch flips
        under it — and matches the numpy oracle,
      * ZERO oscillation: over a 3-round exploit-only tail the pick
        never leaves the winner and no further re-plan actions (the
        cooldown + dropped-window + reversal-dwell hysteresis),
      * the epoch bump is visible end-to-end (replan record old !=
        new epoch; the session's plan re-warm census counted it).
    """
    import json as _json
    import shutil
    import tempfile

    import numpy as np
    import jax
    from matrel_tpu import executor as executor_lib
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.obs import drift
    from matrel_tpu.parallel import coeffs as coeffs_lib, planner
    from matrel_tpu.serve import replan as replan_lib
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    gx, gy = mesh_lib.mesh_grid_shape(mesh)
    backend = jax.default_backend()
    fails = []
    for trial in range(n_trials):
        seed = base + trial
        rng = np.random.default_rng(seed)
        tmp = tempfile.mkdtemp(prefix="matrel_soak_coeffs_")
        table = os.path.join(tmp, "drift.json")
        try:
            n = int(rng.choice([96, 112, 128]))
            cls = drift.shape_class((n, n, n))
            gf = 2.0 * n ** 3 / 1e9
            cands = [s for s in ("bmm_right", "bmm_left", "cpmm",
                                 "rmm", "summa", "xla")
                     if not (s == "summa" and gx != gy)]
            est = {s: max(float(planner.comm_cost(s, n, n, n, 1.0,
                                                  1.0, gx, gy)),
                          1024.0)
                   for s in cands}
            # ground truth: a well-separated ms ladder shuffled over
            # the candidates (gaps >= 45%, far above the 3% sample
            # noise, so the calibrated ranking can never flap on a
            # near-tie); the DECOY is the byte model's favourite (min
            # est bytes, deterministic name tiebreak) with its true
            # cost forced to 4x the worst other — the drift scenario
            # in its purest form
            ladder = [0.4, 0.6, 0.9, 1.35, 2.0, 3.0][:len(cands)]
            rng.shuffle(ladder)
            ms_tab = dict(zip(cands, ladder))
            decoy = min(cands, key=lambda s: (est[s], s))
            ms_tab[decoy] = 4.0 * max(ms_tab[s] for s in cands
                                      if s != decoy)

            def ms_true(s):
                return ms_tab[s]

            def write_table(poisoned: bool) -> None:
                # rows shaped exactly as drift.calibrate derives them
                # from live samples (both ratios from the SAME total
                # ms), so a re-calibration from truthful traffic
                # reproduces the truthful rows and the blend is a
                # fixed point
                entries = {}
                for s in cands:
                    ms = ms_tab[s]
                    r = {"strategy": s, "class": cls,
                         "backend": backend, "count": 10,
                         "ms_median": round(ms, 5),
                         "ms_per_gflop": round(ms / gf, 5),
                         "ms_per_est_mib": round(
                             ms / (est[s] / 2 ** 20), 5)}
                    if poisoned and s == decoy:
                        r["ms_per_gflop"] = 0.01
                        r["ms_per_est_mib"] = 0.0001
                    entries[f"{s}|{cls}|{backend}"] = r
                with open(table, "w") as f:
                    _json.dump({"schema": 1, "entries": entries}, f)
                coeffs_lib.reset_coefficient_cache()

            cfg = MatrelConfig(obs_level="off",
                               drift_table_path=table,
                               coeff_planner_enable=True,
                               coeff_min_samples=2)
            cfg_ctl = cfg.replace(coeff_replan_enable=True,
                                  coeff_replan_interval=10 ** 6,
                                  coeff_replan_cooldown=1)
            A = BlockMatrix.random((n, n), mesh=mesh, seed=seed)
            B = BlockMatrix.random((n, n), mesh=mesh, seed=seed + 1)
            oracle = (A.to_numpy().astype(np.float64)
                      @ B.to_numpy().astype(np.float64))

            def pick():
                plan = executor_lib.compile_expr(
                    A.expr().multiply(B.expr()), mesh, cfg)
                decs = executor_lib.plan_matmul_decisions(plan)
                return decs[0].get("strategy"), \
                    decs[0].get("cost", "analytic")

            # the MEASURED WINNER is the system's own choice under a
            # truth-calibrated table — the pick the loop must converge
            # back to once the poison washes out
            write_table(poisoned=False)
            winner, wcost = pick()
            if wcost != "measured" or winner == decoy:
                fails.append(("coeffs", seed, "BadTruthPick",
                              f"{winner}/{wcost}, decoy {decoy}"))
                continue
            write_table(poisoned=True)

            sess = MatrelSession(mesh=mesh, config=cfg)
            ctl = replan_lib.ReplanController(cfg_ctl, session=sess)

            def feed(s, k=6):
                for _ in range(k):
                    noise = float(rng.uniform(0.97, 1.03))
                    ctl.observe({
                        "kind": "query", "backend": backend,
                        "cache": "miss",
                        "execute_ms": max(ms_true(s) * noise, 1e-4),
                        "matmuls": [{"strategy": s,
                                     "dims": [n, n, n],
                                     "flops": 2.0 * n ** 3,
                                     "est_ici_bytes": est[s]}]})

            first, first_cost = pick()
            if first_cost != "measured" or first != decoy:
                fails.append(("coeffs", seed, "PoisonDidNotTake",
                              f"first pick {first}/{first_cost}, "
                              f"decoy {decoy}"))
                continue
            # prime the session's plan cache under the POISONED epoch:
            # this is the live plan the re-plan round must find, match
            # and re-warm (and the answer must already be right)
            out = sess.run(A.expr().multiply(B.expr()))
            np.testing.assert_allclose(
                out.to_numpy().astype(np.float64), oracle,
                rtol=tol, atol=tol)
            converged_at = None
            tail_replans = 0
            rounds = 6
            for rnd in range(1, rounds + 1):
                cur, _ = pick()
                feed(cur)
                if rnd == 1:
                    # canary sweep: one exploration burst, the
                    # heterogeneous-traffic stand-in that gives
                    # rank_flags its cross-strategy evidence
                    for s in cands:
                        if s != cur:
                            feed(s)
                before = ctl.replans
                ctl.check()
                if converged_at is not None:
                    tail_replans += ctl.replans - before
                # zero wrong answers, epoch flips and all: a REAL
                # query through the session every round
                out = sess.run(A.expr().multiply(B.expr()))
                np.testing.assert_allclose(
                    out.to_numpy().astype(np.float64), oracle,
                    rtol=tol, atol=tol)
                cur, _ = pick()
                if converged_at is None and cur == winner:
                    converged_at = ctl.replans
                elif converged_at is not None and cur != winner:
                    fails.append(("coeffs", seed, "Oscillation",
                                  f"pick left winner {winner} -> "
                                  f"{cur} round {rnd}"))
                    break
            ctl.drain()
            if converged_at is None:
                fails.append(("coeffs", seed, "NoConvergence",
                              f"decoy {decoy} winner {winner} "
                              f"pick {pick()[0]} "
                              f"replans {ctl.replans}"))
                continue
            if converged_at > 3:
                fails.append(("coeffs", seed, "SlowConvergence",
                              f"{converged_at} re-plan rounds"))
            if tail_replans:
                fails.append(("coeffs", seed, "ReplanChurn",
                              f"{tail_replans} re-plan(s) after "
                              f"convergence"))
            if not ctl.events:
                fails.append(("coeffs", seed, "NoReplanRecord", ""))
            else:
                ev = ctl.events[0]
                if ev["old_epoch"] == ev["epoch"]:
                    fails.append(("coeffs", seed, "EpochDidNotBump",
                                  str(ev)))
                if ev.get("replanned") is None \
                        or ev.get("matched", 0) < 1:
                    fails.append(("coeffs", seed, "WarmMissedPlan",
                                  str(ev)))
        except Exception as ex:  # noqa: BLE001 — soak collects everything
            fails.append(("coeffs", seed, type(ex).__name__,
                          str(ex)[:200]))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        print(f"  coeffs {trial + 1}/{n_trials}, "
              f"{len(fails)} failures", flush=True)
    return fails


def soak_checkpoint(n_trials: int, base: int, tol: float):
    """Randomized checkpoint/restore: matrices with random specs, sparse
    tile stacks, loop state — restored values AND shardings must match;
    keep-k GC must hold."""
    import shutil
    import tempfile
    import numpy as np
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.core.sparse import BlockSparseMatrix
    from matrel_tpu.utils.checkpoint import CheckpointManager
    from jax.sharding import PartitionSpec as P

    mesh = mesh_lib.make_mesh()
    x, y = mesh.axis_names
    specs = [P(x, y), P((x, y), None), P(None, (x, y)), P(None, None)]
    fails = []
    for trial in range(base, base + n_trials):
        rng = np.random.default_rng(trial)
        d = tempfile.mkdtemp(prefix="matrel_soak_ckpt_")
        try:
            mgr = CheckpointManager(d, keep=2)
            n = int(rng.choice([8, 16, 24, 32]))
            mats = {}
            vals = {}
            for i in range(int(rng.integers(1, 4))):
                v = rng.standard_normal((n, n)).astype(np.float32)
                spec = specs[int(rng.integers(0, len(specs)))]
                mats[f"m{i}"] = BlockMatrix.from_numpy(v, mesh=mesh,
                                                       spec=spec)
                vals[f"m{i}"] = v
            sp_np = rng.standard_normal((n, n)).astype(np.float32)
            sp_np[rng.random((n, n)) < 0.6] = 0.0
            sp = BlockSparseMatrix.from_numpy(sp_np, block_size=8,
                                              mesh=mesh)
            state = {"iter": int(rng.integers(0, 100))}
            for step in range(int(rng.integers(1, 4))):
                mgr.save(step, matrices=mats, sparse={"s": sp},
                         state=state)
            got = mgr.restore(mesh)
            assert got is not None
            _, rmats, _, rstate = got
            assert rstate == state, (rstate, state)
            for name, v in vals.items():
                np.testing.assert_allclose(rmats[name].to_numpy(), v,
                                           rtol=tol, atol=tol)
                assert rmats[name].spec == mats[name].spec
            rsp = mgr.restore_sparse(mesh)["s"]
            np.testing.assert_allclose(rsp.to_numpy(), sp_np,
                                       rtol=tol, atol=tol)
            assert len(mgr._steps()) <= 2       # keep-k GC held
        except Exception as ex:  # noqa: BLE001
            fails.append(("ckpt", trial, type(ex).__name__,
                          str(ex)[:200]))
        finally:
            shutil.rmtree(d, ignore_errors=True)
    return fails


#: The restore half of soak_durable, run as a NEW PROCESS (the
#: kill-and-restore contract — an in-process "restore" would share
#: interpreter state with the session that saved). Args: state root,
#: matrix side, catalog names, integer-valued names, float tolerance.
_DURABLE_CHILD = '''\
import json, os, sys
# ALWAYS the CPU: under --tpu the parent holds the chip (one process per
# chip), and the restart proof is about the snapshot, not the device
os.environ["JAX_PLATFORMS"] = "cpu"
_f = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _f:
    os.environ["XLA_FLAGS"] = (
        _f + " --xla_force_host_platform_device_count=8").strip()
import numpy as np
root, n = sys.argv[1], int(sys.argv[2])
names = sys.argv[3].split(",")
int_names = set(filter(None, sys.argv[4].split(",")))
tol = float(sys.argv[5])
from matrel_tpu.config import MatrelConfig
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.session import MatrelSession
entry = n * n * 4
cfg = MatrelConfig(obs_level="off", spill_enable=True,
                   result_cache_max_bytes=int(1.5 * entry),
                   result_cache_max_entries=16,
                   spill_host_max_bytes=2 * entry,
                   spill_disk_hits=0, state_dir=root)
sess = MatrelSession(mesh=mesh_lib.make_mesh(), config=cfg)
out = sess.restore()
assert out.get("restored"), out
wrong = int_mismatch = 0
for name in names:
    m = sess.catalog[name]
    got = np.asarray(sess.run(m.expr().t().multiply(m.expr())).data)
    oracle = np.load(os.path.join(root, "oracle_%s.npy" % name))
    if name in int_names and not np.array_equal(got, oracle):
        int_mismatch += 1
    elif not np.allclose(got, oracle, rtol=tol, atol=tol):
        wrong += 1
info = sess.result_cache_info().get("spill") or {}
print(json.dumps({"wrong": wrong, "int_mismatch": int_mismatch,
                  "thawed": info.get("thawed_restored", 0)}))
'''


def soak_durable(n_trials: int, base: int, tol: float):
    """Kill-and-restore battery (docs/DURABILITY.md): random named
    working sets LARGER than the HBM budget serve traffic through the
    spill tiers, the session snapshots (``save_state``) MID-TRAFFIC
    (queries keep flowing after the save), and a NEW PROCESS (pinned
    to the CPU: under --tpu this parent holds the chip) restores the
    snapshot and repeats the whole query mix — zero wrong
    answers, integer-valued working sets bit-exact (``array_equal``,
    the precision plane's int discipline), and at least one answer
    must come from a thawed snapshot entry (a battery that silently
    recomputed everything proves nothing)."""
    import json as json_lib
    import shutil
    import subprocess
    import tempfile
    import numpy as np
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.session import MatrelSession

    mesh = mesh_lib.make_mesh()
    fails = []
    for trial in range(base, base + n_trials):
        rng = np.random.default_rng(trial)
        root = tempfile.mkdtemp(prefix="matrel_soak_durable_")
        try:
            n = int(rng.choice([32, 48, 64]))
            m_count = int(rng.integers(3, 6))
            entry = n * n * 4
            cfg = MatrelConfig(
                obs_level="off", spill_enable=True,
                result_cache_max_bytes=int(1.5 * entry),
                result_cache_max_entries=16,
                spill_host_max_bytes=2 * entry,
                spill_disk_hits=0, state_dir=root)
            sess = MatrelSession(mesh=mesh, config=cfg)
            names, int_names = [], set()
            for i in range(m_count):
                name = f"d{i}"
                if rng.random() < 0.4:
                    v = rng.integers(-4, 5, (n, n)).astype(np.float32)
                    int_names.add(name)
                else:
                    v = rng.standard_normal((n, n)).astype(np.float32)
                sess.register(name,
                              BlockMatrix.from_numpy(v, mesh=mesh))
                names.append(name)

            def gram(s, name):
                mm = s.catalog[name]
                return s.run(mm.expr().t().multiply(mm.expr()))

            oracle = {nm: np.asarray(gram(sess, nm).data)
                      for nm in names}
            # mid-traffic snapshot: repeats flow before AND after
            for nm in names[: max(m_count // 2, 1)]:
                gram(sess, nm)
            sess.save_state()
            for nm in names[m_count // 2:]:
                gram(sess, nm)
            for nm in names:
                np.save(os.path.join(root, f"oracle_{nm}.npy"),
                        oracle[nm])
            child = os.path.join(root, "child.py")
            with open(child, "w") as f:
                f.write(_DURABLE_CHILD)
            env = dict(os.environ)
            env["PYTHONPATH"] = (
                REPO + os.pathsep + env.get("PYTHONPATH", ""))
            out = subprocess.run(
                [sys.executable, child, root, str(n),
                 ",".join(names), ",".join(sorted(int_names)),
                 str(tol)],
                capture_output=True, text=True, timeout=600, env=env)
            assert out.returncode == 0, out.stderr[-400:]
            rep = json_lib.loads(
                out.stdout.strip().splitlines()[-1])
            assert rep["wrong"] == 0, rep
            assert rep["int_mismatch"] == 0, rep
            assert rep["thawed"] > 0, (
                "restore served nothing from the snapshot", rep)
        except Exception as ex:  # noqa: BLE001
            fails.append(("durable", trial, type(ex).__name__,
                          str(ex)[:200]))
        finally:
            shutil.rmtree(root, ignore_errors=True)
    return fails


def main():
    p = argparse.ArgumentParser()
    p.add_argument("battery",
                   choices=["fuzz", "deep", "spmv", "sharded", "ckpt",
                            "serve", "precision", "chaos",
                            "sparse_kernels", "fusion", "overload",
                            "stream", "fleet", "cse", "race",
                            "coeffs", "durable", "all"])
    p.add_argument("--seeds", type=int, default=100)
    p.add_argument("--base", type=int, default=10_000)
    p.add_argument("--tpu", action="store_true",
                   help="run on the real chip (looser tolerance)")
    args = p.parse_args()
    _setup(args.tpu)
    t_start = __import__("time").time()
    tol = 5e-3 if args.tpu else 3e-3
    fails = []
    if args.battery in ("fuzz", "all"):
        fails += soak_fuzz(args.seeds, args.base, tol)
    if args.battery in ("deep", "all"):
        # deeper trees accumulate more bf16 matmul error; widen slightly
        fails += soak_deep(max(args.seeds // 4, 5), args.base, 2 * tol)
    if args.battery in ("spmv", "all"):
        fails += soak_spmv(args.seeds, args.base,
                           1e-3 if args.tpu else 2e-4)
    if args.battery in ("ckpt", "all"):
        fails += soak_checkpoint(max(args.seeds // 5, 5), args.base,
                                 1e-6)
    if args.battery in ("serve", "all"):
        fails += soak_serve(max(args.seeds // 2, 5), args.base, tol)
    if args.battery in ("cse", "all"):
        fails += soak_cse(max(args.seeds // 5, 4), args.base, tol)
    if args.battery in ("chaos", "all"):
        fails += soak_chaos(max(args.seeds // 4, 5), args.base, tol)
    if args.battery in ("overload", "all"):
        fails += soak_overload(max(args.seeds // 5, 5), args.base, tol)
    if args.battery in ("stream", "all"):
        fails += soak_stream(max(args.seeds // 5, 4), args.base, tol)
    if args.battery in ("fleet", "all"):
        fails += soak_fleet(max(args.seeds // 5, 4), args.base, tol)
    if args.battery in ("coeffs", "all"):
        fails += soak_coeffs(max(args.seeds // 10, 8), args.base, tol)
    if args.battery in ("durable", "all"):
        fails += soak_durable(max(args.seeds // 20, 3), args.base, tol)
    if args.battery in ("race", "all"):
        fails += soak_race(max(args.seeds // 10, 3), args.base, tol)
    if args.battery in ("precision", "all"):
        fails += soak_precision(max(args.seeds // 2, 5), args.base, tol)
    if args.battery in ("sharded", "all"):
        fails += soak_sharded(max(args.seeds // 2, 5), args.base, tol)
    if args.battery in ("sparse_kernels", "all"):
        fails += soak_sparse_kernels(max(args.seeds // 5, 4),
                                     args.base, tol)
    if args.battery in ("fusion", "all"):
        fails += soak_fusion(max(args.seeds // 4, 6), args.base, tol)
    print(f"SOAK COMPLETE: {len(fails)} failures")
    for f in fails[:20]:
        print(" ", f)
    _log_tally(args, len(fails), fails[:20], t_start)
    sys.exit(min(len(fails), 125))


def _log_tally(args, n_fails, fail_heads, t_start):
    """Append a machine-checkable tally line to SOAKLOG.jsonl — the
    committed evidence trail for soak runs (round-2 VERDICT: tallies
    lived only as prose in docs). Every run, CPU or TPU, logs here."""
    import json
    import time
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = os.environ.get("JAX_PLATFORMS", "(default)")
    rec = {"ts": round(time.time(), 1),
           "when": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "event": "soak", "battery": args.battery,
           "seeds": args.seeds, "base": args.base,
           "tpu": bool(args.tpu),
           "backend": backend,
           "failures": n_fails,
           "fail_heads": [str(f) for f in fail_heads],
           "wall_s": round(time.time() - t_start, 1)}
    path = os.path.join(REPO, "SOAKLOG.jsonl")
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as e:
        print(f"# could not append {path}: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
