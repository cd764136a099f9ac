"""Weighted-mesh strategy-flip check (VERDICT Next #4 "done when").

Builds a 2-level VIRTUAL mesh — 8 CPU host devices as a (2, 4) grid
with the y axis priced 8× (the DCN axis of a two-slice v5e fabric) —
and proves, through the real planner entry points, that:

  1. the β-only ranking picks the slow-axis collective (rmm's A
     all-gather rides y) and the topology-weighted ranking provably
     flips to the ICI-friendly bmm_right;
  2. MV106 flags a hand-stamped slow-axis plan under the weighted
     config, and stays quiet on the planner's own output;
  3. a weighted config executes a real multiply to oracle numerics
     (weights re-route choices, never change results).

Emits one parseable JSON line (asserted by tests/test_drills.py).
CPU-only by construction — this is a planning check, so it forces the
CPU backend.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

#: The flip shape: on the (2, 4) grid with 3a/8 < b_bytes < 3a/4, the
#: flat model's argmin (rmm) carries ~6× more y-axis bytes than the
#: broadcast alternative, so weighting y flips the pick (docs/TOPOLOGY.md
#: derives the band).
N, K, M = 8192, 2048, 4096
AXIS_WEIGHTS = (1.0, 8.0)


def main() -> int:
    import dataclasses
    from matrel_tpu import analysis
    from matrel_tpu.config import MatrelConfig
    from matrel_tpu.core import mesh as mesh_lib
    from matrel_tpu.core.blockmatrix import BlockMatrix
    from matrel_tpu.executor import execute
    from matrel_tpu.ir.expr import leaf, matmul
    from matrel_tpu.parallel import planner
    from jax.sharding import PartitionSpec as P

    mesh = mesh_lib.make_mesh((2, 4))
    base = BlockMatrix.from_numpy(np.zeros((8, 8), np.float32),
                                  mesh=mesh)

    def fab(n, m, spec=None):
        src = base if spec is None else BlockMatrix.from_numpy(
            np.zeros((8, 8), np.float32), mesh=mesh, spec=spec)
        return leaf(dataclasses.replace(src, shape=(n, m)))

    cfg_flat = MatrelConfig()
    cfg_w = MatrelConfig(axis_cost_weights=AXIS_WEIGHTS)
    node = matmul(fab(N, K), fab(K, M))
    flat_pick, _ = planner.choose_strategy_ex(node, mesh, cfg_flat)
    w_pick, _ = planner.choose_strategy_ex(node, mesh, cfg_w)
    flat_axes = planner.comm_cost_axes(flat_pick, N, K, M, 1.0, 1.0,
                                       2, 4, weights=AXIS_WEIGHTS)
    flipped = (flat_pick == "rmm" and w_pick == "bmm_right"
               and flat_axes[1] > flat_axes[0])

    # MV106: hand-stamp the slow-axis pick (replicated B makes the
    # broadcast free — the grossest version of the smell) on a
    # NON-root-exposed node; the planner's own annotation stays clean
    stamped = matmul(
        matmul(fab(N, K), fab(K, M, spec=P(None, None)))
        .with_attrs(strategy="rmm", strategy_source="override"),
        fab(M, 64))
    diags = analysis.verify_plan(
        planner.annotate_strategies(stamped, mesh, cfg_w), mesh, cfg_w)
    mv106 = [d for d in diags if d.code == "MV106"]
    clean = analysis.verify_plan(
        planner.annotate_strategies(matmul(fab(N, K), fab(K, M)), mesh,
                                    cfg_w), mesh, cfg_w)

    # weighted config executes to oracle numerics (tiny real multiply)
    rng = np.random.default_rng(0)
    xa = rng.standard_normal((64, 32)).astype(np.float32)
    xb = rng.standard_normal((32, 48)).astype(np.float32)
    got = execute(
        BlockMatrix.from_numpy(xa, mesh=mesh).expr().multiply(
            BlockMatrix.from_numpy(xb, mesh=mesh).expr()),
        mesh, cfg_w).to_numpy()
    numerics_ok = bool(np.allclose(got, xa @ xb, rtol=1e-4, atol=1e-4))

    ok = bool(flipped and mv106 and not clean and numerics_ok)
    print(json.dumps({
        "metric": "topology_strategy_flip",
        "grid": [2, 4],
        "axis_weights": list(AXIS_WEIGHTS),
        "dims": [N, K, M],
        "unweighted": flat_pick,
        "weighted": w_pick,
        "slow_axis_bytes": flat_axes[1],
        "fast_axis_bytes": flat_axes[0],
        "mv106_flagged": bool(mv106),
        "clean_plan_quiet": not clean,
        "numerics_ok": numerics_ok,
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
