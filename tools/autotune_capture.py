"""Re-capture the on-chip autotune table under the round-4 measurement
rules (median-of-3 marginals, bounded in-flight chains, tie → null).

Overwrites autotune_v5e_1chip.json for the shapes the round-3 capture
covered. VERDICT r3 #4: the round-3 single-marginal capture persisted
1e-9 noise sentinels as winners; this tool is the re-capture it asked
for, run from tpu_batch.sh on the chip.
"""
import json
import os
import sys

# run as a script from anywhere (the round-6 dry fire-drill caught this
# staged tool crashing on import — tools/ is the script dir, not the
# repo root, so the package was never importable)
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from matrel_tpu.config import MatrelConfig, set_default_config
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.parallel import autotune

# MATREL_AUTOTUNE_{SIDES,DTYPES,SPMV} scale the capture down for the
# dry-batch fire-drill (tools/tpu_batch.sh --dry), which also points
# the positional table-path arg away from the real on-chip table
SIDES = tuple(int(s) for s in os.environ.get(
    "MATREL_AUTOTUNE_SIDES", "1024,2048,4096").split(","))
DTYPES = tuple(os.environ.get(
    "MATREL_AUTOTUNE_DTYPES", "float32,bfloat16").split(","))


def main(path: str = "autotune_v5e_1chip.json") -> None:
    cfg = MatrelConfig(autotune=True, autotune_table_path=path)
    set_default_config(cfg)
    mesh = mesh_lib.make_mesh()
    for side in SIDES:
        for dtype in DTYPES:
            best, times = autotune.autotune_matmul(
                side, side, side, mesh=mesh, dtype=dtype, config=cfg)
            print(json.dumps({"side": side, "dtype": dtype, "best": best,
                              "times": {k: round(v, 6)
                                        for k, v in times.items()}}))
            sys.stdout.flush()
    # SpMV executor choice (VERDICT r3 #8) at a scale whose expanded
    # tables still fit the measurement budget (~235 MB; the row-5 graph
    # itself is compact-only by the 2 GB gate)
    import numpy as np
    from matrel_tpu.core.coo import COOMatrix
    n, m = (int(v) for v in os.environ.get(
        "MATREL_AUTOTUNE_SPMV", "100000,1000000").split(","))
    rng = np.random.default_rng(0)
    A = COOMatrix.from_edges(rng.integers(0, n, m, dtype=np.int32),
                             rng.integers(0, n, m, dtype=np.int32),
                             shape=(n, n))
    plan = A._get_plan()
    if plan is not None:
        autotune._SPMV_CACHE.clear()
        best = autotune.lookup_or_measure_spmv(plan, mesh, cfg)
        gx, gy = mesh_lib.mesh_grid_shape(mesh)
        key = autotune._spmv_key(plan, gx, gy)
        entry = autotune.load_table(path).get(key, {})
        print(json.dumps({"spmv_key": key, "best": best,
                          "times": {k: round(v, 6) for k, v in
                                    entry.get("times", {}).items()}}))
        sys.stdout.flush()


if __name__ == "__main__":
    main(*sys.argv[1:])
