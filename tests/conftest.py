"""Test harness: simulate an 8-device mesh on CPU.

The reference tests on Spark's ``local[*]`` — an in-process cluster that
exercises the real shuffle/partitioner code paths in one JVM (SURVEY.md §4).
The JAX analogue: 8 virtual CPU devices via
``--xla_force_host_platform_device_count``, so every sharding, shard_map and
collective in the framework runs for real, just without ICI.

Must run before jax is imported anywhere — hence module level, in conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The config update holds even where something imported jax (and read the
# environment) before this conftest ran: the tests never touch an
# accelerator, whatever machine they run on.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from matrel_tpu.core import mesh as mesh_lib
    return mesh_lib.make_mesh((2, 4))


@pytest.fixture(scope="session")
def mesh4x2():
    from matrel_tpu.core import mesh as mesh_lib
    return mesh_lib.make_mesh((4, 2))


@pytest.fixture(scope="session")
def mesh_square():
    """2x2 square mesh (SUMMA/Cannon needs gx == gy)."""
    import jax
    from matrel_tpu.core import mesh as mesh_lib
    return mesh_lib.make_mesh((2, 2), devices=jax.devices()[:4])


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture()
def ladder_cells(rng):
    """(rows, cols): the distinct cells of a 2,048 x 2,048 matrix whose
    compact plans in chunks of 2,048 slots over blocks of 512 lines
    hold, in BOTH orientations, chunks of every height the k-wide
    scatter's ladder has (``ops/spmv.py`` ``chunk_windows``): block 1's
    lines hold 7 entries each (300 lines: a chunk in line order spans
    293 of them, the whole block), then 11 (180 lines: a chunk spans
    ~190, the 256-row rung), then 400 (32 lines: five a chunk, the
    128-row rung), their other coordinates distinct in block 3; rows
    and columns alike, in a drawn order."""
    counts = np.r_[np.full(300, 7), np.full(180, 11), np.full(32, 400)]
    line = 512 + np.repeat(np.arange(512), counts)

    def across():
        return 1536 + np.concatenate(
            [rng.choice(512, n, replace=False) for n in counts])

    rows = np.concatenate([line, across()])
    cols = np.concatenate([across(), line])
    order = rng.permutation(rows.size)
    return rows[order], cols[order]


@pytest.fixture(scope="session")
def run_at_root():
    """``run(args, devices=8)``: ``python *args`` from the repository's
    root in a process of its own on ``devices`` virtual CPU devices
    (platform env hermetic), exit code 0 asserted; its stdout."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(args, devices=8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
        # prepend the repo to the inherited path
        prev = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        env["PYTHONPATH"] = os.pathsep.join([repo, *prev])
        r = subprocess.run([sys.executable, *args], capture_output=True,
                           text=True, timeout=300, env=env, cwd=repo)
        assert r.returncode == 0, (args, r.stdout[-800:], r.stderr[-800:])
        return r.stdout.strip()

    return run


@pytest.fixture(autouse=True)
def _fresh_session():
    from matrel_tpu import session
    session.reset_session()
    yield
    session.reset_session()


@pytest.fixture(autouse=True)
def _fresh_profile_ring():
    """The profile tier's ring is the process's, and a traced rehearsal
    of a benchmark cell finds its window in it by the window's length
    (benchmarks/program_spans.py: the FIRST run of query roots that
    fits): where a worker ran another cell's traced rehearsal before, it
    found that one's and dropped its per-layer metrics (PR 57: one of
    ``tests/test_bench_*.py`` failed so in each of two whole runs, and
    the parent's tree fails alike when two of them share a process).
    Every test starts with the ring empty."""
    from matrel_tpu.obs import trace
    trace._PROFILE_RING._buf.clear()
    yield


@pytest.fixture(autouse=True)
def _autotune_table_tmp(tmp_path, monkeypatch):
    """Keep the persisted autotune table out of the repo root and out of
    cross-test state: each test gets a fresh table path + empty cache."""
    from matrel_tpu.parallel import autotune
    monkeypatch.setattr(autotune, "_DEFAULT_TABLE",
                        str(tmp_path / "autotune.json"))
    autotune._CACHE.clear()
    yield
