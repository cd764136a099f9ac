"""The dense chain's counts against hand numbers, and the three readers
of cell ``chain_65k_2x2`` on a reduced trace written by hand with four
chips' planes (``synthetic_ring.py``'s way: every answer known before the
reader runs)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from benchmarks import run as harness  # noqa: E402
import synthetic_ring  # noqa: E402

PEAKS = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
N = 65536


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def reduced(device_s=(2.0, 2.2), chips=4):
    """Two queries of 2.0 and 2.2 s of device time (the mean over four
    planes, as trace_reduce gives it); the ten longest operations, three
    of them collectives, as seconds a chip over the window."""
    return {
        "window_s": 4.5, "busy_s": sum(device_s), "chips_traced": chips,
        "n_device_ops": 400,
        "queries": [{"template": "chain_abc", "span_s": s + 0.01,
                     "device_s": s} for s in device_s],
        "device_ops": [
            ["%convolution.10 f32[32768,8192]", 1.9],
            ["%convolution.13 f32[32768,4096]", 1.9],
            ["%all-gather.32 bf16[32768,65536]", 0.12],
            ["%custom-call.3 bf16[65536,4096]", 0.05],
            ["%all-gather-start.4 (bf16[32768,4096]", 0.02],
            ["%convert_element_type.30 bf16[32768,8192]", 0.04],
            ["%collective-permute-done.1 bf16[8192,8192]", 0.006],
            ["%fusion.14 bf16[32768,32768]", 0.03],
            ["%copy.3 bf16[32768,32768]", 0.01],
            ["%slice.1 bf16[16,65536]", 0.001]],
        "idle_gaps": []}


def run_of(red, said=None):
    return types.SimpleNamespace(
        reduced=red, peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        shapes={"chain_abc": {"n": N, "itemsize": 2, "precision": "default"}},
        say=(said.append if said is not None else lambda line: None))


def test_dense_chain_counts_at_the_cell_s_size():
    c = harness.load_module(os.path.join(BENCH, "counts", "dense_chain.py")) \
        .counts(n=N, itemsize=2, precision="default")
    assert c["flops"] == 4 * N ** 3 == 1_125_899_906_842_624
    # A, B, C read, T and R written: five tables of 8,589,934,592 B
    assert c["bytes"] == 5 * 8_589_934_592
    # on four chips: 1.43 s of MXU, 13 ms of HBM
    assert c["flops"] / (4 * PEAKS["bf16_flops_per_s"]) \
        == pytest.approx(1.4288, rel=1e-3)
    assert c["bytes"] / (4 * PEAKS["hbm_bytes_per_s"]) \
        == pytest.approx(0.01311, rel=1e-3)


def test_chain_matmul_roofline_reckons_for_four_chips():
    said = []
    v = reader("chain_matmul_roofline").read(run_of(reduced(), said))
    # least 1.4288 s over a mean of 2.1 s a query
    assert v == pytest.approx(100 * 1.4288 / 2.1, rel=1e-3)
    assert said and "chips=4" in said[0] and "bound=mxu" in said[0]
    # one plane traced: the same operations on one chip take four times
    one = reader("chain_matmul_roofline").read(run_of(reduced(chips=1)))
    assert one == pytest.approx(4 * v)
    # no clamp: a time too short to be true reads over 100
    assert reader("chain_matmul_roofline").read(
        run_of(reduced(device_s=(1.0, 1.0)))) > 100


def test_collective_wait_ms_sums_the_collectives_it_sees():
    said = []
    v = reader("collective_wait_ms").read(run_of(reduced(), said))
    # all-gather 0.12 + all-gather-start 0.02 + collective-permute-done
    # 0.006 s over two queries; the AsyncCollectiveDone custom call is
    # not named as a collective and is not counted
    assert v == pytest.approx((0.12 + 0.02 + 0.006) / 2 * 1e3)
    assert "10 longest" in said[0] and "%all-gather.32" in said[0]
    quiet = reduced()
    quiet["device_ops"] = quiet["device_ops"][:2]
    assert reader("collective_wait_ms").read(run_of(quiet)) == 0.0


def test_planned_hbm_pct_reads_the_dispatch_spans():
    ring = synthetic_ring.sql_ring()
    for r in ring:
        if r["name"] == "matrel.dispatch":
            r["attrs"].update(mesh="2x2", hbm_plan_bytes=15 << 30)
    said = []
    run = synthetic_ring.run_of(2, said)
    v = reader("planned_hbm_pct").read(run, records=ring,
                                      bytes_limit=16_909_334_528)
    assert v == pytest.approx(100 * (15 << 30) / 16_909_334_528)
    # a parent commit's spans carry no such attribute: nothing, no raise
    assert reader("planned_hbm_pct").read(
        synthetic_ring.run_of(2, said), records=synthetic_ring.sql_ring(),
        bytes_limit=16_909_334_528) is None
    assert "no matrel.dispatch span" in said[-1]


@pytest.mark.parametrize("name", ["chain_matmul_roofline",
                                  "collective_wait_ms", "planned_hbm_pct"])
def test_readers_without_a_trace_give_nothing(name):
    assert reader(name).read(types.SimpleNamespace(
        reduced=None, peaks=PEAKS, shapes={}, say=lambda line: None)) is None
