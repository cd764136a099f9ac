"""The readers of cell ``pnmf_netflix_r128_1c`` on a reduced trace and a
ring written by hand (every answer known before the reader runs), its
count against a hand count, and the deployment's probe, run by hand as
``test_linreg_whole.py`` is:

    python -m pytest benchmarks/tests/test_pnmf.py -q -p no:cacheprovider

``test_rehearsal.py`` takes the cell by name like every other (end to
end traced and untraced, the timed path broken underneath, the bfloat16
control); tier-1 holds the same in ``tests/test_bench_pnmf.py``."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

PEAKS = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
SHAPES = {"users": 480_189, "movies": 17_770, "entries": 100_480_507,
          "rank": 128, "iterations": 3, "plans": {}}
MS = 1_000_000


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def rec(name, start_ms, dur_ms, span_id, parent_id, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "end_ns": int((start_ms + dur_ms) * MS), "span_id": span_id,
            "parent_id": parent_id, "qid": span_id, "tid": 1, "attrs": attrs}


def ring(fits=1, sampled=True):
    """``fits`` fits of six updates of 90 ms: a ``matrel.compute`` root
    over a dispatch of 8 GB whose sampled product has 92 of 100 entries
    on the slab."""
    out, sid = [], 0
    for u in range(6 * fits):
        t0 = 100.0 * u
        out.append(rec("matrel.compute", t0, 90, sid + 1, None))
        out.append(rec("matrel.dispatch", t0 + 1, 1, sid + 2, sid + 1,
                       hbm_plan_bytes=8_000_000_000))
        if sampled:
            out.append(rec("matrel.sampled.plan", t0 + 1.1, 0.01, sid + 3,
                           sid + 2, hit=True, entries=100, dense_entries=92,
                           orientation=("transposed", "forward")[u % 2]))
        sid += 3
    return out


def run_of(fits=1, device_s=0.6, said=None):
    return types.SimpleNamespace(
        reduced={"window_s": 0.6 * fits, "busy_s": device_s * fits,
                 "chips_traced": 1, "n_device_ops": 900,
                 "queries": [{"template": "pnmf_fit", "span_s": 0.6,
                              "device_s": device_s}] * fits,
                 "device_ops": [], "idle_gaps": []},
        peaks=PEAKS, here=BENCH, load_module=harness.load_module,
        shapes={"pnmf_fit": SHAPES},
        say=(said.append if said is not None else lambda line: None))


def test_the_roofline_is_bound_by_the_bytes():
    said = []
    got = reader("pnmf_roofline").read(run_of(said=said))
    assert got == pytest.approx(100.0 * (12_588_651_672 / 819e9) / 0.6)
    assert "bound=hbm" in said[0] and "mxu_passes=6" in said[0]


def test_the_span_readers_on_the_ring():
    assert reader("pnmf_mxu_entries_pct").read(run_of(2), ring(2)) \
        == pytest.approx(92.0)
    # a program without the sampled product (a parent commit): nothing
    # to read, and nothing raised
    assert reader("pnmf_mxu_entries_pct").read(
        run_of(), ring(sampled=False)) is None
    assert reader("pnmf_planned_hbm_pct").read(
        run_of(), ring(), bytes_limit=16_000_000_000) == pytest.approx(50.0)
    assert reader("pnmf_compiles_in_window").read(run_of(), ring()) == 0


def test_the_count_is_four_k_operations_an_entry_an_update():
    counts = harness.load_module(os.path.join(BENCH, "counts", "pnmf.py")) \
        .counts
    got = counts(**SHAPES)
    assert got["flops"] == 6 * 4 * 100_480_507 * 128
    assert got["bytes"] == 12_588_651_672 and got["precision"] == "highest"
