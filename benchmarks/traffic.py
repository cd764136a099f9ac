"""The one general traffic generator. A mix is data
(``benchmarks/workloads/<traffic>.json``): which of the configuration's
queries, with what weight, sent by one closed-loop client (the only
traffic this harness drives: a file that asks for more, by a key that
``run.py`` does not read, is refused). Every seed sends the same multiset
of queries — ``weight`` copies of each per block — in another order, so
the seed changes the order and never the work."""

from __future__ import annotations

import random

CYCLE_BLOCKS = 512


def sequence(mix, seed: int):
    """The cyclic list of query names one client sends."""
    block = [m["query"] for m in mix for _ in range(int(m.get("weight", 1)))]
    if not block:
        raise ValueError("empty mix")
    rng = random.Random(seed)
    out = []
    for _ in range(CYCLE_BLOCKS):
        rng.shuffle(block)
        out.extend(block)
    return out


def kept_offset(seed: int, every: int) -> int:
    """Answers are kept for the check at indices i with i % every ==
    offset; the offset is drawn from the seed."""
    return random.Random(seed ^ 0x5EED).randrange(max(int(every), 1))
