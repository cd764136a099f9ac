"""What the configurations' plain references share: the error measure,
the rounding that the lower-precision control applies, and the seed
handling. Nothing here imports the program."""

from __future__ import annotations

import numpy as np


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float64 on the host; a wrong
    shape or a non-finite entry is infinitely wrong."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def bf16(x) -> np.ndarray:
    """x rounded to bfloat16 (round to nearest even), back in float64: what
    one MXU pass sees of a float32 operand."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def seed_words(seed: int):
    """--seed may exceed 31 bits: split it into two words that any 32-bit
    generator takes."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return seed & 0x7FFFFFFF, seed >> 31


def device_key(seed: int):
    import jax
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)
