"""The jax API surface the codebase leans on, in one place: every
shard_map call site, varying-axes cast and Pallas TPU compiler-params
construction imports from here, so an API move lands in one file."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma=None, **kw):
    """``jax.shard_map`` with ``check_vma`` (the per-output
    varying-manual-axes check) passed only when the caller set it."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pvary(x, axes):
    """Mark a replicated value as varying over ``axes`` inside a
    shard_map (``jax.lax.pcast``; ``pvary`` is deprecated)."""
    return jax.lax.pcast(x, axes, to="varying")


def tpu_compiler_params(**kw):
    """Pallas TPU ``CompilerParams``."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kw)
