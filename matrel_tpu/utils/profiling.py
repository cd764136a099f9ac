"""Named scopes per physical operator (SURVEY.md §5 "Tracing /
profiling"): the executor wraps every node lowering in ``annotate``
(structurally enforced by tests/test_obs.py), so the operator's label
rides the HLO's ``op_name`` and, in a profiler trace, the ``tf_op``
stat of each device operation's event metadata
(``jit(matrel_plan_agg)/matrel.agg/reduce_sum``) — what TensorBoard /
xprof show beside an op. Timing lives in ``matrel_tpu/obs/``.
"""

from __future__ import annotations

import jax


def annotate(name: str):
    """Named scope that shows up in profiler timelines per operator."""
    return jax.named_scope(name)
