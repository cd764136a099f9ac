"""Optimizer, planner, compile: what the planner reckoned the peak of a
tick on the chip to be — the largest ``hbm_plan_bytes`` of the window's
``matrel.delta.update`` spans (planner.rows_delta_plan: ONE table, the
batch, the rows that leave, the views at two words and a correction)
and of its ``matrel.dispatch`` spans — over the device's
``bytes_limit``. PERF.md sets it beside the measured
``memory_peak_bytes``. A second table would read over 120."""

from benchmarks.metrics import window_spans


def read(run, records=None, bytes_limit=None):
    found = window_spans.ticks(run, records)
    if found is None:
        return None
    planned = [r["attrs"].get("hbm_plan_bytes") for r in found[0]
               if r["name"] in ("matrel.delta.update", "matrel.dispatch")]
    planned = [p for p in planned if p]
    if not any(r["name"] == "matrel.delta.update" for r in found[0]) \
            or not planned:
        run.say("window_planned_hbm_pct: no matrel.delta.update span of "
                "the window carries hbm_plan_bytes")
        return None
    if bytes_limit is None:
        import jax
        bytes_limit = (jax.devices()[0].memory_stats() or {}) \
            .get("bytes_limit")
    if not bytes_limit:
        run.say("window_planned_hbm_pct: the device reports no bytes_limit")
        return None
    run.say(f"planned hbm_plan_bytes={max(planned)} "
            f"bytes_limit={bytes_limit}")
    return 100.0 * max(planned) / bytes_limit
