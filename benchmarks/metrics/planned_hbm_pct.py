"""Optimizer, planner, compile: what the planner reckoned the plan's
peak on one device to be (``hbm_plan_bytes`` on the window's
``matrel.dispatch`` spans, the largest of them) over what the device
says it has (``memory_stats()["bytes_limit"]``). PERF.md sets it beside
the measured ``memory_peak_bytes``. A program whose spans carry no such
attribute (a parent commit), or a device that reports no limit (the CPU),
gives None."""

from benchmarks import program_spans


def read(run, records=None, bytes_limit=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    planned = [r["attrs"].get("hbm_plan_bytes") for r in found[0]
               if r["name"] == "matrel.dispatch"]
    planned = [p for p in planned if p]
    if not planned:
        run.say("planned_hbm_pct: no matrel.dispatch span of the window "
                "carries hbm_plan_bytes")
        return None
    if bytes_limit is None:
        import jax
        bytes_limit = (jax.devices()[0].memory_stats() or {}) \
            .get("bytes_limit")
    if not bytes_limit:
        run.say("planned_hbm_pct: the device reports no bytes_limit")
        return None
    run.say(f"planned hbm_plan_bytes={max(planned)} "
            f"bytes_limit={bytes_limit}")
    return 100.0 * max(planned) / bytes_limit
