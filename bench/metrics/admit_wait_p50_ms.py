"""Scheduler: median time from ``DecodeEngine.submit`` to admission into
a slot, from the engine's own stamps (``Request.submit_time`` to
``admit_time``), for the window's requests: the scheduler's queue,
without the load generator's lag."""

from bench import stamps, stats


def read(view):
    if view.run.kind == "backlog":
        return None
    xs = stamps.waits_ms(view, "submit_time", "admit_time")
    return stats.percentile(xs, 50) if xs else None
