"""Device: share of the traced stretch in which no operation ran."""

from bench import trace


def read(view):
    if not view.events or not trace.device_planes(view.events):
        return None
    lo, hi = trace.window(view.events)
    return 100.0 * (1.0 - trace.busy_ns(view.events) / (hi - lo))
