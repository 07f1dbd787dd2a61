"""Model step: device time per decode launch (the jitted decode step with
its logit statistics), from the trace."""

from bench import trace, views


def read(view):
    evs = views.device(view, "XLA Modules", views.DECODE_MODULE)
    return trace.total_ns(evs) / len(evs) / 1e6 if evs else None
