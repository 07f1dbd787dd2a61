"""Model step: device time per prefill-chunk launch, from the trace."""

from bench import trace, views


def read(view):
    evs = views.device(view, "XLA Modules", views.PREFILL_MODULE)
    return trace.total_ns(evs) / len(evs) / 1e6 if evs else None
