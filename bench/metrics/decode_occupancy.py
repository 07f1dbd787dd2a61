"""Scheduler: mean number of decoding slots per decode step in the
window, as a share of ``max_slots``."""

from bench import views


def read(view):
    steps = [s for s in views.window_steps(view) if s.decode_launch]
    if not steps:
        return None
    mean = sum(len(s.decode_lens) for s in steps) / len(steps)
    return 100.0 * mean / view.engine["max_slots"]
