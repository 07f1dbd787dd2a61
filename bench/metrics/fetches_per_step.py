"""Engine host step: mean number of ``engine.fetch`` spans per traced
step, each a host read that waits on the device (one per read site: the
decode step's packed tokens and statistics are one)."""

from bench import stamps


def read(view):
    steps = stamps.step_fetches(view)
    if not steps:
        return None
    return sum(len(f) for _, f in steps) / len(steps)
