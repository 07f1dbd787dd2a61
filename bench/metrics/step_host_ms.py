"""Engine host step: mean over the traced steps (the harness's
``engine.step`` marks) of the step's length less the union of the
program's ``engine.fetch`` spans in it: the host time of a step in which
it neither waits on the device nor reads from it."""

from bench import stamps, stats, trace


def read(view):
    steps = stamps.step_fetches(view)
    if not steps:
        return None
    host_ns = [m[4] - stats.union_length(trace.intervals(f), m[3],
                                         m[3] + m[4])
               for m, f in steps]
    return sum(host_ns) / len(host_ns) / 1e6
