"""Output tokens on the host in the window over the window's length;
partly finished requests count."""

from bench import stats


def read(view):
    run = view.run
    n = sum(1 for t in run.tracks for s in t.stamps if run.t0 <= s < run.t1)
    return stats.rate(n, run.t1 - run.t0)
