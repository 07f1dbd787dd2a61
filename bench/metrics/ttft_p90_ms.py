"""90th percentile of time to first token over every request that arrived
in the window: from when it was due to its first token on the host."""

from bench import stats, views


def read(view):
    xs = views.ttft_ms(view)
    return stats.percentile(xs, 90) if xs else None
