"""95th percentile of every gap between consecutive tokens of every
request in the window, on the host clock."""

from bench import stats, views


def read(view):
    xs = views.itl_ms(view)
    return stats.percentile(xs, 95) if xs else None
