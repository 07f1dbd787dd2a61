"""Load generator: 95th percentile of how late each window request was
submitted against its due time (the driver submits between engine
steps, so a long step shows here)."""

from bench import stats


def read(view):
    run = view.run
    if run.kind == "backlog":
        return None
    xs = [1e3 * (t.submit - t.due) for t in run.window_tracks()]
    return stats.percentile(xs, 95) if xs else None
