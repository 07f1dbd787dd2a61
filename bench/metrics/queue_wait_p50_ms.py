"""Scheduler: median time from due to admission into a slot, for the
window's requests (admission is read from ``Request.state`` after each
step and stamped with the step's start, when ``Scheduler.admit`` ran)."""

from bench import stats


def read(view):
    run = view.run
    if run.kind == "backlog":
        return None
    xs = [1e3 * (t.admit - t.due) for t in run.window_tracks()
          if t.admit is not None]
    return stats.percentile(xs, 50) if xs else None
