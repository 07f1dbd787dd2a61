"""Scheduler: 90th percentile of the time from admission into a slot to
the dispatch of the request's first prefill chunk (``Request.admit_time``
to ``first_chunk_time``), for the window's requests: the head-of-line
wait behind the chunks of prompts admitted before it."""

from bench import stamps, stats


def read(view):
    xs = stamps.waits_ms(view, "admit_time", "first_chunk_time")
    return stats.percentile(xs, 90) if xs else None
