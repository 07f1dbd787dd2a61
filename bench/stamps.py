"""What the engine stamps on each request and marks in each step, as the
readers of its scheduler and host-step metrics take it.

Requests: ``submit_time``, ``admit_time`` and ``first_chunk_time``,
``time.perf_counter`` seconds that ``DecodeEngine`` sets on its
``Request`` (submission, admission into a slot, first prefill chunk).
Steps: the program's ``engine.fetch`` spans, each a host read that waits
on the device, inside the harness's ``engine.step`` marks of a traced
stretch. A program that has neither gives no samples, so a metric read
from them is left out of its result line rather than read as zero.
"""

from __future__ import annotations

from bench import trace as tracemod

STEP = "engine.step"
FETCH = "engine.fetch"


def waits_ms(view, start: str, end: str) -> list[float]:
    """``end - start`` of each window request that carries both stamps,
    in ms."""
    out = []
    for t in view.run.window_tracks():
        a = getattr(t.req, start, None)
        b = getattr(t.req, end, None)
        if a is not None and b is not None:
            out.append(1e3 * (b - a))
    return out


def step_fetches(view) -> list[tuple[list, list]]:
    """(``engine.step`` mark, the ``engine.fetch`` spans that start in it)
    for each step of the traced stretch; none where the trace holds no
    device plane or no fetch span."""
    events = view.events
    if not events or not tracemod.device_planes(events):
        return []
    fetches = [e for e in events if e[2] == FETCH]
    if not fetches:
        return []
    lo, hi = tracemod.window(events)
    marks = sorted((e for e in events if e[2] == STEP and lo <= e[3] < hi),
                   key=lambda e: e[3])
    return [(m, [f for f in fetches if m[3] <= f[3] < m[3] + m[4]])
            for m in marks]
