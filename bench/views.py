"""What the metric readers share: the window's requests, gaps and steps,
and the traced stretch's device events."""

from __future__ import annotations

from bench import trace as tracemod


def ttft_ms(view) -> list[float]:
    """Due time to first token on the host, for every request that arrived
    in the window and got one."""
    return [1e3 * (t.stamps[0] - t.due) for t in view.run.window_tracks()
            if t.stamps]


def itl_ms(view) -> list[float]:
    """Every gap between consecutive tokens of the window's requests: all
    of an open-loop request's gaps, and a backlog's gaps that end inside
    the window."""
    run = view.run
    out = []
    for t in run.window_tracks():
        s = t.stamps
        for a, b in zip(s, s[1:]):
            if run.kind != "backlog" or run.t0 <= b < run.t1:
                out.append(1e3 * (b - a))
    return out


def window_steps(view) -> list:
    run = view.run
    return [s for s in run.steps if run.t0 <= s.t0 < run.t1]


def traced_steps(view) -> list:
    """Engine steps wholly inside the profiled stretch."""
    lo, hi = view.run.trace_span
    return [s for s in view.run.steps if lo <= s.t0 and s.t1 <= hi]


def device(view, line: str, pattern: str) -> list:
    """Device events of ``line`` whose name matches ``pattern``, inside
    the traced stretch."""
    if not view.events:
        return []
    lo, hi = tracemod.window(view.events)
    evs = tracemod.device_events(view.events, line)
    return [e for e in tracemod.matching(evs, pattern) if lo <= e[3] < hi]


def per_step(view, line: str, pattern: str) -> list[tuple]:
    """(step, its device events of ``line`` matching ``pattern``) for each
    traced step. The k-th ``engine.step`` annotation of the trace is the
    k-th traced step; an event belongs to the step in whose annotation it
    starts. A step's decode launch, and what it launched before, have
    ended when the step returns, since the step ends in a host transfer of
    the decode's tokens."""
    if not view.events:
        return []
    lo, hi = tracemod.window(view.events)
    marks = sorted((e for e in view.events
                    if e[2] == "engine.step" and lo <= e[3] < hi),
                   key=lambda e: e[3])
    steps = traced_steps(view)
    if len(marks) != len(steps):
        return []
    evs = device(view, line, pattern)
    return [(s, [e for e in evs if m[3] <= e[3] < m[3] + m[4]])
            for s, m in zip(steps, marks)]


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peak: dict) -> float | None:
    """Least time the chip could take, over the time taken, in %."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


# Names by which the traced stretch's device events are found: the jitted
# decode step (``DecodeEngine._decode``) and prefill chunk
# (``DecodeEngine._prefill_chunk``) as XLA modules, and the Pallas kernels
# as XLA ops.
DECODE_MODULE = r"jit__decode_fused\("
PREFILL_MODULE = r"jit__lambda\("
PAGED_ATTENTION_OP = r"paged_attention(\.\d+)?$"
FUSED_REDUCE_ROWS_OP = r"fused_reduce_rows(\.\d+)?$"
