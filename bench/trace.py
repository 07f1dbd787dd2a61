"""Profiler capture of a steady stretch, and its reduction to events.

``Capture`` starts and stops JAX's profiler between engine steps and
marks the stretch with a ``bench.traced`` host annotation. ``extract``
reads the profile it collected into plain events, on one clock:

  [plane, line, name, start_ns, dur_ns]

keeping the device lines ``XLA Modules`` (one event per program launch)
and ``XLA Ops`` (one per operation), and the host annotations whose names
start with ``bench.`` or ``engine.``. Everything downstream (busy time,
kernel time, idle gaps) is computed from that list, so the reduction can
be checked on a small recorded list without a chip.
"""

from __future__ import annotations

import re

from bench import stats

DEVICE_LINES = ("XLA Modules", "XLA Ops")
HOST_PREFIXES = ("bench.", "engine.")
TRACED = "bench.traced"


class Capture:
    """An in-memory profiler session: nothing is written to disk. The
    Python tracer is off, so the host keeps its pace while traced; the
    host annotations (``TraceAnnotation``) are still recorded."""

    def __init__(self):
        self._session = None
        self._ann = None
        self.xspace = None

    def start(self) -> None:
        import jax
        from jax._src import xla_bridge
        from jax._src.lib import _profiler
        xla_bridge.get_backend()     # the TPU tracer needs libtpu up
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self._session = _profiler.ProfilerSession(opts)
        self._ann = jax.profiler.TraceAnnotation(TRACED)
        self._ann.__enter__()

    def stop(self) -> None:
        self._ann.__exit__(None, None, None)
        self.xspace = self._session.stop()
        self._session = None


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") or name.startswith("/device:GPU:")


def extract(xspace: bytes) -> list[list]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append([plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)])
    return out


# ------------------------------------------------------------ reduction ----

def window(events) -> tuple[float, float]:
    """The traced stretch, from the ``bench.traced`` annotation."""
    for p, line, name, s, d in events:
        if name == TRACED:
            return s, s + d
    raise ValueError("no bench.traced annotation in the trace")


def device_events(events, line: str) -> list[list]:
    return [e for e in events if is_device_plane(e[0]) and e[1] == line]


def device_planes(events) -> list[str]:
    return sorted({e[0] for e in events if is_device_plane(e[0])})


def intervals(evs) -> list[tuple[float, float]]:
    return [(e[3], e[3] + e[4]) for e in evs]


def busy_ns(events) -> float:
    """Time in which an operation ran, averaged over the device planes."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return 0.0
    ops = device_events(events, "XLA Ops")
    return sum(stats.union_length(intervals(e for e in ops if e[0] == p),
                                  lo, hi) for p in planes) / len(planes)


def op_name(name: str) -> str:
    """An XLA op event's instruction name: ``%paged_attention.10 = bf16[..]
    custom-call(..)`` -> ``paged_attention.10``. A module event's name is
    returned whole (``jit__decode_fused(123)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def matching(evs, pattern: str) -> list[list]:
    """Events whose instruction (or module) name matches ``pattern`` from
    its start: an op that only takes a kernel's output as an operand does
    not match the kernel."""
    rx = re.compile(pattern)
    return [e for e in evs if rx.match(op_name(e[2]))]


def self_times(evs) -> list[tuple[list, float]]:
    """(event, self time): an op's duration less that of the ops nested
    in it (a ``while`` loop holds the ops of its body)."""
    order = sorted(evs, key=lambda e: (e[3], -e[4]))
    child = [0.0] * len(order)
    stack: list[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]][3] + order[stack[-1]][4] <= e[3]:
            stack.pop()
        if stack:
            child[stack[-1]] += e[4]
        stack.append(i)
    return [(e, max(0.0, e[4] - c)) for e, c in zip(order, child)]


def total_ns(evs) -> float:
    return sum(e[4] for e in evs)


NAME_CHARS = 160


def top_ops(events, n: int = 10) -> list[list]:
    """[name, seconds] of the device operations that took most time, by
    self time (a loop's own time, not its body's), summed over launches;
    names as the trace gives them, cut to ``NAME_CHARS``."""
    lo, hi = window(events)
    ops = [e for e in device_events(events, "XLA Ops") if lo <= e[3] < hi]
    acc: dict = {}
    for e, t in self_times(ops):
        key = e[2][:NAME_CHARS]
        acc[key] = acc.get(key, 0.0) + t
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events, n: int = 10) -> list[list]:
    """[host span, seconds] of the longest device-idle stretches, each
    named by the innermost host annotation that covers its midpoint."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return []
    ops = [e for e in device_events(events, "XLA Ops") if e[0] == planes[0]]
    host = [e for e in events if not is_device_plane(e[0])
            and e[2] != TRACED]
    out = []
    for s, e in stats.gaps(intervals(ops), lo, hi):
        mid = (s + e) / 2
        cover = [h for h in host if h[3] <= mid < h[3] + h[4]]
        name = (min(cover, key=lambda h: h[4])[2] if cover
                else "outside the benchmark's spans")
        out.append([name, (e - s) / 1e9])
    return sorted(out, key=lambda x: -x[1])[:n]
