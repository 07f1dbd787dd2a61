"""Drive the engine through one run: ramp, window, drain.

One thread. Between engine steps the driver submits every request that is
due (open loop) or tops up the backlog, then calls ``DecodeEngine.step``
and stamps every token the step emitted with the host time at which the
step returned: the step ends in a host transfer of its tokens, so that is
when the token is on the host. Times are ``time.perf_counter`` seconds.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

from bench.loadgen import Arrival, Schedule

TERMINAL = ("done", "cancelled", "expired", "quarantined", "failed")


@dataclass
class Track:
    arrival: Arrival
    req: object                   # repro.serving.engine.Request
    due: float
    submit: float
    admit: float | None = None
    stamps: list = field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.req.state in TERMINAL


@dataclass
class StepLog:
    t0: float
    t1: float
    decode_lens: list             # attention length of each decode token
    first_tokens: int             # requests whose first token came
    prefill_tokens: int           # prompt tokens of the step's chunk
    decode_launch: bool


@dataclass
class Record:
    t_start: float                # schedule start (ramp begins)
    t0: float                     # window start
    t1: float                     # window end
    t_end: float                  # driver stopped
    tracks: list
    steps: list
    kind: str                     # the mix's arrival kind
    trace_span: tuple | None = None

    def window_tracks(self) -> list[Track]:
        if self.kind == "backlog":
            return [t for t in self.tracks
                    if any(self.t0 <= s < self.t1 for s in t.stamps)]
        return [t for t in self.tracks if self.t0 <= t.due < self.t1]


def _noop(_name):
    return contextlib.nullcontext()


class Driver:
    """``span(name)`` wraps the driver's own calls (a profiler annotation
    in a traced run). ``trace_at`` = (start, stop) seconds into the window
    at which ``on_trace(True/False)`` is called, between steps."""

    def __init__(self, engine, schedule: Schedule, seconds: float, *,
                 request_cls, span=_noop, trace_at=None, on_trace=None,
                 clock=time.perf_counter, sleep=time.sleep):
        self.engine = engine
        self.schedule = schedule
        self.seconds = float(seconds)
        self.Request = request_cls
        self.span = span
        self.trace_at = trace_at
        self.on_trace = on_trace
        self.clock = clock
        self.sleep = sleep

    def _submit(self, a: Arrival, due: float, live: dict, tracks: list):
        req = self.Request(rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new)
        with self.span("engine.submit"):
            self.engine.submit(req)
        tr = Track(arrival=a, req=req, due=due, submit=self.clock())
        live[a.rid] = tr
        tracks.append(tr)

    def run(self) -> Record:
        eng, sch, clock = self.engine, self.schedule, self.clock
        t_start = clock()
        t0 = t_start + sch.ramp_s
        t1 = t0 + self.seconds
        t_end = t1 + sch.drain_s
        pending = list(reversed(sch.timed))
        live: dict = {}
        tracks: list[Track] = []
        steps: list[StepLog] = []
        backlog = sch.kind == "backlog"
        closed = False
        tracing = None
        trace_span = None
        kv = eng.kv_stats
        last_dec, last_pf = kv["decode_steps"], kv["prefill_tokens"]
        while True:
            now = clock()
            with self.span("bench.generate"):
                while pending and t_start + pending[-1].due_s <= now:
                    a = pending.pop()
                    self._submit(a, t_start + a.due_s, live, tracks)
                if backlog and now < t1:
                    waiting = sum(1 for t in live.values()
                                  if t.req.state == "queued")
                    for _ in range(sch.waiting_target - waiting):
                        self._submit(sch.next_backlog(), now, live, tracks)
            if self.trace_at is not None:
                lo, hi = t0 + self.trace_at[0], t0 + self.trace_at[1]
                if tracing is None and now >= lo:
                    self.on_trace(True)
                    tracing = clock()
                elif tracing is not None and trace_span is None and now >= hi:
                    trace_span = (tracing, clock())
                    self.on_trace(False)
            if now >= t1:
                if backlog and not closed:
                    # the backlog's requests that never got a slot were
                    # not attempted: withdraw them, and drain the rest
                    for t in live.values():
                        if t.req.state == "queued":
                            eng.cancel(t.req.rid)
                    closed = True
                if now >= t_end:
                    break
                if all(t.finished for t in tracks
                       if backlog or t0 <= t.due < t1):
                    break
            if not eng.num_unfinished:
                nxt = t_start + pending[-1].due_s if pending else t1
                self.sleep(max(0.0, min(nxt, t_end) - clock()))
                continue
            ts = clock()
            with self.span("engine.step"):
                eng.step()
            te = clock()
            with self.span("bench.collect"):
                decode_lens, first = [], 0
                for rid in list(live):
                    tr = live[rid]
                    req = tr.req
                    if tr.admit is None and req.state != "queued":
                        tr.admit = ts
                    n, seen = len(req.output), len(tr.stamps)
                    if n > seen:
                        tr.stamps.extend([te] * (n - seen))
                        got_first = seen == 0
                        first += got_first
                        if n - seen - got_first:
                            decode_lens.append(len(req.prompt) + n - 1)
                    if tr.finished:
                        del live[rid]
                dec, pf = kv["decode_steps"], kv["prefill_tokens"]
                steps.append(StepLog(ts, te, decode_lens, first, pf - last_pf,
                                     dec > last_dec))
                last_dec, last_pf = dec, pf
        if tracing is not None and trace_span is None:
            trace_span = (tracing, clock())
            self.on_trace(False)
        return Record(t_start=t_start, t0=t0, t1=t1, t_end=clock(),
                      tracks=tracks, steps=steps, kind=sch.kind,
                      trace_span=trace_span)
