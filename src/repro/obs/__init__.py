"""Serving telemetry: tracing, metrics, ECM residuals.

The paper's whole method is observability — low-level counters plus an
analytic model, compared continuously, to pinpoint where a bottleneck
lives. This package is that method applied to the serving engine:

  trace      — per-request lifecycle spans on the monotonic ENGINE-STEP
               clock (deterministic; wall time is an optional
               annotation), exported as JSONL and Perfetto-loadable
               Chrome trace JSON
  metrics    — typed Counter/Gauge/Histogram registry; the engine's
               ``metrics_snapshot()`` subsumes the legacy ``kv_stats``
               counters value-for-value and adds distributions (TTFT,
               queue wait) and derived rates, exportable as JSON and
               Prometheus text
  residuals  — every standing ECM forecast paired with its measured
               counterpart, tagged with the BASIS of the measurement
               (deterministic counter vs wall clock) so the perf
               trajectory can tell model error, code regression and
               host drift apart
  profile    — the ECM attribution profiler (``Telemetry(profile=True)``):
               per-phase HLO flops/bytes counters priced on the
               drift-calibrated machine model, so wall time is
               *attributed* (compute/HBM/host/dispatch/unattributed),
               not just measured
  span       — a host phase on the PROFILER'S clock:
               ``jax.profiler.TraceAnnotation``, written into a profiler
               trace beside the device events, so a device-idle gap can
               be put down to what the host was doing in it. Always on:
               under a microsecond a span with no profiler session.
               ``DecodeEngine.step`` tiles each step with the phases
               ``engine.admit``, ``engine.prefill_chunk``,
               ``engine.first_token``, ``engine.decode_step`` (or
               ``engine.verify_step``, with ``engine.propose`` inside)
               and ``engine.retire``, and puts every host read that
               waits on the device in an ``engine.fetch`` of its own.
               The ``Tracer`` is the deterministic lifecycle log on the
               engine-step clock; ``span`` is the host phase on the
               device trace's clock. The phases reuse the Tracer's
               instant names (``prefill_chunk``, ``decode_step``,
               ``verify_step``).

``Telemetry`` bundles the first three behind one handle; ``NULL`` is the
always-off default the engine holds when no telemetry is attached —
every hot-path hook guards on ``obs.enabled``, so a disabled engine
runs the exact PR-7 hot path (one fused launch + one transfer per
step), with the enabled-overhead bound benchmarked by
``benchmarks/bench_serving.py`` (serving/obs/overhead row).
"""

from __future__ import annotations

from repro.obs.metrics import (Counter, Gauge, Histogram, Metric,
                               MetricsRegistry)
from repro.obs.residuals import (ResidualLog, ResidualRecord,
                                 residual_row)
from repro.obs.trace import TraceEvent, Tracer


class Telemetry:
    """One recorder handle: a Tracer + MetricsRegistry + ResidualLog
    sharing the engine-step clock. ``wall_clock=True`` additionally
    stamps trace events with ``time.perf_counter()``; it never changes
    the deterministic event sequence. ``profile=True`` attaches the ECM
    attribution ``Profiler`` (``self.profile``, else None) — the engine
    then records per-phase HLO cost counters and wall seconds; the
    counter side of the attribution stays deterministic, and the
    Perfetto counter tracks it produces are merged only at
    ``to_chrome()`` export, never into the Tracer's event list."""

    enabled = True

    def __init__(self, wall_clock: bool = False, profile: bool = False):
        self.trace = Tracer(wall_clock)
        self.metrics = MetricsRegistry()
        self.residuals = ResidualLog()
        if profile:
            from repro.obs.profile import Profiler
            self.profile = Profiler()
        else:
            self.profile = None

    def set_step(self, step: int) -> None:
        self.trace.set_step(step)
        if self.profile is not None:
            self.profile.set_step(step)

    def to_chrome(self, path) -> int:
        """Chrome-trace export with the profiler's ECM counter tracks
        appended (when profiling); returns the span/instant count."""
        extra = (self.profile.counter_events()
                 if self.profile is not None else None)
        return self.trace.to_chrome(path, extra_events=extra)


class _NullTelemetry:
    """The disabled recorder: ``enabled`` is False and every hook is a
    no-op, so instrumented components can hold it unconditionally and
    the hot path stays a single predictable attribute check."""

    enabled = False
    profile = None

    def set_step(self, step: int) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NULL = _NullTelemetry()


def __getattr__(name: str):
    # ``span`` is jax.profiler.TraceAnnotation, bound on first use so that
    # importing this package does not import jax; later lookups find the
    # class itself, at the annotation's own cost
    if name == "span":
        from jax.profiler import TraceAnnotation
        globals()["span"] = TraceAnnotation
        return TraceAnnotation
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = ["Telemetry", "NULL", "Tracer", "TraceEvent", "MetricsRegistry",
           "Metric", "Counter", "Gauge", "Histogram", "ResidualLog",
           "ResidualRecord", "residual_row", "span"]
# repro.obs.profile (Profiler, Calibration, calibrate) is imported
# lazily — it pulls in jax/kernels, which plain telemetry users
# (metrics scraping, trace readers) should not pay for; so is ``span``.
