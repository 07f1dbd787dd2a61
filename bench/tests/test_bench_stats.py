"""Percentile, rate and idle-share arithmetic on hand-built timestamps."""

import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, stats, trace  # noqa: E402


def test_percentile_interpolates_between_order_statistics():
    xs = [40.0, 10.0, 30.0, 20.0]           # sorted: 10 20 30 40
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 40.0
    assert stats.percentile(xs, 50) == pytest.approx(25.0)
    # rank 0.9 * 3 = 2.7 -> 30 + 0.7 * 10
    assert stats.percentile(xs, 90) == pytest.approx(37.0)


def test_percentile_of_one_sample_and_of_none():
    assert stats.percentile([7.5], 95) == 7.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_over_the_window():
    assert stats.rate(510, 51.0) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_union_merges_overlaps_and_clips():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert stats.union_length(iv) == pytest.approx(4.0)
    assert stats.union_length(iv, 1.5, 5.5) == pytest.approx(2.0)


def test_idle_share_and_gaps_of_a_traced_window():
    # device busy 10-20, 25-40 and 38-45 ns of a traced window 0-50 ns
    events = [["/host:CPU", "python", trace.TRACED, 0.0, 50.0],
              ["/host:CPU", "python", "engine.step", 8.0, 40.0]]
    events += [["/device:TPU:0", "XLA Ops", f"%op.{i} = f32[8] fusion()", s,
                e - s] for i, (s, e) in enumerate([(10.0, 20.0), (25.0, 40.0),
                                                   (38.0, 45.0)])]
    assert trace.busy_ns(events) == pytest.approx(30.0)
    view = types.SimpleNamespace(events=events)
    assert spec.load_reader("idle_share")(view) == pytest.approx(40.0)
    assert stats.gaps(trace.intervals(events[2:]), 0.0, 50.0) == [
        (0.0, 10.0), (20.0, 25.0), (45.0, 50.0)]
    # each gap is named by the host span over its midpoint
    assert trace.idle_gaps(events) == [
        ["outside the benchmark's spans", pytest.approx(10e-9)],
        ["engine.step", pytest.approx(5e-9)],
        ["engine.step", pytest.approx(5e-9)]]
