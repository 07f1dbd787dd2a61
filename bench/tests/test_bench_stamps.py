"""The readers of the engine's request stamps and host-step spans: their
values on a small event list, nothing from the committed trace (which
predates the spans), and the two host-clock metrics in a traced CPU
rehearsal, where the device-trace ones stay unwritten."""

import gzip
import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from rehearsal import rehearse  # noqa: E402

from bench import spec, stamps  # noqa: E402
from bench.drive import Record, StepLog, Track  # noqa: E402

SEED = 2**31 + 5151
FIXTURE = Path(__file__).resolve().parent / "trace_qwen05b_chat.json.gz"
HOST = ("/host:CPU", "python")
MS = 1e6                                   # ns


def ev(name, start_ms, dur_ms, plane=HOST):
    return [plane[0], plane[1], name, start_ms * MS, dur_ms * MS]


def view_of(events):
    return types.SimpleNamespace(events=events)


SYNTHETIC = [
    ev("bench.traced", 0, 100),
    ev("op", 1, 5, ("/device:TPU:0", "XLA Ops")),
    # a step with two overlapping fetches (union 6 ms) and one without any
    ev("engine.step", 10, 20),
    ev("engine.decode_step", 11, 18),
    ev("engine.fetch", 12, 4),
    ev("engine.fetch", 14, 4),
    ev("engine.step", 40, 10),
    # outside the traced stretch: not a step of it
    ev("engine.step", 120, 10),
    ev("engine.fetch", 121, 2),
]


@pytest.mark.parametrize("metric,value", [
    ("step_host_ms", ((20 - 6) + 10) / 2), ("fetches_per_step", 1.0)])
def test_host_step_readers_on_a_small_event_list(metric, value):
    assert spec.load_reader(metric)(view_of(SYNTHETIC)) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", ["step_host_ms", "fetches_per_step"])
def test_host_step_readers_need_fetch_spans_and_a_device(metric):
    read = spec.load_reader(metric)
    with gzip.open(FIXTURE, "rt") as f:
        recorded = json.load(f)["events"]
    assert any(e[2] == "engine.step" for e in recorded)
    assert read(view_of(recorded)) is None           # no engine.fetch
    host_only = [e for e in SYNTHETIC if e[0] == HOST[0]]
    assert read(view_of(host_only)) is None          # no device plane
    assert read(view_of(None)) is None               # untraced


def _run(tracks):
    return types.SimpleNamespace(run=Record(
        t_start=0.0, t0=0.0, t1=10.0, t_end=10.0, tracks=tracks,
        steps=[StepLog(0.0, 1.0, [], 0, 0, False)], kind="poisson"))


def _track(due, **stamp):
    req = types.SimpleNamespace(**stamp)
    return Track(arrival=None, req=req, due=due, submit=due)


def test_stamp_readers_skip_requests_without_stamps():
    tracks = [_track(1.0, submit_time=1.0, admit_time=1.002,
                     first_chunk_time=1.5),
              _track(2.0, submit_time=2.0, admit_time=2.004,
                     first_chunk_time=2.1),
              _track(3.0)]                       # a program without them
    view = _run(tracks)
    assert stamps.waits_ms(view, "submit_time", "admit_time") == \
        pytest.approx([2.0, 4.0])
    assert spec.load_reader("admit_wait_p50_ms")(view) == pytest.approx(3.0)
    assert spec.load_reader("prefill_wait_p90_ms")(view) == \
        pytest.approx(96 + 0.9 * (498 - 96))
    bare = _run([_track(1.0)])
    assert spec.load_reader("admit_wait_p50_ms")(bare) is None
    assert spec.load_reader("prefill_wait_p90_ms")(bare) is None


def test_traced_rehearsal_reads_the_engine_stamps():
    res = rehearse(SEED, trace=True)
    assert res["correct"] is True
    got = res["metrics"]
    for name in ("admit_wait_p50_ms", "prefill_wait_p90_ms"):
        assert got[name]["unit"] == "ms"
        assert got[name]["value"] >= 0
    # the tiny mix queues prompts behind each other's chunks
    assert got["prefill_wait_p90_ms"]["value"] > 0
    # no device plane on the CPU: the host-step metrics stay unwritten
    assert not {"step_host_ms", "fetches_per_step"} & set(got)
