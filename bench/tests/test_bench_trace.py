"""The reduction from a device trace to metrics, checked on a short trace
of the qwen05b.chat cell recorded on a TPU v5e chip and committed
(``trace_qwen05b_chat.json.gz``: the events ``bench.trace.extract`` kept,
cut after a whole decode step, and the engine steps they hold)."""

import gzip
import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, views  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.drive import Record, StepLog  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "trace_qwen05b_chat.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def view(recorded):
    steps = [StepLog(**s) for s in recorded["steps"]]
    run = Record(t_start=0.0, t0=steps[0].t0, t1=steps[-1].t1 + 1.0,
                 t_end=steps[-1].t1 + 1.0, tracks=[], steps=steps,
                 kind="poisson",
                 trace_span=(steps[0].t0, steps[-1].t1))
    config = spec.load_json(spec.BENCH_DIR / "configs" / "qwen1.5-0.5b.json")
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")
    family = spec.load_family(config)
    return types.SimpleNamespace(
        run=run, events=recorded["events"], engine=recorded["engine"],
        dims=family.dims(config), family=family, peak=peaks["TPU v5 lite"])


def test_window_and_busy_time(recorded):
    evs = recorded["events"]
    lo, hi = tr.window(evs)
    assert hi > lo
    assert tr.device_planes(evs) == ["/device:TPU:0"]
    busy = tr.busy_ns(evs)
    assert 0 < busy <= hi - lo


def test_launches_match_the_steps(recorded, view):
    steps = view.run.steps
    decode = views.device(view, "XLA Modules", views.DECODE_MODULE)
    prefill = views.device(view, "XLA Modules", views.PREFILL_MODULE)
    assert len(decode) == sum(s.decode_launch for s in steps) > 0
    assert len(prefill) == sum(s.prefill_tokens > 0 for s in steps) > 0
    attn = views.device(view, "XLA Ops", views.PAGED_ATTENTION_OP)
    assert len(attn) == len(decode) * view.dims.layers
    frr = views.device(view, "XLA Ops", views.FUSED_REDUCE_ROWS_OP)
    assert len(frr) == 2 * len(decode) + 2 * sum(s.first_tokens
                                                 for s in steps)


@pytest.mark.parametrize("metric", [
    "decode_step_ms", "prefill_chunk_ms", "decode_mfu",
    "paged_attention_roofline", "fused_reduce_rows_roofline", "idle_share"])
def test_device_readers_find_their_numbers(view, metric):
    value = spec.load_reader(metric)(view)
    assert value is not None and value > 0
    if metric.endswith(("_roofline", "_mfu", "_share")):
        assert value <= 100


# What these readers gave on this trace before they took their work counts
# from the configuration's family: the same sums in the same order, so the
# same bits.
@pytest.mark.parametrize("metric, value", [
    ("decode_mfu", 0.0478224745673934),
    ("paged_attention_roofline", 0.9782609342347808)])
def test_family_readers_keep_their_values(view, metric, value):
    assert spec.load_reader(metric)(view) == value


def test_breakdown_names_ops_and_host_spans(recorded):
    ops = tr.top_ops(recorded["events"])
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = tr.idle_gaps(recorded["events"])
    assert gaps and all(isinstance(n, str) and s > 0 for n, s in gaps)
