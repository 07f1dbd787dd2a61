"""Host phase spans and request stamps of ``DecodeEngine.step``.

The spans (``repro.obs.span``) go into a profiler trace on the device
events' clock: one engine step inside an in-memory profiler session must
show its phases, nested in the caller's span, with every host read of a
device array inside an ``engine.fetch`` and no program span under the
caller's own names. The stamps (``Request.submit_time``, ``admit_time``,
``first_chunk_time``) keep their order, survive a preemption and its
restore, and are cleared for a retry.
"""

import contextlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.models import api, common
from repro.serving.engine import DecodeEngine, Request, SpecDecodeEngine
from repro.spec import NGramProposer

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tracemod  # noqa: E402

PHASES = ("engine.admit", "engine.prefill_chunk", "engine.first_token",
          "engine.decode_step", "engine.retire")
CALLER = "engine.step"


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    return cfg, params


def _engine(cfg, params, klass=DecodeEngine, **kw):
    return klass(cfg, params, max_slots=2, max_context=64, block_size=16,
                 prefill_chunk=16, **kw)


@contextlib.contextmanager
def _watch_reads(monkeypatch):
    """Record, for every host read of a device array, the names of the
    program spans open around it (innermost last)."""
    from jax._src.array import ArrayImpl

    stack: list[str] = []
    reads: list[tuple] = []
    real_span = obs.span

    @contextlib.contextmanager
    def span(name):
        stack.append(name)
        try:
            with real_span(name):
                yield
        finally:
            stack.pop()

    real_asarray = np.asarray

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            reads.append(tuple(stack))
        return real_asarray(a, *args, **kw)

    value = ArrayImpl._value

    def _value(self):
        reads.append(tuple(stack))
        return value.fget(self)

    monkeypatch.setattr(obs, "span", span)
    monkeypatch.setattr(np, "asarray", asarray)
    monkeypatch.setattr(ArrayImpl, "_value", property(_value))
    yield reads
    monkeypatch.undo()


def _traced_step(engine, monkeypatch):
    """One engine step under the caller's ``engine.step`` annotation in a
    profiler session; (the host events kept, the reads watched)."""
    cap = tracemod.Capture()
    with _watch_reads(monkeypatch) as reads:
        cap.start()
        try:
            with jax.profiler.TraceAnnotation(CALLER):
                engine.step()
        finally:
            cap.stop()
    events = [e for e in tracemod.extract(cap.xspace)
              if not tracemod.is_device_plane(e[0])
              and e[2] != tracemod.TRACED]
    return events, reads


def _check_nesting(events, reads):
    marks = [e for e in events if e[2] == CALLER]
    assert len(marks) == 1
    assert not any(e[2] == "engine.submit" for e in events)
    s0, e0 = marks[0][3], marks[0][3] + marks[0][4]
    program = [e for e in events if e is not marks[0]]
    for e in program:
        assert s0 <= e[3] and e[3] + e[4] <= e0, e
    # every host read sits directly in an engine.fetch, and a fetch holds
    # nothing but reads
    assert reads and all(r and r[-1] == "engine.fetch" for r in reads)
    assert not any(r.count("engine.fetch") > 1 for r in reads)
    # the top-level phases tile the step: what they leave is a sliver
    top = [e for e in program
           if not any(o is not e and o[3] <= e[3]
                      and e[3] + e[4] <= o[3] + o[4] for o in program)]
    covered = sum(e[4] for e in top)
    assert covered >= 0.9 * marks[0][4]
    return {e[2] for e in program}


def test_step_emits_phase_spans_inside_the_callers_span(setup, monkeypatch):
    cfg, params = setup
    engine = _engine(cfg, params)
    # warm: compile every program the traced step runs
    engine.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=2))
    engine.run_until_done()
    # one step: admission, the whole prompt in one chunk, its first token,
    # a decode that emits the second and last token, and the retirement
    engine.submit(Request(rid=1, prompt=[2, 7, 1, 8, 2], max_new_tokens=2))
    events, reads = _traced_step(engine, monkeypatch)
    names = _check_nesting(events, reads)
    assert set(PHASES) | {"engine.fetch"} <= names
    fetches = [e for e in events if e[2] == "engine.fetch"]
    # the first token's choice and its statistics, the decode's packed row
    assert len(fetches) == 3
    assert engine.num_unfinished == 0


def test_spec_step_names_verify_and_propose(setup, monkeypatch):
    cfg, params = setup
    engine = _engine(cfg, params, klass=SpecDecodeEngine,
                     proposer=NGramProposer(), spec_k=2)
    engine.submit(Request(rid=0, prompt=[5, 6, 5, 6, 5], max_new_tokens=12))
    engine.step()                   # prefill, first token, a verify step
    events, reads = _traced_step(engine, monkeypatch)
    names = _check_nesting(events, reads)
    assert {"engine.admit", "engine.verify_step", "engine.propose",
            "engine.fetch"} <= names
    assert "engine.decode_step" not in names


def test_stamps_keep_order_survive_preemption_and_clear_on_retry(setup):
    cfg, params = setup
    engine = _engine(cfg, params)
    # two slots, three requests: the second is admitted behind the
    # first's two-chunk prompt, the third waits for a slot
    reqs = [Request(rid=i, prompt=list(range(10 + i, 10 + i + n)),
                    max_new_tokens=6)
            for i, n in enumerate((20, 5, 7))]
    for r in reqs:
        engine.submit(r)
    assert all(r.admit_time is None for r in reqs)
    victim = None
    for _ in range(100):
        engine.step()
        if victim is None and reqs[1].state == "decoding":
            victim = reqs[1]
            before = (victim.submit_time, victim.admit_time,
                      victim.first_chunk_time)
            engine.preempt(victim.rid)
            assert victim.state == "preempted"
        if not engine.num_unfinished:
            break
    assert victim is not None and engine.kv_stats["restored_blocks"] > 0
    for r in reqs:
        assert r.state == "done"
        assert r.submit_time <= r.admit_time <= r.first_chunk_time
    assert (victim.submit_time, victim.admit_time,
            victim.first_chunk_time) == before
    # admitted together, but the second's first chunk waits for the
    # first's whole prompt; the third waits for a slot
    assert reqs[1].admit_time <= reqs[0].first_chunk_time
    assert reqs[1].first_chunk_time > reqs[0].first_chunk_time
    assert reqs[2].admit_time > reqs[1].admit_time
    # a cancelled run, retried: the retry takes stamps of its own
    retry = Request(rid=9, prompt=[4, 4, 4], max_new_tokens=6)
    engine.submit(retry)
    engine.step()
    first_run = retry.first_chunk_time
    assert engine.cancel(retry.rid) and first_run is not None
    retry.reset_for_retry()
    assert (retry.submit_time, retry.admit_time,
            retry.first_chunk_time) == (None, None, None)
    engine.submit(retry)
    engine.run_until_done()
    assert first_run < retry.submit_time <= retry.admit_time \
        <= retry.first_chunk_time
