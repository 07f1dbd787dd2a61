"""Serving engine tests: paged continuous batching must match solo greedy
generation token-for-token, the admission queue must absorb overload, and
the fused logprob path must match a plain logsumexp reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import api, common, paged
from repro.serving.engine import DecodeEngine, Request
from repro.serving.faults import AdmissionError


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    return cfg, params


MAX_CONTEXT = 64
BLOCK = 16
CHUNK = 32


def _solo_caches(cfg, layout):
    kv = api.KVCache.build(cfg, max_context=layout.max_context,
                           block_size=layout.block_size, max_slots=1)
    caches = kv.init(1)
    row = jnp.arange(1, 1 + layout.max_blocks, dtype=jnp.int32)
    return jax.jit(paged.reset_slot)(caches, jnp.int32(0), row)


def _reference_generate(cfg, params, prompt, n_new, chunk_size=CHUNK):
    """Solo greedy generation through the SAME paged chunked-prefill +
    decode path the engine batches — the determinism contract is that
    batching must not perturb any individual stream."""
    layout = paged.PagedLayout(BLOCK, MAX_CONTEXT // BLOCK)
    caches = _solo_caches(cfg, layout)
    chunk_fn = jax.jit(api.prefill_chunk_fn(cfg))
    decode = jax.jit(api.decode_fn(cfg))
    pos = 0
    while pos < len(prompt):
        chunk = prompt[pos:pos + chunk_size]
        logits, caches = chunk_fn(params, jnp.asarray([chunk], jnp.int32),
                                  caches, jnp.int32(0), jnp.int32(pos))
        pos += len(chunk)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < n_new:
        logits, caches = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                                caches)
        out.append(int(jnp.argmax(logits[0])))
    return out


def _engine(cfg, params, **kw):
    kw.setdefault("max_context", MAX_CONTEXT)
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("prefill_chunk", CHUNK)
    return DecodeEngine(cfg, params, **kw)


def test_single_request_matches_reference(setup):
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    req = Request(rid=0, prompt=[5, 9, 11], max_new_tokens=6)
    engine.submit(req)
    engine.run_until_done()
    assert req.done
    assert req.output == _reference_generate(cfg, params, [5, 9, 11], 6)


def test_continuous_batching_mid_stream_join(setup):
    """A request joining mid-decode must not perturb the resident request,
    and both must match their solo generations."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    r1 = Request(rid=1, prompt=[1, 2, 3, 4], max_new_tokens=8)
    engine.submit(r1)
    engine.step()
    engine.step()                      # r1 two tokens in
    r2 = Request(rid=2, prompt=[7, 8], max_new_tokens=5)
    engine.submit(r2)                  # joins mid-stream
    engine.run_until_done()
    assert r1.done and r2.done
    assert r1.output == _reference_generate(cfg, params, [1, 2, 3, 4], 8)
    assert r2.output == _reference_generate(cfg, params, [7, 8], 5)


def test_slot_and_block_reuse(setup):
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=1)
    r1 = Request(rid=1, prompt=[3, 1], max_new_tokens=3)
    engine.submit(r1)
    engine.run_until_done()
    free_after = engine.scheduler.allocator.num_free
    assert free_after == engine.kv.num_blocks - 1   # all blocks returned
    r2 = Request(rid=2, prompt=[9, 9, 9], max_new_tokens=3)
    engine.submit(r2)                  # reuses the slot AND its blocks
    engine.run_until_done()
    assert r2.output == _reference_generate(cfg, params, [9, 9, 9], 3)


def test_submit_beyond_slot_pool_queues(setup):
    """Regression: submitting more requests than slots must queue, not
    assert — every request completes, in FIFO admission order."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new_tokens=3)
            for i in range(6)]
    for r in reqs:
        engine.submit(r)               # 6 requests, 2 slots: no assert
    assert engine.num_unfinished == 6
    engine.run_until_done()
    assert all(r.done for r in reqs)
    for r in reqs:
        assert r.output == _reference_generate(cfg, params, r.prompt, 3)


def test_chunked_prefill_interleaves_with_decode(setup):
    """A long prompt is prefilled chunk-by-chunk while the resident
    request keeps emitting one token per engine step (never stalled)."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2, prefill_chunk=4)
    r1 = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=12)
    engine.submit(r1)
    engine.step()                      # r1 prefilled + first token + 1 step
    emitted = [len(r1.output)]         # == 2
    long_prompt = list(range(5, 5 + 20))   # 5 chunks of 4
    r2 = Request(rid=2, prompt=long_prompt, max_new_tokens=4)
    engine.submit(r2)
    for _ in range(5):                 # r2's prefill spans these steps
        engine.step()
        emitted.append(len(r1.output))
    # r1 gained a token on EVERY step — chunked prefill did not stall it
    assert emitted == list(range(2, 8)), emitted
    engine.run_until_done()
    assert r1.output == _reference_generate(cfg, params, r1.prompt, 12)
    assert r2.output == _reference_generate(cfg, params, long_prompt, 4)


def test_context_overflow_rejected(setup):
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    # AdmissionError subclasses ValueError — both contracts hold
    with pytest.raises(AdmissionError):
        engine.submit(Request(rid=0, prompt=list(range(60)),
                              max_new_tokens=10))   # 70 > 64
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0, prompt=list(range(60)),
                              max_new_tokens=10))


def test_ssm_family_engine():
    """The engine also serves SSM archs (constant-size state caches +
    conv/SSD state continuation across prefill chunks)."""
    cfg = reduced(get_config("mamba2-780m"))
    params = common.init_params(api.schema(cfg), jax.random.key(1))
    engine = _engine(cfg, params, max_slots=2, prefill_chunk=2)
    req = Request(rid=0, prompt=[4, 8, 15], max_new_tokens=5)
    engine.submit(req)
    engine.run_until_done()
    assert req.done and len(req.output) == 5
    # parity with the solo chunked path
    assert req.output == _reference_generate(cfg, params, [4, 8, 15], 5)


def test_ssm_interleaved_prefill_parity():
    """Regression: the batched decode step must not pollute the recurrent
    SSM state of a slot that is mid-chunked-prefill — both the resident
    request and the late joiner must match their solo generations."""
    cfg = reduced(get_config("mamba2-780m"))
    params = common.init_params(api.schema(cfg), jax.random.key(1))
    engine = _engine(cfg, params, max_slots=2, prefill_chunk=4)
    r1 = Request(rid=1, prompt=[4, 8, 15], max_new_tokens=10)
    engine.submit(r1)
    engine.step()                      # r1 resident and decoding
    long_prompt = list(range(3, 23))   # 5 chunks, interleaved with decode
    r2 = Request(rid=2, prompt=long_prompt, max_new_tokens=4)
    engine.submit(r2)
    engine.run_until_done()
    assert r1.done and r2.done
    assert r1.output == _reference_generate(cfg, params, [4, 8, 15], 10,
                                            chunk_size=4)
    assert r2.output == _reference_generate(cfg, params, long_prompt, 4,
                                            chunk_size=4)


def test_submit_rejects_pool_overflow(setup):
    """A request that could never fit the (oversubscribed) block pool is
    rejected at submit instead of livelocking the FIFO queue."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2, max_context=64,
                     num_blocks=3)     # 2 usable blocks = 32 tokens
    with pytest.raises(AdmissionError):
        engine.submit(Request(rid=0, prompt=[1] * 30, max_new_tokens=10))
    ok = Request(rid=1, prompt=[1] * 20, max_new_tokens=10)
    engine.submit(ok)
    engine.run_until_done()
    assert ok.done


def test_logprobs_fused_path(setup):
    """The fused-engine logprob/metric path: every emitted token carries a
    logprob equal to (chosen logit - logsumexp), computed via the batched
    fused reduction; must match a plain jnp logsumexp reference."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    req = Request(rid=0, prompt=[5, 9, 11], max_new_tokens=4)
    engine.submit(req)

    # independent reference replay through the solo paged path
    layout = paged.PagedLayout(BLOCK, MAX_CONTEXT // BLOCK)
    caches = _solo_caches(cfg, layout)
    chunk_fn = jax.jit(api.prefill_chunk_fn(cfg))
    decode = jax.jit(api.decode_fn(cfg))
    logits, caches = chunk_fn(params, jnp.asarray([[5, 9, 11]], jnp.int32),
                              caches, jnp.int32(0), jnp.int32(0))
    ref_lp = []
    row = np.asarray(logits, np.float32).reshape(-1)
    tok = int(row.argmax())
    lse = float(jax.scipy.special.logsumexp(jnp.asarray(row)))
    ref_lp.append(row[tok] - lse)
    while len(ref_lp) < 4:
        logits, caches = decode(params, jnp.asarray([[tok]], jnp.int32),
                                caches)
        row = np.asarray(logits, np.float32).reshape(-1)
        tok = int(row.argmax())
        lse = float(jax.scipy.special.logsumexp(jnp.asarray(row)))
        ref_lp.append(row[tok] - lse)

    engine.run_until_done()
    assert req.done and len(req.logprobs) == 4
    np.testing.assert_allclose(np.asarray(req.logprobs), np.asarray(ref_lp),
                               rtol=1e-5, atol=1e-5)
    assert all(lp <= 0.0 for lp in req.logprobs)
    # the batched stats dict is exposed for monitoring
    assert set(engine.last_logit_stats) == {"logprob", "logsumexp", "max",
                                            "mean", "rms", "round_off"}


def test_sampling_deterministic_per_seed(setup):
    """Temperature sampling is keyed on (request seed, emit index) only:
    the same seed reproduces the same tokens across engines and batch
    compositions; a different seed (almost surely) diverges."""
    cfg, params = setup

    def generate(seed, companion=False):
        engine = _engine(cfg, params, max_slots=2)
        req = Request(rid=0, prompt=[5, 9, 11], max_new_tokens=8,
                      temperature=1.5, seed=seed)
        engine.submit(req)
        if companion:      # a second (greedy) request shares the batch
            engine.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=8))
        engine.run_until_done()
        return req.output

    solo = generate(7)
    assert generate(7) == solo                       # reproducible
    assert generate(7, companion=True) == solo       # batch-invariant
    runs = {tuple(generate(s)) for s in (7, 8, 9, 10)}
    assert len(runs) > 1                             # seed actually matters


def test_sampling_top_k_one_is_greedy(setup):
    """top_k=1 collapses the sampling distribution onto the argmax, so any
    temperature/seed must reproduce the greedy stream exactly."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2)
    req = Request(rid=0, prompt=[5, 9, 11], max_new_tokens=6,
                  temperature=2.0, top_k=1, seed=123)
    engine.submit(req)
    engine.run_until_done()
    assert req.output == _reference_generate(cfg, params, [5, 9, 11], 6)
    # logprobs ride the fused stats pass for sampled tokens too
    assert len(req.logprobs) == 6 and all(lp <= 0.0 for lp in req.logprobs)


def test_quantized_kv_engine_matches_solo(setup):
    """An int8-KV engine still satisfies the determinism contract: batched
    greedy serving matches the solo paged path under the SAME quantized
    cache (and touches ~1.6x fewer KV bytes than bf16 pools would —
    head_dim=16 here, so the f32 scale amortizes over only 16 elements)."""
    cfg, _ = setup
    cfg = cfg.with_(kv_dtype="int8")
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    engine = _engine(cfg, params, max_slots=2)
    reqs = [Request(rid=0, prompt=[5, 9, 11], max_new_tokens=6),
            Request(rid=1, prompt=[7, 8], max_new_tokens=4)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    assert reqs[0].output == _reference_generate(cfg, params, [5, 9, 11], 6)
    assert reqs[1].output == _reference_generate(cfg, params, [7, 8], 4)
    st = engine.kv_stats
    assert st["paged_bytes_bf16"] > 1.5 * st["paged_bytes"]


def test_kv_traffic_accounting(setup):
    """Short requests in a wide-context engine touch far fewer KV bytes
    than the contiguous per-slot layout would."""
    cfg, params = setup
    engine = _engine(cfg, params, max_slots=2, max_context=256)
    for i in range(3):
        engine.submit(Request(rid=i, prompt=[1 + i, 2, 3],
                              max_new_tokens=4))
    engine.run_until_done()
    st = engine.kv_stats
    assert st["paged_bytes"] > 0
    assert st["contiguous_bytes"] > 4 * st["paged_bytes"]
    # the typed metrics snapshot subsumes kv_stats value-for-value
    snap = engine.metrics_snapshot()
    assert all(snap[k] == v for k, v in st.items())


def _slot_lens(caches) -> np.ndarray:
    """Every slot's cached length ([B]), read from the ``len`` leaves of
    a batched cache tree (one row per layer, all equal)."""
    rows = [np.asarray(leaf).reshape(-1, np.shape(leaf)[-1])
            for path, leaf in jax.tree_util.tree_leaves_with_path(caches)
            if str(getattr(path[-1], "key", path[-1])) == "len"]
    stacked = np.concatenate(rows)
    assert (stacked == stacked[0]).all(), stacked
    return stacked[0]


def test_decode_launch_holds_non_decoding_rows(setup):
    """Inside the fused decode launch only decoding rows advance: a slot
    never admitted, a retired slot and a slot mid-prefill each leave the
    launch at the length it came in with, while every decoding row moves
    by one. Outputs equal each request's solo run; ``held_rows`` counts
    the rows held, summed over decode launches."""
    cfg, params = setup
    slots = 8
    engine = _engine(cfg, params, max_slots=slots, prefill_chunk=4)
    launches = []
    decode = engine._decode

    def recorded(params, feed, caches):
        decoding = frozenset(engine.scheduler.decoding)
        prefilling = {r.slot for r in engine.scheduler.prefilling}
        before = _slot_lens(caches)
        out = decode(params, feed, caches)
        launches.append((decoding, prefilling, feed[1], before,
                         _slot_lens(out[2])))
        return out

    engine._decode = recorded
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=16),
            Request(rid=1, prompt=[4, 5], max_new_tokens=16),
            Request(rid=2, prompt=[6, 7], max_new_tokens=2)]
    for r in reqs:
        engine.submit(r)
    while not reqs[2].done:
        engine.step()
    long_req = Request(rid=3, prompt=list(range(5, 25)), max_new_tokens=3)
    engine.submit(long_req)            # 5 chunks of 4: mid-prefill a while
    reqs.append(long_req)
    engine.run_until_done()
    assert all(r.done for r in reqs)

    seen = {"decoding": 0, "prefilling": 0, "retired": 0, "free": 0}
    ever_live = set()
    for decoding, prefilling, live, before, after in launches:
        assert set(np.flatnonzero(np.asarray(live))) == set(decoding)
        for s in range(slots):
            if s in decoding:
                assert after[s] == before[s] + 1, (s, before, after)
                seen["decoding"] += 1
                continue
            assert after[s] == before[s], (s, before, after)
            if s in prefilling:          # admitted, or chunks in
                seen["prefilling"] += int(before[s] > 0)
            else:
                assert before[s] == 0, (s, before)
                seen["retired" if s in ever_live else "free"] += 1
        ever_live |= decoding
    # every state a row can be in at a decode launch was exercised
    assert all(seen.values()), seen
    # a steady decoding set reuses its device mask: no upload a step
    for prev, cur in zip(launches, launches[1:]):
        if prev[0] == cur[0]:
            assert cur[2] is prev[2]

    for r in reqs:                     # each equals its solo run
        solo_engine = _engine(cfg, params, max_slots=slots, prefill_chunk=4)
        solo = Request(rid=r.rid, prompt=list(r.prompt),
                       max_new_tokens=r.max_new_tokens)
        solo_engine.submit(solo)
        solo_engine.run_until_done()
        assert r.output == solo.output
        assert r.logprobs == solo.logprobs

    held = sum(slots - len(d) for d, *_ in launches)
    assert engine.kv_stats["held_rows"] == held > 0
    metric = engine.metrics_registry()["held_rows"]
    assert metric.unit == "rows" and metric.value == held
