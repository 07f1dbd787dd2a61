"""The plain f32 reference against the serving engine's logits: chunked
prefill into the paged pools, then decode through the cache, on a
reduced Qwen2-shaped and a reduced StableLM-shaped model (CPU)."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, weights  # noqa: E402
from bench import run as runmod  # noqa: E402
from bench.reference import dense  # noqa: E402

HERE = Path(__file__).resolve().parent
# The engine computes in bf16 (activations, matmul inputs, the paged
# pools) with f32 accumulation and f32 logits; over 2 layers its logits
# differ from the f32 reference by well under 1% of their norm. An
# unrelated result (a wrong head, mask or position) differs by ~100%.
REL_TOL = 0.03


def engine_logits(config, seed, prompt, max_new):
    """Serve one request; return its tokens and every logit row the
    engine produced for it (last prefill chunk, then each decode step)."""
    import jax.numpy as jnp
    from repro.serving.engine import DecodeEngine, Request
    cfg, m = runmod.program_config(config)
    geo = config["engine"]
    eng = DecodeEngine(cfg, weights.make_params(m, seed),
                       max_slots=geo["max_slots"],
                       max_context=geo["max_context"],
                       block_size=geo["block_size"],
                       prefill_chunk=geo["prefill_chunk"])
    rows = []
    prefill, decode = eng._prefill_chunk, eng._decode

    def prefill_rec(*a):
        logits, caches = prefill(*a)
        rows.append(("prefill", np.asarray(logits[0], np.float64)))
        return logits, caches

    def decode_rec(*a):
        r, packed, caches = decode(*a)
        rows.append(("decode", np.asarray(r, np.float64)))
        return r, packed, caches

    eng._prefill_chunk, eng._decode = prefill_rec, decode_rec
    req = Request(rid=0, prompt=list(prompt), max_new_tokens=max_new)
    eng.submit(req)
    eng.run_until_done()
    last_prefill = [r for k, r in rows if k == "prefill"][-1]
    decoded = [r[req.slot if req.slot is not None else 0]
               for k, r in rows if k == "decode"]
    return req, m, np.stack([last_prefill] + decoded[:max_new - 1])


@pytest.mark.parametrize("name", ["tiny_dense", "tiny_stablelm"])
def test_reference_matches_engine_prefill_then_cached_decode(name):
    import jax
    config = spec.load_json(HERE / f"{name}.json")
    seed = 2**32 + 17
    prompt = np.random.default_rng(0).integers(
        0, config["vocab_size"], 21).tolist()
    req, m, got = engine_logits(config, seed, prompt, 6)
    assert len(req.output) == 6
    params = weights.make_params(m, seed)
    seq = np.asarray(prompt + req.output[:-1], np.int32)
    h = dense.hidden(params, seq, m)
    rows = np.arange(len(prompt) - 1, len(seq))
    want = np.asarray(dense.logits_at(params, h, jax.numpy.asarray(rows), m,
                                      "f32"), np.float64)
    assert got.shape == want.shape == (6, m.vocab)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert np.all(rel < REL_TOL), rel
    # the tokens the engine served are its own greedy choices
    assert [int(np.argmax(r)) for r in got] == req.output


def test_round_mantissa_keeps_the_requested_bits():
    import jax.numpy as jnp
    x = jnp.asarray([1.0, 1.0625, 1.1, 3.0, -2.7, 1e-3], jnp.float32)
    r = np.asarray(dense.round_mantissa(x, 3))
    # 3 mantissa bits: steps of 1/8 between 1 and 2
    assert r[0] == 1.0 and r[1] == 1.0 and r[2] == 1.125
    assert r[3] == 3.0 and r[4] == -2.75
    assert abs(r[5] - 1e-3) / 1e-3 <= 2 ** -4
    # 7 bits is bfloat16's rounding
    assert np.array_equal(np.asarray(dense.round_mantissa(x, 7)),
                          np.asarray(x.astype(jnp.bfloat16)
                                     .astype(jnp.float32)))
