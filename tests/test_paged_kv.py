"""Paged-KV stack tests: allocator invariants, block-layout bitwise
equivalence with the contiguous formulation, the Pallas paged-decode
kernel vs. the gather oracle, chunked-prefill vs. one-shot parity, and
full-pool admission ordering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import api, common, paged
from repro.models.attention import attend_cache
from repro.models.paged import PagedLayout
from repro.serving.engine import BlockAllocator, DecodeEngine, Request
from repro.serving.faults import AllocatorError


# ------------------------------------------------------------ allocator ----

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(num_blocks=8)            # 7 usable (block 0 reserved)
    assert a.num_free == 7
    x = a.alloc(3)
    y = a.alloc(2)
    assert len(set(x) | set(y)) == 5            # disjoint
    assert paged.NULL_BLOCK not in x + y        # null block never leaves
    assert a.num_free == 2
    a.free(x)
    assert a.num_free == 5
    z = a.alloc(4)                              # reuses freed blocks
    assert set(z) & set(x)
    assert not set(z) & set(y)


def test_allocator_exhaustion_and_double_free():
    a = BlockAllocator(num_blocks=4)
    blocks = a.alloc(3)
    # AllocatorError subclasses RuntimeError: recoverable (admission
    # catches it and lets the queue head wait) yet still a loud failure
    # for callers that don't
    with pytest.raises(AllocatorError):
        a.alloc(1)
    with pytest.raises(RuntimeError):           # back-compat contract
        a.alloc(1)
    a.free(blocks)
    with pytest.raises(AllocatorError):
        a.free(blocks)                          # double free detected


# ------------------------------------------------------------ layout -------

def test_pool_roundtrip_bitwise():
    """pool_from_rows -> gather_blocks reproduces the rows bit-for-bit:
    the paged layout is a pure re-layout, not a recompute."""
    layout = PagedLayout(8, 5)
    rows = np.random.default_rng(0).standard_normal((3, 37, 2, 4)
                                                    ).astype(np.float32)
    pool = paged.pool_from_rows(jnp.asarray(rows), layout)
    table = paged.identity_table(3, layout)
    back = np.asarray(paged.gather_blocks(pool, table))
    assert back.shape == (3, 40, 2, 4)
    assert np.array_equal(back[:, :37], rows)
    assert np.all(back[:, 37:] == 0)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_gqa_pools_stored_lane_dense(kv_dtype):
    """Every GQA pool leaf is stored [L, nb, bs, hkv * d], the layout the
    paged-attention kernel reads; quantized scale tiles stay
    [L, nb, bs, hkv]."""
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(
        num_layers=3, num_heads=4, num_kv_heads=2, head_dim=64,
        kv_dtype=kv_dtype)
    kv = api.KVCache.build(cfg, max_context=64, block_size=16, max_slots=2)
    nb, bs = kv.num_blocks, kv.layout.block_size
    shapes = {str(getattr(p[-1], "key", p[-1])): leaf.shape
              for p, leaf in jax.tree_util.tree_flatten_with_path(
                  kv.specs(2))[0]}
    assert shapes["kpool"] == shapes["vpool"] == (3, nb, bs, 2 * 64)
    if kv_dtype == "bf16":
        assert "kscale" not in shapes
    else:
        assert shapes["kscale"] == shapes["vscale"] == (3, nb, bs, 2)
    # the prefill's pools take the same layout as the spec
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    _, caches = jax.jit(api.prefill_fn(cfg, kv.layout))(
        params, {"tokens": jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)})
    for p, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        name = str(getattr(p[-1], "key", p[-1]))
        if name in paged.POOL_KEYS:
            assert leaf.shape[0] == 3 and leaf.shape[2:] == shapes[name][2:]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_lane_dense_pool_roundtrip_bitwise(kv_dtype):
    """K/V rows written to the lane-dense pools (quantized per (token,
    head), then flattened) and gathered back through the table come out
    as the padded rows bitwise: the stored layout moves no bits."""
    from repro.models import attention
    from repro.quant import core as qcore

    layout = PagedLayout(8, 5)
    b, s, hkv, d = 3, 37, 2, 16
    rows = jax.random.normal(jax.random.key(0), (2, b, s, hkv, d),
                             jnp.bfloat16)
    fmt = qcore.get_format(kv_dtype)
    pools, want = {}, []
    for name, scale, r in zip(("kpool", "vpool"), ("kscale", "vscale"),
                              rows):
        if fmt is None:
            payload = r
            want.append(r)
        else:
            payload, sc = qcore.quantize_lastdim(r, fmt)
            pools[scale] = paged.pool_from_rows(sc, layout)
            want.append(qcore.dequantize_lastdim(payload, sc, jnp.bfloat16))
        pools[name] = paged.pool_from_rows(attention._flat_heads(payload),
                                           layout)
        assert pools[name].shape == (1 + b * 5, 8, hkv * d)
    table = paged.identity_table(b, layout)
    back = attention._gather_kv(pools, table, fmt, jnp.bfloat16, hkv)
    for got, w in zip(back, want):
        assert got.shape == (b, 40, hkv, d)
        assert np.array_equal(np.asarray(got[:, :s], np.float32),
                              np.asarray(w, np.float32))
        assert np.all(np.asarray(got[:, s:], np.float32) == 0)


def test_scatter_token_and_chunk():
    layout = PagedLayout(4, 3)
    pool = jnp.zeros((1 + 2 * 3, 4, 2), jnp.float32)
    table = paged.identity_table(2, layout)
    lens = jnp.asarray([5, 2], jnp.int32)
    vals = jnp.asarray([[1.0, 1.0], [2.0, 2.0]])
    pool = paged.scatter_token(pool, table, lens, vals)
    virt = np.asarray(paged.gather_blocks(pool, table))
    assert np.all(virt[0, 5] == 1.0) and np.all(virt[1, 2] == 2.0)
    assert np.count_nonzero(virt) == 4

    chunk = jnp.arange(1, 7, dtype=jnp.float32).reshape(3, 2)
    pool = paged.scatter_chunk(pool, table[0], jnp.int32(6), chunk)
    virt = np.asarray(paged.gather_blocks(pool, table))
    assert np.array_equal(virt[0, 6:9], np.asarray(chunk))   # crosses blocks


def test_scatter_chunk_multi_matches_sequential():
    """The speculative verify's one-launch multi-slot scatter is bitwise
    the per-slot scatter_chunk loop — including duplicated rows (the
    fixed-shape padding), whose identical values resolve deterministically."""
    layout = PagedLayout(4, 3)
    rng = np.random.default_rng(1)
    pool0 = jnp.asarray(rng.standard_normal((1 + 2 * 3, 4, 2)),
                        jnp.float32)
    table = paged.identity_table(2, layout)
    pos0s = jnp.asarray([5, 2], jnp.int32)
    vals = jnp.asarray(rng.standard_normal((2, 3, 2)), jnp.float32)

    seq = pool0
    for i in range(2):
        seq = paged.scatter_chunk(seq, table[i], pos0s[i], vals[i])
    multi = paged.scatter_chunk_multi(pool0, table, pos0s, vals)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(multi))

    # duplicate rows (padding) write the same values twice — same result
    dup = paged.scatter_chunk_multi(
        pool0, jnp.concatenate([table, table[:1]]),
        jnp.concatenate([pos0s, pos0s[:1]]),
        jnp.concatenate([vals, vals[:1]]))
    np.testing.assert_array_equal(np.asarray(multi), np.asarray(dup))

    # positions past the table clip into its last entry; pointing that at
    # the null block absorbs the overflow (spec windows near capacity)
    null_table = jnp.asarray([[1, paged.NULL_BLOCK, paged.NULL_BLOCK]],
                             jnp.int32)
    over = paged.scatter_chunk_multi(pool0, null_table,
                                     jnp.asarray([3], jnp.int32), vals[:1])
    np.testing.assert_array_equal(np.asarray(over)[2:],
                                  np.asarray(pool0)[2:])


def test_set_lens_touches_only_len():
    """Rollback is surgical: ``set_lens`` rewrites the named slots' len
    entries and nothing else in the cache tree."""
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2)
    kv = api.KVCache.build(cfg, max_context=64, block_size=16, max_slots=3)
    caches = jax.tree.map(
        lambda x: x + 1 if x.dtype == jnp.int32 else x + 0.5, kv.init(3))
    rolled = paged.set_lens(caches, jnp.asarray([0, 2], jnp.int32),
                            jnp.asarray([7, 4], jnp.int32))
    from jax.tree_util import tree_flatten_with_path
    flat_a = tree_flatten_with_path(caches)[0]
    flat_b = tree_flatten_with_path(rolled)[0]
    for (path, a), (_, b) in zip(flat_a, flat_b):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "len":
            assert np.all(np.asarray(b)[:, [0, 2]] == [7, 4])
            np.testing.assert_array_equal(np.asarray(a)[:, 1],
                                          np.asarray(b)[:, 1])
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_attend_equals_contiguous_bitwise():
    """Attention over block-gathered K/V equals attention over the
    contiguous rows bitwise — the acceptance bar for replacing the
    contiguous decode path."""
    key = jax.random.key(0)
    b, s, hq, hkv, d = 3, 48, 4, 2, 16
    layout = PagedLayout(8, 6)
    rows_k = jax.random.normal(key, (b, s, hkv, d), jnp.float32)
    rows_v = jax.random.normal(jax.random.key(1), (b, s, hkv, d), jnp.float32)
    q = jax.random.normal(jax.random.key(2), (b, 1, hq, d), jnp.float32)
    lens = jnp.asarray([5, 48, 17], jnp.int32)

    contiguous = attend_cache(q, rows_k, rows_v, lens)

    pool_k = paged.pool_from_rows(rows_k, layout)
    pool_v = paged.pool_from_rows(rows_v, layout)
    table = paged.identity_table(b, layout)
    gk = paged.gather_blocks(pool_k, table)
    gv = paged.gather_blocks(pool_v, table)
    paged_out = attend_cache(q, gk, gv, lens)
    assert np.array_equal(np.asarray(contiguous), np.asarray(paged_out))


# ------------------------------------------------------ chunked prefill ----

def _chunked_prefill(cfg, params, prompt, chunk_size, layout):
    kv = api.KVCache.build(cfg, max_context=layout.max_context,
                           block_size=layout.block_size, max_slots=1)
    caches = kv.init(1)
    row = jnp.arange(1, 1 + layout.max_blocks, dtype=jnp.int32)
    caches = jax.jit(paged.reset_slot)(caches, jnp.int32(0), row)
    chunk_fn = jax.jit(api.prefill_chunk_fn(cfg))
    pos = 0
    while pos < len(prompt):
        chunk = prompt[pos:pos + chunk_size]
        logits, caches = chunk_fn(params, jnp.asarray([chunk], jnp.int32),
                                  caches, jnp.int32(0), jnp.int32(pos))
        pos += len(chunk)
    return logits, caches


@pytest.mark.parametrize("arch,chunk", [("qwen1.5-0.5b", 4),
                                        ("qwen1.5-0.5b", 5),
                                        ("mamba2-780m", 4)])
def test_chunked_prefill_equals_one_shot(arch, chunk):
    """Prefilling a prompt chunk-by-chunk (ragged final chunk included)
    yields the same last-position logits and greedy continuation as the
    one-shot prefill."""
    cfg = reduced(get_config(arch))
    if cfg.family in ("dense", "moe", "vlm"):
        cfg = cfg.with_(num_layers=2)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    layout = PagedLayout(16, 4)
    prompt = list(range(2, 15))                       # 13 tokens

    logits_one, caches_one = jax.jit(api.prefill_fn(cfg, layout))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    logits_chunked, caches_chunked = _chunked_prefill(cfg, params, prompt,
                                                      chunk, layout)
    np.testing.assert_allclose(np.asarray(logits_chunked, np.float32),
                               np.asarray(logits_one, np.float32),
                               atol=1e-4, rtol=1e-4)
    assert int(jnp.argmax(logits_chunked[0])) == int(jnp.argmax(logits_one[0]))

    # greedy continuation agrees token-for-token
    decode = jax.jit(api.decode_fn(cfg))
    tok_a = tok_b = int(jnp.argmax(logits_one[0]))
    for _ in range(4):
        la, caches_one = decode(params, jnp.asarray([[tok_a]], jnp.int32),
                                caches_one)
        lb, caches_chunked = decode(params, jnp.asarray([[tok_b]], jnp.int32),
                                    caches_chunked)
        tok_a, tok_b = int(jnp.argmax(la[0])), int(jnp.argmax(lb[0]))
        assert tok_a == tok_b


def test_paged_decode_prefix_consistency():
    """Paged decode continues the teacher-forced forward: logits for
    position L from (prefill L-1, decode 1) match the full forward."""
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    toks = np.random.default_rng(0).integers(1, 250, 12).tolist()
    layout = PagedLayout(16, 2)

    full, _ = jax.jit(api.forward_fn(cfg))(
        params, {"tokens": jnp.asarray([toks], jnp.int32)})
    _, caches = jax.jit(api.prefill_fn(cfg, layout))(
        params, {"tokens": jnp.asarray([toks[:-1]], jnp.int32)})
    step, _ = jax.jit(api.decode_fn(cfg))(
        params, jnp.asarray([[toks[-1]]], jnp.int32), caches)
    np.testing.assert_allclose(np.asarray(step[0], np.float32),
                               np.asarray(full[0, -1], np.float32),
                               atol=0.05, rtol=0.05)


# ------------------------------------------------------------ admission ----

@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    return cfg, params


def test_admission_fifo_order(tiny):
    """With one slot, requests complete strictly in submission order."""
    cfg, params = tiny
    engine = DecodeEngine(cfg, params, max_slots=1, max_context=64,
                          block_size=16)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new_tokens=3)
            for i in range(4)]
    for r in reqs:
        engine.submit(r)
    completion = []
    for _ in range(200):
        if not engine.num_unfinished:
            break
        engine.step()
        for r in reqs:
            if r.done and r.rid not in completion:
                completion.append(r.rid)
    assert completion == [0, 1, 2, 3]


def test_block_pool_gates_admission(tiny):
    """An oversubscribed pool (3 slots, blocks for ~1 request) serializes
    admission on block availability; everyone still completes and block 0
    is never handed out."""
    cfg, params = tiny
    engine = DecodeEngine(cfg, params, max_slots=3, max_context=64,
                          block_size=16, num_blocks=4)   # 3 usable blocks
    reqs = [Request(rid=i, prompt=list(range(1, 21)), max_new_tokens=6)
            for i in range(3)]                           # 2 blocks each
    for r in reqs:
        engine.submit(r)
    peak = 0
    seen_blocks = set()
    for _ in range(400):
        if not engine.num_unfinished:
            break
        engine.step()
        active = engine.num_active + len(engine.scheduler.prefilling)
        peak = max(peak, active)
        for r in reqs:
            seen_blocks.update(r.blocks)
    assert all(r.done for r in reqs)
    assert peak == 1                    # pool admitted one request at a time
    assert paged.NULL_BLOCK not in seen_blocks
    assert engine.scheduler.allocator.num_free == 3   # everything returned


def test_engine_rejects_only_oversize(tiny):
    cfg, params = tiny
    engine = DecodeEngine(cfg, params, max_slots=2, max_context=64)
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0, prompt=[1] * 60, max_new_tokens=10))
    ok = Request(rid=1, prompt=[1] * 30, max_new_tokens=10)
    engine.submit(ok)
    engine.run_until_done()
    assert ok.done
