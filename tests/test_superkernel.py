"""Bitwise parity grid for THE paged-attention superkernel.

One kernel family (``repro.kernels.paged_attention``) now serves decode
(W=1), speculative verify (W=k+1), GQA and MLA, and all three pool
dtypes (bf16 / int8 / fp8) behind the single ``ops.paged_attention``
dispatch.  Because query rows are padded to a uniform tile, every width
lowers to the SAME compiled program — so output row ``w`` of a width-W
call must be BITWISE the width-1 decode step at position ``offs + w``.
That identity is the whole correctness story for speculative verify
(accepted tokens must be indistinguishable from tokens the engine would
have decoded one at a time), so these tests pin it exactly, across the
full (width x pool dtype x table permutation x ragged tail) grid, plus
allclose agreement with a dequantize-first oracle and the full-model
dispatch branches.  Kernel calls run in interpret mode (CPU container).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops
from repro.models import api, attention, common, paged
from repro.models.paged import PagedLayout
from repro.quant import core as qcore

K_DRAFT = 4                         # spec draft length -> verify width 5
WIDTHS = (1, 4, K_DRAFT + 1)
DTYPES = ("bf16", "int8", "fp8")


# ------------------------------------------------------------ fixtures ----

def _pools(seed, b, s, hkv, d, layout, fmt_name):
    """Paged K/V pools in the given payload dtype (+ scale pools or None),
    in the stored layout: payloads [nb, bs, hkv * d] (quantized per
    (token, head) first, then flattened), scales [nb, bs, hkv]."""
    rows_k = jax.random.normal(jax.random.key(seed), (b, s, hkv, d))
    rows_v = jax.random.normal(jax.random.key(seed + 1), (b, s, hkv, d))
    fmt = qcore.get_format(fmt_name)
    flat = lambda x: x.reshape(b, s, hkv * d)
    if fmt is None:
        pools = (paged.pool_from_rows(flat(rows_k.astype(jnp.bfloat16)),
                                      layout),
                 paged.pool_from_rows(flat(rows_v.astype(jnp.bfloat16)),
                                      layout),
                 None, None)
    else:
        qk, sk = qcore.quantize_lastdim(rows_k, fmt)
        qv, sv = qcore.quantize_lastdim(rows_v, fmt)
        pools = (paged.pool_from_rows(flat(qk), layout),
                 paged.pool_from_rows(flat(qv), layout),
                 paged.pool_from_rows(sk, layout),
                 paged.pool_from_rows(sv, layout))
    # the layout the cache stores, handed to the kernel as it is
    spec = attention.gqa_cache_spec(
        b, layout, attention.AttnConfig(num_heads=hkv, num_kv_heads=hkv,
                                        head_dim=d, kv_dtype=fmt_name),
        num_blocks=pools[0].shape[0])
    for name, pool in zip(("kpool", "vpool", "kscale", "vscale"), pools):
        if pool is not None:
            assert pool.shape == spec[name].shape, (name, pool.shape)
            assert pool.dtype == spec[name].dtype, (name, pool.dtype)
    return pools


def _permute(pools, table, seed=3):
    """Scramble pool block order (keeping null block 0) and remap the
    table so the virtual rows are unchanged."""
    nb = next(p.shape[0] for p in pools if p is not None)
    perm = np.concatenate(
        [[0], 1 + np.random.default_rng(seed).permutation(nb - 1)]
    ).astype(np.int32)
    inv = np.argsort(perm).astype(np.int32)
    pools_p = tuple(None if p is None else jnp.asarray(np.asarray(p)[inv])
                    for p in pools)
    return pools_p, jnp.asarray(perm[np.asarray(table)])


def _dequant_first_oracle(q, kpool, vpool, kscale, vscale, table, lens, offs):
    """Gather the virtual rows, dequantize in f32 FIRST, masked softmax.

    Deliberately the opposite formulation from the kernel (which folds
    scales post-dot into the compensated streams), so agreement here is
    evidence the refactor changed only the evaluation order."""
    b, w, hq, d = q.shape
    heads = lambda x: x.reshape(x.shape[:2] + (-1, d))    # gathered rows
    k = heads(qcore.cast_f32(paged.gather_blocks(kpool, table)))
    v = heads(qcore.cast_f32(paged.gather_blocks(vpool, table)))
    if kscale is not None:
        k = k * paged.gather_blocks(kscale, table)[..., None]
        v = v * paged.gather_blocks(vscale, table)[..., None]
    g = hq // k.shape[2]
    qf = q.astype(jnp.float32).reshape(b, w, -1, g, d)
    s = jnp.einsum("bwhgd,bshd->bwhgs", qf, k) * (d ** -0.5)
    kpos = jnp.arange(k.shape[1])
    lim = offs[:, None] + jnp.arange(w)[None, :]               # [B, W]
    mask = kpos[None, None, :] <= lim[:, :, None]               # [B, W, S]
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bwhgs,bshd->bwhgd", p, v).reshape(b, w, hq, -1)


# ------------------------------------------------------------ the grid ----

@pytest.mark.parametrize("fmt_name", DTYPES)
@pytest.mark.parametrize("w", WIDTHS)
def test_superkernel_parity_grid(fmt_name, w):
    """The acceptance grid: (width x pool dtype), each cell checked for
    (a) bitwise table-permutation invariance, (b) bitwise width
    invariance — row w of the wide call == the width-1 decode step at
    its position — and (c) allclose vs the dequantize-first oracle.
    Lens are ragged (mid-block tails + one full table)."""
    b, hq, hkv, d, bs, mb = 3, 4, 2, 16, 8, 4
    layout = PagedLayout(bs, mb)
    kpool, vpool, kscale, vscale = _pools(7, b, mb * bs, hkv, d, layout,
                                          fmt_name)
    table = paged.identity_table(b, layout)
    lens = jnp.asarray([w + 4, mb * bs, 2 * bs + 1], jnp.int32)
    offs = lens - w
    q = jax.random.normal(jax.random.key(3), (b, w, hq, d), jnp.float32)

    wide = ops.paged_attention(q, kpool, vpool, table, lens,
                               kscale=kscale, vscale=vscale, interpret=True)

    # (a) scrambled block table: payload AND scale blocks remap together
    (kp, vp, ksp, vsp), table_p = _permute((kpool, vpool, kscale, vscale),
                                           table)
    wide_p = ops.paged_attention(q, kp, vp, table_p, lens,
                                 kscale=ksp, vscale=vsp, interpret=True)
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(wide_p))

    # (b) width invariance, the spec-verify contract
    for j in range(w):
        narrow = ops.paged_attention(q[:, j:j + 1], kpool, vpool, table,
                                     offs + j + 1, kscale=kscale,
                                     vscale=vscale, interpret=True)
        np.testing.assert_array_equal(np.asarray(wide[:, j]),
                                      np.asarray(narrow[:, 0]))

    # (c) correctness vs the opposite-order reference
    want = _dequant_first_oracle(q, kpool, vpool, kscale, vscale, table,
                                 lens, offs)
    np.testing.assert_allclose(np.asarray(wide, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------ MLA ---------

def _latent_pools(seed, b, s, c, r, layout, fmt_name):
    rows_c = jax.random.normal(jax.random.key(seed), (b, s, c))
    rows_r = jax.random.normal(jax.random.key(seed + 1), (b, s, r))
    fmt = qcore.get_format(fmt_name)
    if fmt is None:
        return (paged.pool_from_rows(rows_c.astype(jnp.bfloat16), layout),
                paged.pool_from_rows(rows_r.astype(jnp.bfloat16), layout),
                None, None)
    qc, sc = qcore.quantize_lastdim(rows_c, fmt)
    qr, sr = qcore.quantize_lastdim(rows_r, fmt)
    return (paged.pool_from_rows(qc, layout), paged.pool_from_rows(qr, layout),
            paged.pool_from_rows(sc, layout), paged.pool_from_rows(sr, layout))


@pytest.mark.parametrize("fmt_name", DTYPES)
@pytest.mark.parametrize("w", (1, K_DRAFT + 1))
def test_superkernel_mla_parity(fmt_name, w):
    """Same grid for the MLA configuration (MQA-like: one latent stream,
    two score dots, V == the c_kv block, f32 context latents out)."""
    b, h, c, r, bs, mb = 2, 3, 16, 8, 8, 3
    layout = PagedLayout(bs, mb)
    ck, kr, cks, krs = _latent_pools(11, b, mb * bs, c, r, layout, fmt_name)
    table = paged.identity_table(b, layout)
    lens = jnp.asarray([w + 2, 2 * bs + 3], jnp.int32)
    offs = lens - w
    scale = (c + r) ** -0.5
    q_lat = jax.random.normal(jax.random.key(5), (b, w, h, c), jnp.float32)
    q_rope = jax.random.normal(jax.random.key(6), (b, w, h, r), jnp.float32)

    wide = ops.paged_attention(q_lat, ck, None, table, lens, q_rope=q_rope,
                               rope_pool=kr, kscale=cks, rope_scale=krs,
                               scale=scale, interpret=True)

    (ck_p, kr_p, cks_p, krs_p), table_p = _permute((ck, kr, cks, krs), table)
    wide_p = ops.paged_attention(q_lat, ck_p, None, table_p, lens,
                                 q_rope=q_rope, rope_pool=kr_p, kscale=cks_p,
                                 rope_scale=krs_p, scale=scale,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(wide_p))

    for j in range(w):
        narrow = ops.paged_attention(
            q_lat[:, j:j + 1], ck, None, table, offs + j + 1,
            q_rope=q_rope[:, j:j + 1], rope_pool=kr, kscale=cks,
            rope_scale=krs, scale=scale, interpret=True)
        np.testing.assert_array_equal(np.asarray(wide[:, j]),
                                      np.asarray(narrow[:, 0]))

    # dequant-first latent oracle
    ckf = qcore.cast_f32(paged.gather_blocks(ck, table))
    krf = qcore.cast_f32(paged.gather_blocks(kr, table))
    if cks is not None:
        ckf = ckf * paged.gather_blocks(cks, table)[..., None]
        krf = krf * paged.gather_blocks(krs, table)[..., None]
    s = (jnp.einsum("bwhc,bsc->bwhs", q_lat, ckf)
         + jnp.einsum("bwhr,bsr->bwhs", q_rope, krf)) * scale
    kpos = jnp.arange(ckf.shape[1])
    lim = offs[:, None] + jnp.arange(w)[None, :]
    s = jnp.where(kpos[None, None, None, :] <= lim[:, :, None, None],
                  s, -jnp.inf)
    want = jnp.einsum("bwhs,bsc->bwhc", jax.nn.softmax(s, axis=-1), ckf)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------------- model dispatch ---

@pytest.mark.parametrize("kv_dtype", DTYPES)
def test_gqa_decode_kernel_dispatch(monkeypatch, kv_dtype):
    """The TPU dispatch branch of gqa_decode (superkernel, interpret mode
    off-TPU) agrees with the pure-JAX gather branch through a full model
    decode step, for every pool dtype."""
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2,
                                                    kv_dtype=kv_dtype)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    layout = PagedLayout(16, 2)
    prompt = jnp.asarray([[5, 9, 11]], jnp.int32)
    logits, caches = jax.jit(api.prefill_fn(cfg, layout))(
        params, {"tokens": prompt})
    tok = jnp.asarray([[int(jnp.argmax(logits[0]))]], jnp.int32)

    lg_gather, _ = jax.jit(api.decode_fn(cfg))(params, tok, caches)
    monkeypatch.setattr(attention, "paged_kernel_enabled", lambda: True)
    lg_kernel, _ = jax.jit(api.decode_fn(cfg))(params, tok, caches)
    np.testing.assert_allclose(np.asarray(lg_kernel, np.float32),
                               np.asarray(lg_gather, np.float32),
                               atol=5e-2, rtol=5e-2)
    assert int(jnp.argmax(lg_kernel[0])) == int(jnp.argmax(lg_gather[0]))


@pytest.mark.parametrize("kv_dtype", DTYPES)
def test_kernel_verify_bitwise_equals_sequential_decode(monkeypatch,
                                                        kv_dtype):
    """Through the KERNEL dispatch (the TPU path, interpret off-TPU): one
    width-(k+1) verify pass over the shared paged cache returns logits
    bitwise identical to k+1 sequential decode steps, for all three pool
    dtypes — the end-to-end form of the width-invariance contract that
    makes speculative acceptance exact."""
    monkeypatch.setattr(attention, "paged_kernel_enabled", lambda: True)
    cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=2,
                                                    kv_dtype=kv_dtype)
    params = common.init_params(api.schema(cfg), jax.random.key(0))
    layout = PagedLayout(8, 6)
    prompt = [5, 9, 11, 2, 7]
    logits, caches = jax.jit(api.prefill_fn(cfg, layout))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})

    decode = jax.jit(api.decode_fn(cfg))
    tok = int(jnp.argmax(logits[0]))
    window, seq_logits, cur = [], [], caches
    for _ in range(3):
        window.append(tok)
        lg, cur = decode(params, jnp.asarray([[tok]], jnp.int32), cur)
        seq_logits.append(np.asarray(lg[0], np.float32))
        tok = int(jnp.argmax(lg[0]))

    vlg, _ = jax.jit(api.verify_fn(cfg))(
        params, jnp.asarray([window], jnp.int32), caches,
        jnp.asarray([0], jnp.int32), jnp.asarray([len(prompt)], jnp.int32))
    np.testing.assert_array_equal(np.asarray(vlg[0], np.float32),
                                  np.stack(seq_logits))
