"""Attention: chunked-flash GQA (training/prefill), paged decode, and MLA.

Memory-efficient attention is mandatory here: the assigned shape cells go up
to 32k prefill, and materializing [B, H, L, L] scores is impossible at those
sizes. The flash implementation is a pure-JAX blockwise online-softmax
(scan over KV chunks inside a map over Q chunks) — the TPU-idiomatic
formulation that XLA fuses well and that bounds live memory to one
(q_chunk × kv_chunk) tile per (batch, head).

The online-softmax accumulator is itself a long accumulation chain; the
``kahan_acc`` flag switches it to compensated (Neumaier) accumulation —
the paper's technique applied inside attention (off by default; validated in
tests/test_models_attention.py).

Decode caches are block-paged (see ``repro.models.paged``): K/V live in a
shared block pool indexed through per-sequence block tables, so a sequence
only occupies (and the decode gather only touches) the blocks its actual
length needs. Each GQA pool is stored lane-dense, [nb, bs, hkv * d], the
layout the superkernel reads; writers flatten the new rows' heads and
readers split the heads of gathered rows only. ``flash_attention`` takes
a dynamic ``q_offset`` so chunked prefill can extend a paged cache
incrementally — queries at absolute positions ``q_offset..q_offset+C-1``
against the gathered prefix+chunk.
The serving decode dispatches per backend (``paged_kernel_enabled``): the
Pallas block-table kernel ``repro.kernels.paged_attention`` on TPU; off
TPU, decode and verify run the chunked-prefill formulation itself
(gather + dequantize, then ``flash_attention`` at width 1/C) — chunk
splits are bitwise invariant, so prefill, decode and verify share ONE
set of numerics and a decode-written KV block is bit-identical to the
prefill-written block a cold run would produce. Session-KV reuse of
generated tokens (``repro.serving.prefix_cache``) depends on exactly
that equality.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kahan
from repro.models import common, paged
from repro.models.common import ParamSpec
from repro.models.paged import PagedLayout
from repro.quant import core as qcore

Array = jax.Array

NEG_INF = -1e30


def _shard_blhd(x: Array) -> Array:
    """Constrain [B, L, H, D] activations: batch over (pod, data), heads
    over model. Verified against the dry-run: without this, GSPMD drops the
    head sharding across the flash-attention reshapes and every chip
    computes all heads."""
    from repro.distributed.sharding import shard_act
    return shard_act(x, "act_batch", "act_seq", "act_heads", None)


class AttnConfig(NamedTuple):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rotary_fraction: float = 1.0
    q_chunk: int = 512
    kv_chunk: int = 512
    kahan_acc: bool = False
    causal: bool = True
    # §Perf knob: triangular block packing — compute only the nq(nq+1)/2
    # valid (q,kv) block pairs of a causal mask instead of all nq·nk
    causal_packing: bool = False
    # low-bit KV pools (repro.quant): "bf16" | "int8" | "fp8". Quantized
    # pools carry per-(block, token-row, head) scale tiles ("kscale" /
    # "vscale") addressed through the SAME block table as the data.
    kv_dtype: str = "bf16"


def gqa_schema(d_model: int, cfg: AttnConfig) -> dict:
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d_model, h * dh), ("embed", "q_heads"), init="fan_in"),
        "wk": ParamSpec((d_model, kv * dh), ("embed", "kv_heads"), init="fan_in"),
        "wv": ParamSpec((d_model, kv * dh), ("embed", "kv_heads"), init="fan_in"),
        "wo": ParamSpec((h * dh, d_model), ("q_heads", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((h * dh,), ("q_heads",), init="zeros")
        s["bk"] = ParamSpec((kv * dh,), ("kv_heads",), init="zeros")
        s["bv"] = ParamSpec((kv * dh,), ("kv_heads",), init="zeros")
    return s


def _project_qkv(p: dict, x: Array, cfg: AttnConfig, positions: Array
                 ) -> tuple[Array, Array, Array]:
    b, l, _ = x.shape
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = common.dense(x, p["wq"], p.get("bq")).reshape(b, l, h, dh)
    k = common.dense(x, p["wk"], p.get("bk")).reshape(b, l, kv, dh)
    v = common.dense(x, p["wv"], p.get("bv")).reshape(b, l, kv, dh)
    rd = int(dh * cfg.rotary_fraction)
    if rd:
        q = common.apply_rope(q.swapaxes(1, 2), positions[:, None, :],
                              theta=cfg.rope_theta, rotary_dim=rd).swapaxes(1, 2)
        k = common.apply_rope(k.swapaxes(1, 2), positions[:, None, :],
                              theta=cfg.rope_theta, rotary_dim=rd).swapaxes(1, 2)
    return _shard_blhd(q), _shard_blhd(k), _shard_blhd(v)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    kahan_acc: bool = False, kv_len: Array | None = None,
                    causal_packing: bool = False,
                    q_offset: Array | int = 0) -> Array:
    """Blockwise attention. q: [B, Lq, Hq, D]; k/v: [B, Lk, Hkv, Dv].

    Returns [B, Lq, Hq, Dv]. GQA handled by grouping q heads over kv heads.
    ``q_offset`` places the queries at absolute positions offset..offset+Lq-1
    for the causal mask (chunked prefill against an already-cached prefix).
    Both ``q_offset`` and ``kv_len`` accept a per-batch [B] array — the
    multi-slot speculative verify runs every slot's draft window in one
    launch, each at its own cache offset. Scalars reproduce the original
    mask bitwise (the array path only widens the mask's broadcast shape).
    """
    b, lq_orig, hq, d = q.shape
    _, lk_orig, hkv, dv = v.shape
    if hkv < hq:
        # GQA under tensor parallelism: repeat KV heads up to the q-head
        # count so the head dim shards cleanly over the model axis (each TP
        # rank holds its q-heads' KV copy — Megatron-style). Decode keeps
        # the compact kv-head cache; this affects train/prefill only.
        groups = hq // hkv
        k = _shard_blhd(jnp.repeat(k, groups, axis=2))
        v = _shard_blhd(jnp.repeat(v, groups, axis=2))
        hkv = hq
    groups = hq // hkv
    scale = d ** -0.5

    qc = min(q_chunk, lq_orig)
    kc = min(kv_chunk, lk_orig)
    # pad to chunk multiples; padded KV positions are masked via kv_len,
    # padded Q rows are sliced off the output.
    pad_q = (-lq_orig) % qc
    pad_k = (-lk_orig) % kc
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = lk_orig
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    lq, lk = lq_orig + pad_q, lk_orig + pad_k

    from repro.distributed.sharding import shard_act
    # [B, Hkv, G, Lq, D] / [B, Hkv, Lk, D]
    qg = q.reshape(b, lq, hkv, groups, d).transpose(0, 2, 3, 1, 4)
    qg = shard_act(qg, "act_batch", "act_heads", None, "act_seq", None)
    kt = shard_act(k.transpose(0, 2, 1, 3),
                   "act_batch", "act_heads", "act_seq", None)
    vt = shard_act(v.transpose(0, 2, 1, 3),
                   "act_batch", "act_heads", "act_seq", None)

    nq, nk = lq // qc, lk // kc
    qg = qg.reshape(b, hkv, groups, nq, qc, d)

    static_zero_offset = isinstance(q_offset, int) and q_offset == 0
    if causal and causal_packing and lq == lk and nq == nk \
            and kv_len is None and not kahan_acc and static_zero_offset:
        packed = jax.checkpoint(
            functools.partial(_flash_causal_packed, qc=qc, kc=kc, scale=scale),
            policy=jax.checkpoint_policies.nothing_saveable)
        out = packed(qg, kt, vt)
        out = out.reshape(b, hq, lq, dv).transpose(0, 2, 1, 3).astype(v.dtype)
        return out[:, :lq_orig] if pad_q else out

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def per_q_chunk(qi):
        # checkpointed: the kv scan's backward would otherwise stash the
        # [nk, B, H, qc, kc] probability blocks (flash attention's memory
        # win gone, ~1 GB/layer at 4k); recompute them instead.
        q_blk = qg[:, :, :, qi]                       # [B,Hkv,G,qc,D]
        # [qc] for a scalar offset, [B, qc] for the per-slot verify path
        q_pos = (jnp.asarray(q_offset)[..., None] + qi * qc
                 + jnp.arange(qc))

        def kv_step(carry, ki):
            m, l, acc, acc_c = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kt, ki * kc, kc, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(vt, ki * kc, kc, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            k_pos = ki * kc + jnp.arange(kc)
            mask = jnp.ones(q_pos.shape[:-1] + (qc, kc), dtype=bool)
            if causal:
                mask &= q_pos[..., None] >= k_pos
            if kv_len is not None:
                mask &= k_pos < jnp.asarray(kv_len)[..., None, None]
            # [qc,kc] broadcasts over [B,H,G,qc,kc]; a per-batch [B,qc,kc]
            # mask needs the head/group axes inserted
            mb_ = mask if mask.ndim == 2 else mask[:, None, None]
            s = jnp.where(mb_, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mb_
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            if kahan_acc:
                acc_s, acc_cc = kahan.neumaier_step(
                    acc * corr[..., None], acc_c * corr[..., None], pv)
                return (m_new, l_new, acc_s, acc_cc), None
            return (m_new, l_new, acc * corr[..., None] + pv, acc_c), None

        m0 = jnp.full((b, hkv, groups, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, groups, qc), jnp.float32)
        a0 = jnp.zeros((b, hkv, groups, qc, dv), jnp.float32)
        (m, l, acc, acc_c), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, a0), jnp.arange(nk))
        if kahan_acc:
            acc = acc + acc_c
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                     # [B,Hkv,G,qc,Dv]

    out = jax.lax.map(per_q_chunk, jnp.arange(nq))     # [nq,B,Hkv,G,qc,Dv]
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, groups, lq, dv)
    out = out.reshape(b, hq, lq, dv).transpose(0, 2, 1, 3).astype(v.dtype)
    return out[:, :lq_orig] if pad_q else out


def _flash_causal_packed(qg: Array, kt: Array, vt: Array, *, qc: int,
                         kc: int, scale: float) -> Array:
    """Triangular-packed causal flash: one scan over the nq(nq+1)/2 valid
    (q-block, kv-block) pairs in row-major order — the online-softmax state
    resets at each row start and the row output is emitted at the diagonal.
    Halves attention FLOPs and score traffic vs. the masked full grid
    (§Perf hypothesis H1; measured in EXPERIMENTS.md)."""
    b, hkv, groups, nq, _, d = qg.shape
    dv = vt.shape[-1]

    pairs_q = jnp.concatenate(
        [jnp.full((i + 1,), i, jnp.int32) for i in range(nq)])
    pairs_k = jnp.concatenate(
        [jnp.arange(i + 1, dtype=jnp.int32) for i in range(nq)])

    m0 = jnp.full((b, hkv, groups, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, groups, qc), jnp.float32)
    a0 = jnp.zeros((b, hkv, groups, qc, dv), jnp.float32)
    outs0 = jnp.zeros((nq, b, hkv, groups, qc, dv), jnp.float32)

    def step(carry, pair):
        qi, ki = pair
        m, l, acc, outs = carry
        row_start = ki == 0
        m = jnp.where(row_start, NEG_INF, m)
        l = jnp.where(row_start, 0.0, l)
        acc = jnp.where(row_start, 0.0, acc)

        q_blk = jax.lax.dynamic_index_in_dim(qg, qi, axis=3, keepdims=False)
        k_blk = jax.lax.dynamic_slice_in_dim(kt, ki * kc, kc, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(vt, ki * kc, kc, axis=2)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        # only the diagonal block needs a mask
        diag = qi == ki
        tri = jnp.arange(qc)[:, None] >= jnp.arange(kc)[None, :]
        mask = tri | (~diag)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        finished = (acc / jnp.maximum(l, 1e-30)[..., None])
        outs = jax.lax.cond(
            diag,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, finished, qi, axis=0),
            lambda o: o, outs)
        return (m_new, l, acc, outs), None

    (_, _, _, outs), _ = jax.lax.scan(step, (m0, l0, a0, outs0),
                                      (pairs_q, pairs_k))
    # [nq,B,Hkv,G,qc,Dv] -> [B,Hkv,G,Lq,Dv]
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(
        b, hkv, groups, nq * qc, dv)
    return out


def attend_cache(q: Array, k: Array, v: Array, valid_len: Array, *,
                 kscale: Array | None = None, vscale: Array | None = None,
                 out_dtype=None) -> Array:
    """Single-token attention against materialized K/V rows.

    q: [B, 1, Hq, D]; k/v: [B, S, Hkv, D]; valid_len: [B] valid lengths
    (the new token's K/V must already be written at valid_len-1). Used on
    block-gathered paged rows and on encoder cross-attention memory.

    Quantized rows pass RAW payloads plus per-(token, head) ``kscale`` /
    ``vscale`` [B, S, Hkv]: the scales are folded post-dot into the
    [B, Hkv, G, S] score tile and post-softmax into p — the hoisted-scale
    formulation of the superkernel (head_dim× less dequant arithmetic
    than materializing dequantized rows, and fp8 widens via the cheap
    ``cast_f32`` bit reinterpretation). The bf16 path (kscale None) is
    bitwise the historical implementation.
    """
    b, _, hq, d = q.shape
    _, s_max, hkv, dv = v.shape
    groups = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, hkv, groups, d)
    kf = qcore.cast_f32(k) if kscale is not None else k.astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32), kf) * scale
    if kscale is not None:
        s = s * kscale.transpose(0, 2, 1)[:, :, None, :]       # [B,Hkv,1,S]
    mask = jnp.arange(s_max)[None, :] < valid_len[:, None]     # [B,S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if vscale is None:
        out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, 1, hq, dv).astype(out_dtype or v.dtype)
    pv = p * vscale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhgs,bshd->bhgd", pv, qcore.cast_f32(v),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, dv).astype(out_dtype or jnp.float32)


def attend_cache_multi(q: Array, k: Array, v: Array, q_pos: Array, *,
                       kscale: Array | None = None,
                       vscale: Array | None = None, out_dtype=None) -> Array:
    """Multi-query attention against materialized K/V rows.

    q: [B, C, Hq, D]; k/v: [B, S, Hkv, D]; q_pos: [B, C] absolute positions
    (query j attends keys at positions <= q_pos[b, j], which must already
    be written). This is ``attend_cache`` widened to C queries with the
    same score/softmax structure (including the hoisted-scale quantized
    fold) — the CPU-side speculative verify uses it so that a verify row
    reproduces the decode step's numerics: C == 1 with
    q_pos == valid_len - 1 is exactly the decode formulation.
    """
    b, c, hq, d = q.shape
    _, s_max, hkv, dv = v.shape
    groups = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, c, hkv, groups, d)
    kf = qcore.cast_f32(k) if kscale is not None else k.astype(jnp.float32)
    s = jnp.einsum("bchgd,bshd->bchgs", qg.astype(jnp.float32), kf) * scale
    if kscale is not None:
        s = s * kscale.transpose(0, 2, 1)[:, None, :, None, :]
    k_pos = jnp.arange(s_max)
    mask = q_pos[:, :, None] >= k_pos[None, None, :]           # [B,C,S]
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if vscale is None:
        out = jnp.einsum("bchgs,bshd->bchgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, c, hq, dv).astype(out_dtype or v.dtype)
    pv = p * vscale.transpose(0, 2, 1)[:, None, :, None, :]
    out = jnp.einsum("bchgs,bshd->bchgd", pv, qcore.cast_f32(v),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, hq, dv).astype(out_dtype or jnp.float32)


def gqa_forward(p: dict, x: Array, cfg: AttnConfig, *,
                positions: Array | None = None) -> Array:
    """Full-sequence (train / prefill) GQA block. x: [B, L, d]."""
    b, l, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = flash_attention(q, k, v, causal=cfg.causal, q_chunk=cfg.q_chunk,
                          kv_chunk=cfg.kv_chunk, kahan_acc=cfg.kahan_acc,
                          causal_packing=cfg.causal_packing)
    return common.dense(out.reshape(b, l, -1), p["wo"])


def gqa_prefill(p: dict, x: Array, cfg: AttnConfig, layout: PagedLayout
                ) -> tuple[Array, dict]:
    """One-shot prefill: forward + emit a block-paged KV cache.

    The computed K/V rows are re-laid-out into a per-batch identity-table
    pool in the stored lane-dense layout [nb, bs, hkv * d] (a pure reshape —
    the later block gather reproduces them bitwise).
    Under a quantized ``kv_dtype`` the rows are quantized per (token, head)
    first and the prefill attention runs over the *dequantized* values —
    the cache IS the quantized data, so every consumer (this prefill, later
    chunks, decode) sees exactly the same K/V and the only divergence from
    the bf16 path is the quantization rounding itself.
    """
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    q, k, v = _project_qkv(p, x, cfg, positions)
    fmt = qcore.get_format(cfg.kv_dtype)
    scale_pools = {}
    if fmt is None:
        k_store, v_store = k, v
    else:
        k_store, sk = qcore.quantize_lastdim(k, fmt)
        v_store, sv = qcore.quantize_lastdim(v, fmt)
        k = qcore.dequantize_lastdim(k_store, sk, x.dtype)
        v = qcore.dequantize_lastdim(v_store, sv, x.dtype)
        scale_pools = {"kscale": paged.pool_from_rows(sk, layout),
                       "vscale": paged.pool_from_rows(sv, layout)}
    out = flash_attention(q, k, v, causal=cfg.causal, q_chunk=cfg.q_chunk,
                          kv_chunk=cfg.kv_chunk, kahan_acc=cfg.kahan_acc,
                          causal_packing=cfg.causal_packing)
    cache = {"kpool": paged.pool_from_rows(_flat_heads(k_store), layout),
             "vpool": paged.pool_from_rows(_flat_heads(v_store), layout),
             "block_table": paged.identity_table(b, layout),
             "len": jnp.full((b,), l, jnp.int32), **scale_pools}
    return common.dense(out.reshape(b, l, -1), p["wo"]), cache


def _flat_heads(x: Array) -> Array:
    """[..., hkv, d] -> [..., hkv * d]: K/V rows in the pools' stored
    lane-dense layout."""
    return x.reshape(x.shape[:-2] + (-1,))


def _scatter_kv(cache: dict, k: Array, v: Array,
                fmt: qcore.QuantFormat | None, scatter_fn) -> dict:
    """Append K/V payloads [..., hkv, d] (and, when quantized, their
    per-(token, head) scale tiles) through ``scatter_fn(pool, vals)`` — the
    ONE place the quantize-on-write happens for both the token and chunk
    append paths. Rows are quantized per (token, head) first, then
    flattened to the pools' [..., hkv * d] layout."""
    if fmt is None:
        return {"kpool": scatter_fn(cache["kpool"], _flat_heads(k)),
                "vpool": scatter_fn(cache["vpool"], _flat_heads(v))}
    qk, sk = qcore.quantize_lastdim(k, fmt)
    qv, sv = qcore.quantize_lastdim(v, fmt)
    return {"kpool": scatter_fn(cache["kpool"], _flat_heads(qk)),
            "vpool": scatter_fn(cache["vpool"], _flat_heads(qv)),
            "kscale": scatter_fn(cache["kscale"], sk),
            "vscale": scatter_fn(cache["vscale"], sv)}


def _gather_kv(pools: dict, table: Array, fmt: qcore.QuantFormat | None,
               dtype, hkv: int) -> tuple[Array, Array]:
    """Materialize virtual K/V rows [B, mb*bs, hkv, d] from the pools —
    dequantizing to ``dtype`` when the pools are quantized (every reader
    sees exactly what the cache stores). Only the gathered rows are
    reshaped to split the heads, never the pool."""
    def rows(name):
        g = paged.gather_blocks(pools[name], table)    # [B, mb*bs, hkv*d]
        return g.reshape(g.shape[:-1] + (hkv, -1))
    k, v = rows("kpool"), rows("vpool")
    if fmt is None:
        return k, v
    return (qcore.dequantize_lastdim(
                k, paged.gather_blocks(pools["kscale"], table), dtype),
            qcore.dequantize_lastdim(
                v, paged.gather_blocks(pools["vscale"], table), dtype))


def paged_kernel_enabled() -> bool:
    """Dispatch policy for the serving decode: the Pallas block-table
    kernel on TPU (it moves exactly the table's blocks — the traffic the
    engine's kv_stats counts), the pure-JAX gather formulation elsewhere
    (interpret-mode Pallas inside the scanned decode would crawl on CPU).
    Evaluated at trace time; tests exercise the kernel branch by
    monkeypatching (interpret mode picks up automatically off-TPU)."""
    return jax.default_backend() == "tpu"


def gqa_decode(p: dict, x: Array, cfg: AttnConfig, cache: dict
               ) -> tuple[Array, dict]:
    """One-token paged decode. x: [B, 1, d]; cache: paged (pool + table).

    Quantized pools (``cfg.kv_dtype``) scatter the new token's quantized
    K/V plus its per-head scales. TPU dispatches to the paged-attention
    superkernel (``ops.paged_attention``, width 1 — scales folded post-dot
    into the compensated streams); elsewhere the step runs the CHUNKED
    PREFILL formulation at width 1 — gather + dequantize the virtual rows,
    then ``flash_attention`` with a per-slot ``q_offset``. Prefill chunking
    is bitwise invariant to the chunk split, so a decode step writes K/V
    (and emits logits) bit-identical to prefilling the same token at the
    same position: the session-KV tier can re-serve decode-written blocks
    to a later prompt and stay bitwise a cold full-history prefill
    (tests/test_prefix_cache.py three-turn parity).
    """
    b, _, _ = x.shape
    idx = cache["len"]                                 # [B]
    table = cache["block_table"]
    positions = idx[:, None]                           # next position
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    fmt = qcore.get_format(cfg.kv_dtype)
    pools = _scatter_kv(
        cache, k_new[:, 0], v_new[:, 0], fmt,
        lambda pool, vals: paged.scatter_token(pool, table, idx, vals))
    if paged_kernel_enabled():
        from repro.kernels import ops
        out = ops.paged_attention(
            q, pools["kpool"], pools["vpool"], table, idx + 1,
            kscale=pools.get("kscale"),
            vscale=pools.get("vscale")).astype(x.dtype)
    else:
        k, v = _gather_kv(pools, table, fmt, x.dtype,
                          cfg.num_kv_heads)            # [B, mb*bs, H, D]
        out = flash_attention(q, k, v, causal=cfg.causal,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              kahan_acc=cfg.kahan_acc,
                              q_offset=idx, kv_len=idx + 1)
    new_cache = {**pools, "block_table": table, "len": idx + 1}
    return common.dense(out.reshape(b, 1, -1), p["wo"]), new_cache


def gqa_prefill_chunk(p: dict, x: Array, cfg: AttnConfig, cache: dict,
                      slot, pos0) -> tuple[Array, dict]:
    """Prefill one chunk of ONE sequence into the shared paged cache.

    x: [1, C, d]; ``slot`` indexes the batched cache, ``pos0`` is the number
    of tokens already cached for it (both dynamic). The chunk's K/V are
    scattered into the slot's blocks, then the chunk queries run flash
    attention over the gathered prefix+chunk with ``q_offset=pos0`` — for
    pos0 == 0 this is bitwise the one-shot prefill attention (the trailing
    fully-masked KV blocks contribute exact identity updates).
    """
    _, c, _ = x.shape
    positions = (pos0 + jnp.arange(c, dtype=jnp.int32))[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    table_row = cache["block_table"][slot]             # [mb]
    fmt = qcore.get_format(cfg.kv_dtype)
    # quantized pools: the chunk is quantized per (token, head) as it is
    # written — per-token scales make this append bit-identical to the
    # one-shot prefill's quantization of the same tokens
    pools = _scatter_kv(
        cache, k_new[0], v_new[0], fmt,
        lambda pool, vals: paged.scatter_chunk(pool, table_row, pos0, vals))
    k, v = _gather_kv(pools, table_row[None], fmt, x.dtype,
                      cfg.num_kv_heads)                # [1, mb*bs, H, D]
    out = flash_attention(q, k, v, causal=cfg.causal, q_chunk=cfg.q_chunk,
                          kv_chunk=cfg.kv_chunk, kahan_acc=cfg.kahan_acc,
                          q_offset=pos0, kv_len=pos0 + c)
    new_cache = {**pools, "block_table": cache["block_table"],
                 "len": cache["len"].at[slot].set(pos0 + c)}
    return common.dense(out.reshape(1, c, -1), p["wo"]), new_cache


def gqa_verify_chunk(p: dict, x: Array, cfg: AttnConfig, cache: dict,
                     slots: Array, pos0s: Array) -> tuple[Array, dict]:
    """Speculative verify: append + attend a C-token window for S slots in
    ONE batched pass.

    x: [S, C, d]; ``slots`` [S] indexes the batched cache, ``pos0s`` [S] is
    each slot's cached length (the window lands at pos0..pos0+C-1). This is
    the chunked-prefill formulation batched over slots: quantize-on-write
    through the shared ``_scatter_kv`` append (bitwise the decode append for
    the same token), then flash attention over the gathered prefix+window
    with per-slot ``q_offset``/``kv_len``. Rejected suffixes are rolled back
    by the caller purely via ``paged.set_lens`` — blocks stay allocated.
    Duplicate slot rows (fixed-shape padding) must carry identical tokens.
    """
    s_n, c, _ = x.shape
    positions = pos0s[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    tables = cache["block_table"][slots]               # [S, mb]
    fmt = qcore.get_format(cfg.kv_dtype)
    pools = _scatter_kv(
        cache, k_new, v_new, fmt,
        lambda pool, vals: paged.scatter_chunk_multi(pool, tables, pos0s,
                                                     vals))
    if paged_kernel_enabled():
        # TPU: the superkernel at query width C — ONE walk of each slot's
        # resident blocks for the whole window (the one-walk traffic the
        # kv_stats spec accounting prices), and row w is bitwise the
        # width-1 decode step at that position, so greedy accept/reject
        # cannot flip on formulation rounding.
        from repro.kernels import ops
        out = ops.paged_attention(
            q, pools["kpool"], pools["vpool"], tables, pos0s + c,
            kscale=pools.get("kscale"),
            vscale=pools.get("vscale")).astype(x.dtype)
    else:
        # CPU fallback is the chunked-prefill formulation with per-slot
        # offsets: chunking invariance makes every verify row bitwise the
        # width-1 decode step at its position (spec == non-spec greedy
        # streams) AND bitwise the prefill of the same token — the one
        # formulation the session-KV parity contract rests on.
        k, v = _gather_kv(pools, tables, fmt, x.dtype,
                          cfg.num_kv_heads)            # [S, mb*bs, H, D]
        out = flash_attention(q, k, v, causal=cfg.causal,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              kahan_acc=cfg.kahan_acc,
                              q_offset=pos0s, kv_len=pos0s + c)
    new_cache = {**pools, "block_table": cache["block_table"],
                 "len": cache["len"].at[slots].set(pos0s + c)}
    return common.dense(out.reshape(s_n, c, -1), p["wo"]), new_cache


def gqa_cache_spec(batch: int, layout: PagedLayout, cfg: AttnConfig,
                   dtype=jnp.bfloat16, num_blocks: int | None = None) -> dict:
    nb = (paged.default_num_blocks(layout, batch) if num_blocks is None
          else num_blocks)
    fmt = qcore.get_format(cfg.kv_dtype)
    # lane-dense [nb, bs, hkv * d]: the layout the paged-attention
    # superkernel reads. The TPU tiles a pool's last two dims, so a
    # [.., hkv, d] pool with d under 128 lanes is stored in other bytes
    # and every launch would re-lay each layer's pool out
    pool = (nb, layout.block_size, cfg.num_kv_heads * cfg.head_dim)
    spec = {"kpool": jax.ShapeDtypeStruct(pool, dtype if fmt is None
                                          else fmt.storage),
            "vpool": jax.ShapeDtypeStruct(pool, dtype if fmt is None
                                          else fmt.storage),
            "block_table": jax.ShapeDtypeStruct((batch, layout.max_blocks),
                                                jnp.int32),
            "len": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    if fmt is not None:
        # per-(block, token-row, head) scale tiles, pooled like the data
        sshape = (nb, layout.block_size, cfg.num_kv_heads)
        spec["kscale"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
        spec["vscale"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
    return spec
