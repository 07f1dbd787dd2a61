"""Pallas TPU superkernel: ONE paged-attention block walk for every
serving path — decode, speculative verify, quantized pools, MLA latents.

The serving engine's three attention consumers used to be three
near-identical kernels: the bf16 decode walk, a quantized sibling with
in-register dequant, and a gather-based flash formulation for the
speculative verify window. This module merges them into a single
configurable kernel family, the PR-1 reduction-engine consolidation
repeated at the attention layer, parameterized by

  * **query width W** (1 for decode, k+1 for the spec-verify window):
    q carries W query rows per sequence at absolute positions
    ``q_offsets[b] + w``; row ``w`` attends keys at positions
    ``< q_offsets[b] + 1 + w``. Masking is per-row, and a fully masked
    block is an EXACT identity update of the compensated streams
    (p == 0, corr == exp(0) == 1, m unchanged at the finite NEG_INF).
    Query rows are padded to ``_ROW_TILE`` so every width lowers to the
    SAME program, making output row ``w`` of a width-W call bitwise the
    width-1 decode step at that position — the invariance
    tests/test_superkernel.py locks across all pool dtypes. One verify call therefore streams each
    resident block exactly once (the one-walk traffic
    ``repro.ecm.tpu``'s speculation model prices) instead of the k+1
    sequential walks it replaces.
  * **pool dtype** (bf16 | int8 | fp8-e4m3): quantized pools arrive as
    raw payloads plus per-(token-row, head) f32 scale tiles riding the
    SAME block table. fp8 payloads widen by bit reinterpretation
    (``quant.core.cast_f32``), never XLA's slow elementwise convert.
  * **dequant mode — the fp8-regression fix**: scales are loaded once
    per block and folded *post-dot* into the unrolled streams:
    ``s = (q · K_raw) · attn_scale · kscale[None, :]`` on the K side and
    ``p' = p · vscale[None, :]`` before the p·V fold. The multiplies
    land on the [rows, bs] score tile instead of the [bs, head_dim]
    payload — head_dim× less dequant work per streamed element — and no
    dequantized K/V copy is ever materialized. Exactly the paper's
    lesson: the extra arithmetic must ride in the unrolled loop body's
    bandwidth headroom, not as per-element scalar work on the critical
    path.
  * **layout** (GQA K/V pools | MLA latent pools): MLA is the MQA-like
    case — scores are a two-part sum over the c_kv and k_rope streams
    and the VALUE is the c_kv block itself, so each block is streamed
    once for both uses; the kernel emits context latents and the caller
    applies the absorbed ``wv_b``.

The walk: grid (batch, table slot) with scalar prefetch — the
block-table index feeds the BlockSpec index map, so each grid step DMAs
exactly one pool block, for all kv heads at once, from HBM. GQA pools
are stored lane-dense as [nb, bs, hkv * d] (``attention.gqa_cache_spec``)
and the kernel reads them as they are stored, so each block's trailing
two dims are the array's own: the TPU lowering refuses a block whose
second-minor dim is 1 of hkv, which a per-head grid axis needs. A
[nb, bs, hkv, d] pool is not those bytes: the TPU tiles the last two
dims, and for d under 128 lanes it pads d or stores the dims in another
order, so viewing such a pool lane-dense re-lays it out in every launch.
The kv heads are a static loop inside the step, each reading its lane
window. The online-softmax normalizer and output accumulator keep
compensated (sum, carry) stream pairs per head (``kahan.neumaier_step``,
rescale applied to sum AND carry — the DESIGN.md §4.2 decay-scaling
rule). Blocks entirely past a sequence's length skip their MXU work via
``pl.when``.

Exposed through the single ``ops.paged_attention`` dispatch
(auto-interpret on CPU) and validated by the bitwise parity grid in
tests/test_superkernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kahan
from repro.kernels.flash_attention import NEG_INF
from repro.quant.core import cast_f32

# Query rows are padded to this tile so every width W with
# W * groups <= _ROW_TILE lowers to the SAME kernel program — same block
# shapes, same jaxpr, same compiled executable. Bitwise width invariance
# (verify row w == the width-1 decode step at that position) then follows
# from row-locality of the math alone, instead of depending on the
# compiler making identical fusion/FMA choices for different row counts
# (XLA CPU provably does not: unpadded rows=2 vs rows=6 kernels disagree
# by 1 ulp on ~3% of outputs). On TPU the pad is the natural sublane
# alignment; decode is memory-bound so the extra MXU rows ride free.
_ROW_TILE = 32


def _pad_rows(x: jax.Array, rows: int) -> tuple[jax.Array, int]:
    """Zero-pad axis 2 (query rows) of [b, hkv, rows, d] to the tile."""
    pad = -rows % _ROW_TILE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x, rows + pad


# ------------------------------------------------ shared kernel fragments --

def init_softmax_scratch(m_scr, ls_scr, lc_scr, accs_scr, accc_scr) -> None:
    """Reset the online-softmax scratch at the start of a block walk."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    ls_scr[...] = jnp.zeros_like(ls_scr)
    lc_scr[...] = jnp.zeros_like(lc_scr)
    accs_scr[...] = jnp.zeros_like(accs_scr)
    accc_scr[...] = jnp.zeros_like(accc_scr)


def fold_softmax_block(s, v, vs, j, *, bs: int, rows: int, row_limits,
                       m_scr, ls_scr, lc_scr, accs_scr, accc_scr) -> None:
    """Fold one block's scores + values into the compensated online softmax.

    s: [rows, bs] scores with the attention scale and any K-side dequant
    scales already folded in; v: [bs, dv] f32 value payload (raw-cast for
    quantized pools); vs: [1, bs] V-side dequant scales folded into the
    post-softmax probabilities (None for bf16) — the normalizer sums the
    UNSCALED p, so out = Σ p·(vs·v) / Σ p is exactly softmax over
    dequantized values; row_limits: [rows, 1] exclusive per-row key
    limits (the query-width masking). The softmax rescale multiplies sum
    AND carry (decay-scaling rule). The scratch refs are one kv head's
    [rows, 128] / [rows, dv] views.
    """
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
    mask = k_pos < row_limits
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...][:, :1]                     # [rows, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask
    corr = jnp.exp(m_prev - m_new)                 # [rows, 1]
    ls, lc = kahan.neumaier_step(ls_scr[...][:, :1] * corr,
                                 lc_scr[...][:, :1] * corr,
                                 p.sum(axis=-1, keepdims=True))
    pv = jax.lax.dot_general(
        p if vs is None else p * vs, v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # [rows, dv]
    accs, accc = kahan.neumaier_step(accs_scr[...] * corr,
                                     accc_scr[...] * corr, pv)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    ls_scr[...] = jnp.broadcast_to(ls, ls_scr.shape)
    lc_scr[...] = jnp.broadcast_to(lc, lc_scr.shape)
    accs_scr[...] = accs
    accc_scr[...] = accc


def emit_softmax_output(o_ref, ls_scr, lc_scr, accs_scr, accc_scr) -> None:
    """Normalize every head's compensated accumulators into the output
    block [1, hkv, rows, dv]."""
    l = ls_scr[...][..., :1] + lc_scr[...][..., :1]
    acc = accs_scr[...] + accc_scr[...]
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_grid_spec(b: int, mb: int, bs: int, hkv: int, rows: int,
                    q_dims: tuple, kv_widths: tuple, dv: int,
                    n_scales: int) -> "pltpu.PrefetchScalarGridSpec":
    """Grid over (batch, table slot) with the (block_table, lens,
    q_offsets) scalar prefetch; each step DMAs one pool block for ALL kv
    heads. ``q_dims`` gives the trailing dim of each query operand
    ([b, hkv, rows, d]); ``kv_widths`` the trailing dim of each pool
    operand, stored lane-dense as [nb, bs, hkv * d]; ``n_scales`` appends
    that many [nb, bs, hkv] scale-tile operands following the same
    table-indexed walk — ONE scale DMA per block, not per element. Every
    block's trailing two dims are the array's own, the layout the TPU
    lowering requires of a block that is not (8, 128)-aligned."""
    def q_spec(d):
        return pl.BlockSpec((1, hkv, rows, d), lambda i, j, *_: (i, 0, 0, 0))

    def table_spec(width):
        return pl.BlockSpec(
            (1, bs, width),
            lambda i, j, table, lens, offs: (table[i, j], 0, 0))

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # (block_table, lens, q_offsets)
        grid=(b, mb),
        in_specs=[*(q_spec(d) for d in q_dims),
                  *(table_spec(w) for w in kv_widths),
                  *([table_spec(hkv)] * n_scales)],
        out_specs=pl.BlockSpec((1, hkv, rows, dv),
                               lambda i, j, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 128), jnp.float32),   # m (col 0 used)
            pltpu.VMEM((hkv, rows, 128), jnp.float32),   # l sum
            pltpu.VMEM((hkv, rows, 128), jnp.float32),   # l carry
            pltpu.VMEM((hkv, rows, dv), jnp.float32),    # acc sum
            pltpu.VMEM((hkv, rows, dv), jnp.float32),    # acc carry
        ],
    )


# ------------------------------------------------------------ the kernel ---

def _super_kernel(table_ref, lens_ref, offs_ref, *refs, mla: bool,
                  quant: bool, scale: float, bs: int, rows: int,
                  groups: int, hkv: int, dk: int, dv: int):
    """One body for the whole family; ``mla``/``quant`` are trace-time
    flags, so each configuration lowers to a specialized kernel with no
    in-kernel branching. The kv-head loop is unrolled at trace time: head
    h reads its static lane window of the lane-dense block."""
    scratch = refs[-5:]
    o_ref = refs[-6]
    ins = refs[:-6]
    m_scr, ls_scr, lc_scr, accs_scr, accc_scr = scratch

    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        init_softmax_scratch(*scratch)

    length = lens_ref[b]
    # row r is query-width index r // groups: exclusive key limit per row
    row_limits = (offs_ref[b] + 1
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                  // groups)

    # Dead blocks (entirely past the sequence length) are exact identity
    # updates — skip their MXU work. Blocks past an individual ROW's limit
    # but under ``length`` are handled by the per-row mask in the fold
    # (also exact identity updates — the width-invariance contract).
    @pl.when(j * bs < length)
    def _block():
        n_q = 2 if mla else 1
        q_refs, kv_refs, sc_refs = ins[:n_q], ins[n_q:n_q + 2], ins[n_q + 2:]
        # per-(token row, head) dequant scales [bs, hkv], transposed once
        # per block so head h's scales are the row [1, bs] of the fold
        sc = [r[0].T for r in sc_refs]
        for h in range(hkv):
            fold = functools.partial(
                fold_softmax_block, j=j, bs=bs, rows=rows,
                row_limits=row_limits, m_scr=m_scr.at[h],
                ls_scr=ls_scr.at[h], lc_scr=lc_scr.at[h],
                accs_scr=accs_scr.at[h], accc_scr=accc_scr.at[h])
            hs = [x[h:h + 1] for x in sc]                # [1, bs] each
            if mla:
                # one latent "head" (hkv == 1): two score streams (c_kv
                # latents + shared rope key), value IS the c_kv block —
                # streamed once, used twice
                ql_ref, qr_ref = q_refs
                ck = cast_f32(kv_refs[0][0])               # [bs, c]
                kr = cast_f32(kv_refs[1][0])               # [bs, r]
                s_lat = jax.lax.dot_general(
                    ql_ref[0, 0].astype(jnp.float32), ck,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [rows, bs]
                s_rope = jax.lax.dot_general(
                    qr_ref[0, 0].astype(jnp.float32), kr,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if quant:
                    cs, rs = hs
                    fold((s_lat * cs + s_rope * rs) * scale, ck, cs)
                else:
                    fold((s_lat + s_rope) * scale, ck, None)
            else:
                k_ref, v_ref = kv_refs
                k = cast_f32(k_ref[0, :, h * dk:(h + 1) * dk])   # [bs, dk]
                v = cast_f32(v_ref[0, :, h * dv:(h + 1) * dv])   # [bs, dv]
                s = jax.lax.dot_general(
                    q_refs[0][0, h].astype(jnp.float32), k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quant:
                    # post-dot scale fold: [rows, bs] multiplies, not [bs, dk]
                    ks, vs = hs
                    fold(s * ks, v, vs)
                else:
                    fold(s, v, None)

    @pl.when(j == nj - 1)
    def _emit():
        emit_softmax_output(o_ref, ls_scr, lc_scr, accs_scr, accc_scr)


# ------------------------------------------------------------ wrappers -----

def paged_attention_pallas(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                           block_table: jax.Array, lens: jax.Array,
                           q_offsets: jax.Array, *,
                           kscale: jax.Array | None = None,
                           vscale: jax.Array | None = None,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """W query rows per sequence against (optionally quantized) paged KV.

    q: [B, W, Hq, D]; kpool/vpool: [nb, bs, Hkv * D], the stored
    lane-dense layout — bf16/f32, or int8/fp8 with kscale/vscale
    [nb, bs, Hkv] per-(token-row, head) f32 scales; the kv head count is
    the pool width over q's head dim. block_table: [B, mb] int32; lens:
    [B] total valid keys (the window's K/V must already be scattered);
    q_offsets: [B] absolute position of query row 0 (row w attends keys
    < q_offsets + 1 + w; for decode q_offsets == lens - 1). Returns
    [B, W, Hq, Dv] in q's dtype.
    """
    b, w, hq, d = q.shape
    _, bs, kwidth = kpool.shape
    hkv, dk = kwidth // d, d
    assert hkv * d == kwidth and hq % hkv == 0, (q.shape, kpool.shape)
    dv = vpool.shape[-1] // hkv
    mb = block_table.shape[1]
    groups = hq // hkv
    rows = w * groups
    # [B, W, Hq, D] -> [b, hkv, W*groups, d], width-major rows per kv head,
    # zero-padded to the uniform row tile (pad rows compute garbage that is
    # sliced off; the pad is what makes every width the same program)
    qg = (q.reshape(b, w, hkv, groups, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, rows, d))
    qg, rows_pad = _pad_rows(qg, rows)
    quant = kscale is not None

    grid_spec = paged_grid_spec(b, mb, bs, hkv, rows_pad, (d,),
                                (hkv * dk, hkv * dv), dv, 2 if quant else 0)
    kernel = functools.partial(
        _super_kernel, mla=False, quant=quant,
        scale=d ** -0.5 if scale is None else scale, bs=bs, rows=rows_pad,
        groups=groups, hkv=hkv, dk=dk, dv=dv)
    args = [block_table, lens, q_offsets, qg, kpool, vpool]
    if quant:
        args += [kscale, vscale]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_pad, dv), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*args)
    return (out[:, :, :rows]
            .reshape(b, hkv, w, groups, dv).transpose(0, 2, 1, 3, 4)
            .reshape(b, w, hq, dv))


def paged_latent_attention_pallas(q_lat: jax.Array, q_rope: jax.Array,
                                  ck_pool: jax.Array, kr_pool: jax.Array,
                                  block_table: jax.Array, lens: jax.Array,
                                  q_offsets: jax.Array, *,
                                  ck_scale: jax.Array | None = None,
                                  kr_scale: jax.Array | None = None,
                                  scale: float,
                                  interpret: bool = False) -> jax.Array:
    """MLA absorbed-latent attention over paged latent pools (MQA-like:
    one shared KV "head", every query head grouped onto it).

    q_lat: [B, W, H, C] (q_nope absorbed through wk_b by the caller);
    q_rope: [B, W, H, R]; ck_pool: [nb, bs, C]; kr_pool: [nb, bs, R];
    quantized pools add per-token ck_scale/kr_scale [nb, bs]. ``scale``
    is the MLA softmax scale (nope_dim + rope_dim)^-0.5 — NOT derivable
    from the latent width. Returns context latents [B, W, H, C] f32; the
    caller applies the absorbed ``wv_b``.
    """
    b, w, h, c = q_lat.shape
    r = q_rope.shape[-1]
    _, bs, _ = ck_pool.shape
    mb = block_table.shape[1]
    rows = w * h
    ql, rows_pad = _pad_rows(q_lat.reshape(b, 1, rows, c), rows)
    qr, _ = _pad_rows(q_rope.reshape(b, 1, rows, r), rows)
    quant = ck_scale is not None

    grid_spec = paged_grid_spec(b, mb, bs, 1, rows_pad, (c, r), (c, r), c,
                                2 if quant else 0)
    kernel = functools.partial(_super_kernel, mla=True, quant=quant,
                               scale=scale, bs=bs, rows=rows_pad, groups=h,
                               hkv=1, dk=c, dv=c)
    args = [block_table, lens, q_offsets, ql, qr, ck_pool, kr_pool]
    if quant:
        args += [ck_scale[:, :, None], kr_scale[:, :, None]]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, rows_pad, c), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(*args)
    return out[:, :, :rows].reshape(b, w, h, c)
