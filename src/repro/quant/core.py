"""Block quantization core: the one implementation every low-bit path uses.

The paper's result — compensation is free whenever the loop is memory-bound
— makes Kahan-corrected accumulation the natural partner of quantization:
halve (fp8/int8 vs bf16) the bytes a kernel must stream and spend the
widened bandwidth headroom on the dequant multiply plus the compensated
fold, so the *only* error a low-bit path introduces is the quantization
rounding itself, never accumulation order.

Three granularities, one scheme (symmetric, per-tile amax scaling):

  ``quantize_blocks``     flat fixed-size blocks (scale per ``block``
                          elements) — the error-feedback all-reduce payload
                          (``repro.distributed.compression``), hoisted here
                          so the KV and gradient paths share bit-identical
                          quantization.
  ``quantize_lastdim``    scale per trailing-axis vector — the KV-cache
                          granularity: one scale per (token, kv-head) for
                          GQA pools, per (token,) for MLA latents. Being
                          per-token it is *append-stable*: quantizing a
                          chunk as it is scattered into a block pool yields
                          bit-identical payloads to one-shot quantization,
                          which is what makes chunked-prefill-quantize ==
                          one-shot-quantize hold exactly.
  ``quantize_weight``     scale per (K-block, out-column) tile for int8
                          weight matmuls; the K-block granularity matches
                          the Pallas kernel's K-grid so dequantization is a
                          per-block multiply folded into the compensated
                          accumulate (``repro.kernels.kahan_matmul``).

Formats are symmetric with a clamped amax scale; ``fp8`` uses e4m3 (no
inf, ±448) and ``int8`` the usual [-127, 127]. ``"bf16"`` is the identity
format (``get_format`` returns None) so every call site can branch on one
knob, ``ModelConfig.kv_dtype``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

# flat-block granularity of the EF all-reduce payload (bitwise contract
# with the pre-hoist repro.distributed.compression implementation)
EF_BLOCK = 256
SCALE_EPS = 1e-12


class QuantFormat(NamedTuple):
    """A symmetric quantization target: value dtype + max representable
    magnitude (the amax of a tile maps onto ``qmax``)."""

    name: str
    dtype: jnp.dtype
    qmax: float

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def storage(self) -> jnp.dtype:
        """Pool/payload dtype as STORED. fp8 payloads are stored as raw
        e4m3 bytes (uint8), not ``float8_e4m3fn``: XLA CPU scalarizes any
        loop fusion whose element type is f8 — a gather, transpose or
        dynamic-update-slice touching an f8-typed pool runs ~20x slower
        than the same byte traffic as u8/int8, which is where most of the
        fp8 decode regression lived (the profile shows the whole-pool
        ``transpose_copy`` and ``dynamic-update-slice`` fusions, not the
        dot, eating the step). Every read path widens via the
        ``e4m3_to_f32`` bit trick, which starts from the u8 view anyway,
        so the f8 dtype only ever exists transiently inside ``_encode``."""
        if self.dtype == jnp.float8_e4m3fn:
            return jnp.dtype(jnp.uint8)
        return jnp.dtype(self.dtype)


INT8 = QuantFormat("int8", jnp.int8, 127.0)
FP8 = QuantFormat("fp8", jnp.float8_e4m3fn, 448.0)


def e4m3_to_f32(q: Array) -> Array:
    """Widen fp8 e4m3fn to f32 by bit reinterpretation, not convert.

    XLA CPU lowers the elementwise ``f8e4m3fn -> f32`` convert to a slow
    scalar-ish expansion, and when that convert sits fused inside a jitted
    decode step it costs MORE than the fp8 byte cut saves — the root cause
    of the fp8 0.70x decode regression. The same value is reachable with
    32-bit integer ops only, which is also all the TPU's Pallas lowering
    offers inside the superkernel (it has no 16-bit shifts): a normal
    e4m3 magnitude (exponent field >= 1) moves its exponent+mantissa bits
    to f32 bits 30..20 and re-biases the exponent by 127 - 7 = 120; a
    denormal (exponent field 0) is its 3-bit mantissa times 2^-9, exact
    in f32 without producing an f32 denormal (which the TPU would flush).
    The result is BITWISE the native convert for every e4m3 value
    (verified exhaustively over all 256 bytes in tests/test_quant.py).
    The two NaN encodings (0x7f/0xff) map to ±480 instead of NaN;
    quantized caches never store NaN (``_encode`` scales into range), so
    the hot paths below use this unconditionally.

    Accepts either ``float8_e4m3fn`` values or their raw-byte ``uint8``
    view (how pools store them — see ``QuantFormat.storage``).
    """
    u = (q if q.dtype == jnp.uint8
         else jax.lax.bitcast_convert_type(q, jnp.uint8)).astype(jnp.int32)
    mag = u & 0x7F
    normal = jax.lax.bitcast_convert_type((mag << 20) + (120 << 23),
                                          jnp.float32)
    val = jnp.where(mag < 8, mag.astype(jnp.float32) * jnp.float32(2.0 ** -9),
                    normal)
    return jnp.where(u >= 0x80, -val, val)


def cast_f32(x: Array) -> Array:
    """Widen any pool payload dtype to f32, routing fp8 e4m3 through the
    bit-shift reinterpretation (``e4m3_to_f32``) instead of XLA's slow
    elementwise convert. The single cast used by every quantized read
    path: ``dequantize_lastdim``, the hoisted-scale attends in
    ``repro.models.attention``, and the paged-attention superkernel.
    ``uint8`` payloads ARE fp8 here — pools store e4m3 as raw bytes
    (``QuantFormat.storage``)."""
    if x.dtype in (jnp.float8_e4m3fn, jnp.uint8):
        return e4m3_to_f32(x)
    return x.astype(jnp.float32)

FORMATS: dict[str, QuantFormat | None] = {
    "bf16": None,            # identity — keep the bf16 pools
    "int8": INT8,
    "fp8": FP8,
}


def get_format(kv_dtype: str) -> QuantFormat | None:
    """Resolve a ``kv_dtype`` knob; None means 'not quantized'."""
    if kv_dtype not in FORMATS:
        raise ValueError(f"unknown quant format {kv_dtype!r}; "
                         f"known: {sorted(FORMATS)}")
    return FORMATS[kv_dtype]


def _encode(x: Array, scale: Array, fmt: QuantFormat) -> Array:
    """Map f32 values with a broadcastable ``scale`` onto the format."""
    y = x / scale
    if fmt.dtype == jnp.int8:
        return jnp.clip(jnp.round(y), -fmt.qmax, fmt.qmax).astype(jnp.int8)
    # fp8 e4m3: amax lands exactly on ±448, so no clip is needed (and the
    # format has no inf to overflow into — values are in range by scaling).
    # Stored as the raw-byte u8 view: see ``QuantFormat.storage``.
    return jax.lax.bitcast_convert_type(y.astype(fmt.dtype), fmt.storage)


# ------------------------------------------------------------ last-dim ----

def quantize_lastdim(x: Array, fmt: QuantFormat) -> tuple[Array, Array]:
    """Per-vector symmetric quantization over the trailing axis.

    x: [..., D] any float dtype. Returns (q [..., D] fmt.dtype,
    scales [...] f32). One scale per trailing vector — for K/V rows
    [.., H, D] that is one scale per (token, head), pooled [nb, bs, H]
    alongside the [nb, bs, H * D] payload block so it rides the block
    table exactly like the data.
    """
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax / fmt.qmax, SCALE_EPS)
    return _encode(x, scale[..., None], fmt), scale


def dequantize_lastdim(q: Array, scales: Array,
                       dtype=jnp.float32) -> Array:
    """Inverse of ``quantize_lastdim``: q [..., D], scales [...] -> [..., D].

    fp8 payloads widen via ``e4m3_to_f32`` (bitwise the native convert,
    ~2x faster fused on XLA CPU — see the postmortem in README.md)."""
    return (cast_f32(q) * scales[..., None]).astype(dtype)


# ------------------------------------------------------------ flat blocks --

def quantize_blocks(x: Array, fmt: QuantFormat = INT8,
                    block: int = EF_BLOCK) -> tuple[Array, Array, int]:
    """Flat per-block symmetric quantization (the EF all-reduce payload).

    Flattens, zero-pads to a ``block`` multiple, and emits one scale per
    block. Returns (q [nblocks, block], scales [nblocks, 1] f32, pad).
    Bitwise contract: for int8 this reproduces the pre-hoist
    ``distributed.compression._quantize`` exactly (same op order), which
    tests/test_quant.py locks in.
    """
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / fmt.qmax
    scale = jnp.maximum(scale, SCALE_EPS)
    return _encode(blocks, scale, fmt), scale.astype(jnp.float32), pad


def dequantize_blocks(q: Array, scales: Array, pad: int,
                      shape: tuple) -> Array:
    """Inverse of ``quantize_blocks`` back to ``shape`` (f32)."""
    out = (cast_f32(q) * scales).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


# ------------------------------------------------------------ weights ------

def quantize_weight(w: Array, fmt: QuantFormat = INT8,
                    block_k: int = 256) -> tuple[Array, Array]:
    """Per-(K-block, out-column) tile quantization of a [K, N] weight.

    Returns (q [K, N] fmt.dtype, scales [K // block_k, N] f32). The K-block
    granularity is chosen to match the matmul kernel's K-grid: inside
    ``kernels.kahan_matmul.kahan_matmul_q8`` the dequant is then a single
    per-tile multiply of the MXU partial product before the compensated
    fold, so accumulation stays full fp32 + carry.
    """
    k, n = w.shape
    assert k % block_k == 0, (w.shape, block_k)
    wb = w.astype(jnp.float32).reshape(k // block_k, block_k, n)
    amax = jnp.max(jnp.abs(wb), axis=1)                     # [K/bk, N]
    scale = jnp.maximum(amax / fmt.qmax, SCALE_EPS)
    q = _encode(wb, scale[:, None, :], fmt).reshape(k, n)
    return q, scale


def dequantize_weight(q: Array, scales: Array) -> Array:
    """Inverse of ``quantize_weight`` -> f32 [K, N]."""
    nk, n = scales.shape
    k = q.shape[0]
    wb = cast_f32(q).reshape(nk, k // nk, n)
    return (wb * scales[:, None, :]).reshape(k, n)


# ------------------------------------------------------------ accounting ---

def kv_bytes_per_value(kv_dtype: str, vec_len: int,
                       baseline_itemsize: int = 2) -> float:
    """HBM bytes per cached KV *element* including the amortized f32 scale
    (one scale per ``vec_len`` elements). The input of the ECM decode-
    speedup prediction (``repro.ecm.tpu.predicted_decode_speedup``) and the
    analytic mirror of ``KVCache.token_bytes``."""
    fmt = get_format(kv_dtype)
    if fmt is None:
        return float(baseline_itemsize)
    return fmt.itemsize + 4.0 / vec_len
