"""Public jit'd wrappers for the reduction-engine kernels.

Handles canonicalization (flatten only — the engine lays the operand out
as (rows, 128) lane tiles and masks the final partial block in-kernel),
interpret-mode selection (on off-TPU; off on a TPU, where every kernel
compiles), dtype policy, and the unroll default.

Single-output reductions (``kahan_dot``, ``kahan_sum``, ``naive_dot``)
and the fused multi-reductions (``fused_reduce``, ``batched_fused_reduce``,
``batched_kahan_dot``) all lower to the same engine
(``repro.kernels.engine``); see that module for the unrolling strategy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import engine
from repro.kernels import kahan_acc as _kacc
from repro.kernels.engine import LANES, SUBLANES  # noqa: F401 (re-export)


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _block_elems(block_rows: int | None, unroll: int | None, n: int) -> int:
    """Map the legacy ``block_rows`` knob to engine block elements."""
    u = engine.default_unroll(("dot",)) if unroll is None else unroll
    if block_rows is None:
        return engine.pick_block_elems(n, u)
    return engine.pick_block_elems(n, u, requested=block_rows * LANES)


# ------------------------------------------------------------ scalars -----

@functools.partial(jax.jit,
                   static_argnames=("block_rows", "unroll", "interpret"))
def _kahan_dot_impl(x, y, block_rows, unroll, interpret):
    flat_x, flat_y = x.reshape(-1), y.reshape(-1)
    (out,) = engine.fused_reduce_flat(
        (flat_x, flat_y), outputs=("dot",), unroll=unroll,
        block_elems=_block_elems(block_rows, unroll, flat_x.shape[0]),
        interpret=interpret)
    return out


def kahan_dot(x: jax.Array, y: jax.Array, *, block_rows: int | None = None,
              unroll: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Compensated scalar product of two same-shape arrays -> scalar."""
    assert x.shape == y.shape, (x.shape, y.shape)
    return _kahan_dot_impl(x, y, block_rows, unroll,
                           _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "unroll", "interpret"))
def _kahan_sum_impl(x, block_rows, unroll, interpret):
    flat = x.reshape(-1)
    (out,) = engine.fused_reduce_flat(
        (flat,), outputs=("sum",), unroll=unroll,
        block_elems=_block_elems(block_rows, unroll, flat.shape[0]),
        interpret=interpret)
    return out


def kahan_sum(x: jax.Array, *, block_rows: int | None = None,
              unroll: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Compensated full-array sum -> scalar."""
    return _kahan_sum_impl(x, block_rows, unroll, _auto_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "unroll", "interpret"))
def _naive_dot_impl(x, y, block_rows, unroll, interpret):
    flat_x, flat_y = x.reshape(-1), y.reshape(-1)
    (out,) = engine.fused_reduce_flat(
        (flat_x, flat_y), outputs=("dot",), unroll=unroll, compensated=False,
        block_elems=_block_elems(block_rows, unroll, flat_x.shape[0]),
        interpret=interpret)
    return out


def naive_dot(x: jax.Array, y: jax.Array, *, block_rows: int | None = None,
              unroll: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """Baseline (uncompensated) scalar product -> scalar."""
    assert x.shape == y.shape
    return _naive_dot_impl(x, y, block_rows, unroll,
                           _auto_interpret(interpret))


# ------------------------------------------------------------ fused -------

@functools.partial(jax.jit,
                   static_argnames=("outputs", "unroll", "interpret",
                                    "has_y"))
def _fused_reduce_impl(x, y, outputs, unroll, interpret, has_y):
    flat_x = x.reshape(-1)
    ops = (flat_x, y.reshape(-1)) if has_y else (flat_x,)
    outs = engine.fused_reduce_flat(ops, outputs=outputs, unroll=unroll,
                                    interpret=interpret)
    return dict(zip(outputs, outs))


def fused_reduce(x: jax.Array, y: jax.Array | None = None, *,
                 outputs=("sum", "sumsq", "maxabs"),
                 unroll: int | None = None,
                 interpret: bool | None = None) -> dict[str, jax.Array]:
    """One streaming pass -> {output: scalar} for any subset of
    ``dot | sum | sumsq | max | maxabs`` (``dot`` needs ``y``).

    HBM traffic is paid once for the whole statistic family — e.g. the
    gradient-norm + max-|g| pair in ``repro.optim`` or the pre-reduce
    shard statistics in ``repro.distributed.collectives``.
    """
    outputs = tuple(outputs)
    if "dot" in outputs and y is None:
        raise ValueError("'dot' output requires the second operand y")
    if y is not None:
        assert x.shape == y.shape
    else:
        y = x  # placeholder operand; has_y=False keeps it out of the call
    return _fused_reduce_impl(x, y, outputs, unroll,
                              _auto_interpret(interpret),
                              "dot" in outputs)


@functools.partial(jax.jit,
                   static_argnames=("outputs", "unroll", "interpret",
                                    "has_y"))
def _batched_fused_impl(x2, y2, outputs, unroll, interpret, has_y):
    ops = (x2, y2) if has_y else (x2,)
    outs = engine.fused_reduce_rows(ops, outputs=outputs, unroll=unroll,
                                    interpret=interpret)
    return dict(zip(outputs, outs))


def batched_fused_reduce(x: jax.Array, y: jax.Array | None = None, *,
                         outputs=("sum", "sumsq", "maxabs"),
                         unroll: int | None = None,
                         interpret: bool | None = None
                         ) -> dict[str, jax.Array]:
    """Row-wise fused reduction: (B, N) -> {output: (B,)} in one launch."""
    assert x.ndim == 2, x.shape
    outputs = tuple(outputs)
    if "dot" in outputs and y is None:
        raise ValueError("'dot' output requires the second operand y")
    if y is not None:
        assert x.shape == y.shape
    else:
        y = x
    return _batched_fused_impl(x, y, outputs, unroll,
                               _auto_interpret(interpret),
                               "dot" in outputs)


def batched_kahan_dot(x: jax.Array, y: jax.Array, *,
                      unroll: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Many independent compensated dots in one launch:
    (B, N) x (B, N) -> (B,)."""
    assert x.ndim == 2 and x.shape == y.shape, (x.shape, y.shape)
    return batched_fused_reduce(x, y, outputs=("dot",), unroll=unroll,
                                interpret=interpret)["dot"]


# ------------------------------------------------------------ paged -------

@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_attention_impl(q, kpool, vpool, kscale, vscale, q_rope,
                          rope_pool, rope_scale, table, lens, offs, scale,
                          interpret):
    from repro.kernels import paged_attention
    if q_rope is not None:
        return paged_attention.paged_latent_attention_pallas(
            q, q_rope, kpool, rope_pool, table, lens, offs,
            ck_scale=kscale, kr_scale=rope_scale, scale=scale,
            interpret=interpret)
    return paged_attention.paged_attention_pallas(
        q, kpool, vpool, table, lens, offs, kscale=kscale, vscale=vscale,
        scale=scale, interpret=interpret)


def paged_attention(q: jax.Array, kpool: jax.Array, vpool: jax.Array | None,
                    block_table: jax.Array, lens: jax.Array, *,
                    q_offsets: jax.Array | None = None,
                    kscale: jax.Array | None = None,
                    vscale: jax.Array | None = None,
                    q_rope: jax.Array | None = None,
                    rope_pool: jax.Array | None = None,
                    rope_scale: jax.Array | None = None,
                    scale: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """THE serving attention dispatch: one scalar-prefetch block-table
    walk (``repro.kernels.paged_attention``) configured per call.

    q: [B, W, Hq, D] — W query rows per sequence (1 for decode, k+1 for
    the speculative verify window) at absolute positions
    ``q_offsets + w``; defaults to ``lens - W``, i.e. the window was just
    appended to the cache. Returns [B, W, Hq, Dv].

    GQA pools: kpool/vpool [nb, bs, Hkv * D], as the cache stores them;
    quantized pools (int8/fp8) pass kscale/vscale [nb, bs, Hkv] and the
    kernel folds the scales post-dot into the compensated streams.

    MLA latents: pass the c_kv pool as ``kpool`` [nb, bs, C] with
    ``vpool=None`` (the value IS the latent block), the rope stream via
    ``q_rope`` [B, W, H, R] / ``rope_pool`` [nb, bs, R], per-token
    ``kscale``/``rope_scale`` [nb, bs] when quantized, and the explicit
    MLA softmax ``scale``. Returns context latents [B, W, H, C] f32.
    """
    assert q.ndim == 4, q.shape
    assert block_table.shape[0] == q.shape[0] == lens.shape[0]
    if q_rope is None:
        assert vpool is not None and kpool.ndim == 3, kpool.shape
        if kscale is not None:
            assert (kscale.shape[:2] == kpool.shape[:2] and kpool.shape[2]
                    == kscale.shape[2] * q.shape[-1]), (kscale.shape,
                                                        kpool.shape)
    else:
        assert vpool is None and rope_pool is not None and kpool.ndim == 3
        assert scale is not None, "MLA needs the explicit softmax scale"
    lens = lens.astype(jnp.int32)
    offs = (lens - q.shape[1] if q_offsets is None
            else q_offsets.astype(jnp.int32))
    return _paged_attention_impl(q, kpool, vpool, kscale, vscale, q_rope,
                                 rope_pool, rope_scale, block_table, lens,
                                 offs, scale, _auto_interpret(interpret))


# ------------------------------------------------------ quantized matmul --

@functools.partial(jax.jit, static_argnames=("interpret",))
def _q8_matmul_impl(a, qw, scales, interpret):
    # direct from-import: the package re-exports a FUNCTION named
    # kahan_matmul that shadows the module attribute
    from repro.kernels.kahan_matmul import kahan_matmul_q8
    return kahan_matmul_q8(a, qw, scales, interpret=interpret)


def q8_matmul(a: jax.Array, qw: jax.Array, scales: jax.Array, *,
              interpret: bool | None = None) -> jax.Array:
    """A @ dequant(qw) with Kahan-compensated fp32 K-accumulation — the
    int8 weight path for MLP/attention projections. ``qw``/``scales`` come
    from ``repro.quant.core.quantize_weight``; see
    ``repro.kernels.kahan_matmul.kahan_matmul_q8``."""
    assert a.ndim == 2 and qw.ndim == 2 and scales.ndim == 2
    return _q8_matmul_impl(a, qw, scales, _auto_interpret(interpret))


# ------------------------------------------------------------ acc ---------

@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _kahan_acc_impl(s, c, u, block_rows, interpret):
    shape = s.shape
    ns, nc = _kacc.kahan_acc_flat(s.reshape(-1), c.reshape(-1),
                                  u.reshape(-1), block_rows=block_rows,
                                  interpret=interpret)
    return ns.reshape(shape), nc.reshape(shape)


def kahan_accumulate(acc_sum: jax.Array, acc_carry: jax.Array,
                     update: jax.Array, *, block_rows: int = 512,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Elementwise compensated accumulate on arbitrary-shape arrays."""
    assert acc_sum.shape == acc_carry.shape == update.shape
    return _kahan_acc_impl(acc_sum, acc_carry, update, block_rows,
                           _auto_interpret(interpret))
