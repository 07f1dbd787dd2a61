"""Plain float32 reference forward for the dense family (Qwen2, StableLM).

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching, one sequence at a time and one layer at a time, so that it fits
beside nothing else on the chip. It follows the published architectures:

- pre-norm blocks, RMSNorm (Qwen2) or LayerNorm with bias (StableLM);
- q, k, v projections (with bias for Qwen2), rotary embeddings on the
  first ``rotary_dim`` dims of each head in the rotate-half convention
  (StableLM rotates 25% of them), causal softmax attention at
  1/sqrt(head_dim), grouped kv heads shared by consecutive query heads;
- a gated SiLU feed-forward, ``down(silu(gate(x)) * up(x))``;
- a final norm and an untied head.

It imports nothing of the program and reads its weights from
``bench.weights``, made from the seed. ``rounding`` puts the control in
the program's place: ``"fp8"`` rounds both inputs of every matmul to the
3 mantissa bits of float8 e4m3 (without its range limit), one precision
below the bfloat16 the configurations state. ``stats="bf16"`` computes
the logits and their statistics in bfloat16, one precision below the f32
logits and compensated f32 statistics the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims

HI = jax.lax.Precision.HIGHEST


def round_mantissa(x, bits: int):
    """Round f32 ``x`` to ``bits`` mantissa bits, to nearest even."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    half = jnp.uint32((1 << (drop - 1)) - 1)
    odd = (u >> drop) & jnp.uint32(1)
    u = (u + half + odd) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


ROUNDING = {"f32": lambda x: x,
            "fp8": lambda x: round_mantissa(x, 3),
            "bf16": lambda x: round_mantissa(x, 7)}


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HI)


def _norm(x, p, m: Dims):
    if m.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m.norm_eps) * p["scale"] \
            + p["bias"]
    var = jnp.square(x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + m.norm_eps) * p["scale"]


def _rope(x, pos, m: Dims):
    """x: [L, heads, D]; rotate-half on the first rotary_dim dims."""
    rd = m.rotary_dim
    inv = 1.0 / (m.rope_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32)
                                  / rd))
    ang = pos[:, None, None].astype(jnp.float32) * inv      # [L, 1, rd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "rounding"))
def _layer(p, h, m: Dims, rounding: str):
    rnd = ROUNDING[rounding]
    n = h.shape[0]
    pos = jnp.arange(n)
    x = _norm(h, p["ln_attn"], m)
    a = p["attn"]
    q = _mm(x, a["wq"], rnd)
    k = _mm(x, a["wk"], rnd)
    v = _mm(x, a["wv"], rnd)
    if m.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(n, m.heads, m.head_dim), pos, m)
    k = _rope(k.reshape(n, m.kv_heads, m.head_dim), pos, m)
    v = v.reshape(n, m.kv_heads, m.head_dim)
    groups = m.heads // m.kv_heads
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=HI)
    s = s / np.sqrt(m.head_dim)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", rnd(w), rnd(v), precision=HI)
    h = h + _mm(o.reshape(n, -1), a["wo"], rnd)
    x = _norm(h, p["ln_mlp"], m)
    gu = _mm(x, p["ffn"]["w_gate_up"], rnd)
    gate, up = gu[:, :m.ff], gu[:, m.ff:]
    return h + _mm(jax.nn.silu(gate) * up, p["ffn"]["w_down"], rnd)


def _logits(params, h, rows, m: Dims, rounding: str):
    x = _norm(h[rows], params["final_norm"], m)
    head = params["embed"].T if m.tied else params["lm_head"]
    return _mm(x, head, ROUNDING[rounding])                  # [P, V]


logits_at = jax.jit(_logits, static_argnames=("m", "rounding"))


def _lse(logits, mx, rnd):
    """logsumexp with the shifted exponentials, their sum and the result
    each rounded by ``rnd`` (the sum itself accumulates in f32)."""
    e = rnd(jnp.exp(rnd(logits - mx[:, None])))
    return rnd(mx + jnp.log(rnd(e.sum(-1))))


@functools.partial(jax.jit, static_argnames=("m", "rounding", "stats"))
def _head(params, h, rows, ids, m: Dims, rounding: str, stats: str):
    """Logit statistics at ``rows`` of ``h``: max, argmax, logsumexp and
    the logit of ``ids``, with the logits and statistics rounded to
    ``stats``."""
    rnd = ROUNDING[stats]
    logits = rnd(_logits(params, h, rows, m, rounding))
    mx = logits.max(-1)
    return {"max": mx, "argmax": logits.argmax(-1),
            "lse": _lse(logits, mx, rnd),
            "at": jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]}


def hidden(params, tokens: np.ndarray, m: Dims, rounding: str = "f32"):
    """Final hidden states [len(tokens), d] of one sequence."""
    h = params["embed"][jnp.asarray(tokens)]
    for i in range(m.layers):
        layer = jax.tree.map(lambda x: x[i], params["layers"])
        h = _layer(layer, h, m, rounding)
    return h


def head_stats(params, h, rows: np.ndarray, ids: np.ndarray, m: Dims,
               rounding: str = "f32", stats: str = "f32") -> dict:
    out = _head(params, h, jnp.asarray(rows), jnp.asarray(ids), m, rounding,
                stats)
    return {k: np.asarray(v) for k, v in out.items()}
