"""Random weights for the dense family, made on the device from the seed.

The benchmark makes the weights itself, in the parameter layout the
serving engine takes (the names below), so that the plain reference can
make the very same values from the same seed without taking anything
from the program. One jitted call makes every leaf, in f32, the type the
engine holds its parameters in.

Layout (``L`` layers stacked on a leading axis):

  embed [V, d]; final_norm {scale [d], bias [d] (layernorm)};
  lm_head [d, V] unless tied;
  layers: ln_attn / ln_mlp {scale [L, d], bias [L, d] (layernorm)},
          attn {wq [L, d, H*D], wk / wv [L, d, KV*D], wo [L, H*D, d],
                bq [L, H*D], bk / bv [L, KV*D] (qkv bias)},
          ffn {w_gate_up [L, d, 2F] (gate first, then up), w_down [L, F, d]}
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

NORM_JITTER = 0.1
BIAS_STD = 0.02


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    norm: str             # "rmsnorm" | "layernorm"
    norm_eps: float
    qkv_bias: bool
    tied: bool
    rope_theta: float
    rotary_dim: int

    @staticmethod
    def from_config(c: dict) -> "Dims":
        arch = c["architecture"]
        d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
        head_dim = int(c.get("head_dim", d // h))
        norm = arch["norm"]
        eps = c["rms_norm_eps"] if norm == "rmsnorm" else c["layer_norm_eps"]
        frac = float(c.get("partial_rotary_factor", 1.0))
        return Dims(layers=int(c["num_hidden_layers"]), d=d,
                    ff=int(c["intermediate_size"]), heads=h,
                    kv_heads=int(c["num_key_value_heads"]),
                    head_dim=head_dim, vocab=int(c["vocab_size"]),
                    norm=norm, norm_eps=float(eps),
                    qkv_bias=bool(arch["qkv_bias"]),
                    tied=bool(c["tie_word_embeddings"]),
                    rope_theta=float(c["rope_theta"]),
                    rotary_dim=int(head_dim * frac))


def leaves(m: Dims) -> list[tuple[str, tuple, str, float]]:
    """(path, shape, kind, scale) of every leaf, in a fixed order."""
    L, d, f = m.layers, m.d, m.ff
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out = [("embed", (m.vocab, d), "normal", d ** -0.5)]

    def norm(prefix, lead):
        out.append((f"{prefix}/scale", lead + (d,), "norm", NORM_JITTER))
        if m.norm == "layernorm":
            out.append((f"{prefix}/bias", lead + (d,), "normal", BIAS_STD))

    norm("final_norm", ())
    if not m.tied:
        out.append(("lm_head", (d, m.vocab), "normal", d ** -0.5))
    norm("layers/ln_attn", (L,))
    norm("layers/ln_mlp", (L,))
    out += [("layers/attn/wq", (L, d, qd), "normal", d ** -0.5),
            ("layers/attn/wk", (L, d, kvd), "normal", d ** -0.5),
            ("layers/attn/wv", (L, d, kvd), "normal", d ** -0.5),
            ("layers/attn/wo", (L, qd, d), "normal", qd ** -0.5)]
    if m.qkv_bias:
        out += [("layers/attn/bq", (L, qd), "normal", BIAS_STD),
                ("layers/attn/bk", (L, kvd), "normal", BIAS_STD),
                ("layers/attn/bv", (L, kvd), "normal", BIAS_STD)]
    out += [("layers/ffn/w_gate_up", (L, d, 2 * f), "normal", d ** -0.5),
            ("layers/ffn/w_down", (L, f, d), "normal", f ** -0.5)]
    return out


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from a seed of any size."""
    return int(np.random.SeedSequence(seed % (1 << 64)).generate_state(1)[0]
               & 0x7FFFFFFF)


def make_params(m: Dims, seed: int) -> dict:
    """Every leaf, as a nested dict of f32 arrays, in one jitted call."""
    import jax
    import jax.numpy as jnp

    specs = leaves(m)

    def build(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for (path, shape, kind, scale), k in zip(specs, keys):
            x = jax.random.normal(k, shape, jnp.float32) * scale
            if kind == "norm":
                x = 1.0 + x
            node = tree
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = x
        return tree

    return jax.jit(build)(jax.random.key(jax_seed(seed)))
