"""The dense decoder family (Qwen2, StableLM): grouped-query attention
and a gated SiLU feed-forward in every layer. It binds the weights of
``bench.weights``, the reference of ``bench.reference.dense`` and the
work counts of ``bench.work`` (see ``bench.families`` for the interface).
"""

from __future__ import annotations

from bench import weights
from bench.reference import dense as reference
from bench.work import model_step, paged_attention

make_params = weights.make_params
hidden = reference.hidden
head_stats = reference.head_stats


def dims(config: dict) -> weights.Dims:
    return weights.Dims.from_config(config)


def program_want(m: weights.Dims) -> dict:
    return {"d_model": m.d, "d_ff": m.ff, "num_heads": m.heads,
            "num_kv_heads": m.kv_heads, "num_layers": m.layers,
            "vocab_size": m.vocab, "rope_theta": m.rope_theta,
            "tie_embeddings": m.tied, "head_dim": m.head_dim,
            "norm": m.norm, "qkv_bias": m.qkv_bias, "act": "swiglu",
            "rotary_fraction": m.rotary_dim / m.head_dim, "family": "dense"}


def decode_flops(m: weights.Dims, lens) -> float:
    return model_step.decode_flops(lens, m.layers, m.d, m.ff, m.heads,
                                   m.kv_heads, m.head_dim, m.vocab)


def attention_work(m: weights.Dims, lens, kv_dtype: str) -> tuple:
    return paged_attention.work(lens, m.heads, m.kv_heads, m.head_dim,
                                kv_dtype)


def attention_launches(m: weights.Dims) -> int:
    """One launch per layer."""
    return m.layers
