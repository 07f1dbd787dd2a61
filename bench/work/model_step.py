"""Flops of a dense decoder's serving steps, from its shapes.

Matmul flops are 2 per multiply-add of the weights each token meets (the
embedding lookup is free), attention's are q.k and p.v over the keys
each query sees, and the head is counted once per token whose logits
the step must produce.
"""

from __future__ import annotations


def layer_weight_macs(d: int, ff: int, heads: int, kv_heads: int,
                      head_dim: int) -> int:
    qd, kvd = heads * head_dim, kv_heads * head_dim
    return d * qd + 2 * d * kvd + qd * d + 3 * d * ff


def decode_flops(lens, layers: int, d: int, ff: int, heads: int,
                 kv_heads: int, head_dim: int, vocab: int) -> float:
    """One decode step for sequences whose new token sees ``lens`` keys."""
    w = layer_weight_macs(d, ff, heads, kv_heads, head_dim)
    n = len(lens)
    attn = sum(4 * heads * head_dim * int(k) for k in lens)
    return float(layers * (2 * w * n + attn) + 2 * d * vocab * n)


def prefill_flops(pos0: int, chunk: int, layers: int, d: int, ff: int,
                  heads: int, kv_heads: int, head_dim: int,
                  vocab: int) -> float:
    """One prefill chunk of ``chunk`` tokens after ``pos0`` cached ones;
    the head runs for the last token only."""
    w = layer_weight_macs(d, ff, heads, kv_heads, head_dim)
    keys = sum(pos0 + i + 1 for i in range(chunk))
    return float(layers * (2 * w * chunk + 4 * heads * head_dim * keys)
                 + 2 * d * vocab)
