"""Decode attention over a paged KV cache, one query token per sequence.

The problem: for each sequence of ``L`` cached tokens (the new one
included), scores q.k over L keys and the weighted sum p.v over L
values, for every query head. Bytes: the K and V of each sequence's
actual L tokens at the pool's element size (no padding to blocks), their
scales where the pool is quantized, and q and the output in bf16.
"""

from __future__ import annotations

POOL_BYTES = {"bf16": 2, "int8": 1, "fp8": 1}
SCALE_BYTES = 4           # one f32 scale per (token, kv head) for K and V
ACT_BYTES = 2             # q in and attention out, bf16


def work(lens, heads: int, kv_heads: int, head_dim: int,
         kv_dtype: str = "bf16") -> tuple[float, float]:
    """(flops, bytes) of one launch over sequences of lengths ``lens``."""
    tokens = sum(int(n) for n in lens)
    flops = 2 * 2 * heads * head_dim * tokens
    per_token = 2 * kv_heads * head_dim * POOL_BYTES[kv_dtype]
    if kv_dtype != "bf16":
        per_token += 2 * kv_heads * SCALE_BYTES
    nbytes = tokens * per_token + len(lens) * 2 * heads * head_dim * ACT_BYTES
    return float(flops), float(nbytes)
