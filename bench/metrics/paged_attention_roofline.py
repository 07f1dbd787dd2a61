"""Kernels: the paged-attention kernel's share of its roofline. The work
of each traced decode step (``bench.work.paged_attention``: the decoding
sequences' actual lengths, every layer) over the kernel's device time in
that step. Memory bound at decode: one query row per sequence. A step
whose trace does not hold one launch per layer is left out."""

from bench import trace, views
from bench.work import paged_attention


def read(view):
    m = view.dims
    flops = nbytes = 0.0
    kept = []
    for s, evs in views.per_step(view, "XLA Ops", views.PAGED_ATTENTION_OP):
        if not s.decode_launch or len(evs) != m.layers:
            continue
        f, b = paged_attention.work(s.decode_lens, m.heads, m.kv_heads,
                                    m.head_dim, view.engine["kv_dtype"])
        flops += f * m.layers
        nbytes += b * m.layers
        kept += evs
    if not kept:
        return None
    return views.roofline_pct(flops, nbytes, trace.total_ns(kept) / 1e9,
                              view.peak)
