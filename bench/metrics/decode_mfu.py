"""Model step: the decode step's share of the chip's bf16 peak. Flops
from ``bench.work.model_step`` at each traced step's decoding slots and
their lengths, over the device time of that step's decode launch."""

from bench import trace, views
from bench.work import model_step


def read(view):
    m = view.dims
    flops, kept = 0.0, []
    for s, evs in views.per_step(view, "XLA Modules", views.DECODE_MODULE):
        if not s.decode_launch or len(evs) != 1:
            continue
        flops += model_step.decode_flops(s.decode_lens, m.layers, m.d, m.ff,
                                         m.heads, m.kv_heads, m.head_dim,
                                         m.vocab)
        kept += evs
    if not kept:
        return None
    seconds = trace.total_ns(kept) / 1e9
    return 100.0 * flops / (seconds * view.peak["bf16_flops_per_s"])
