"""Model step: the decode step's share of the chip's bf16 peak. Flops
from the cell's family (``decode_flops``, ``bench/families/``) at each
traced step's decoding slots and their lengths, over the device time of
that step's decode launch."""

from bench import trace, views


def read(view):
    m = view.dims
    flops, kept = 0.0, []
    for s, evs in views.per_step(view, "XLA Modules", views.DECODE_MODULE):
        if not s.decode_launch or len(evs) != 1:
            continue
        flops += view.family.decode_flops(m, s.decode_lens)
        kept += evs
    if not kept:
        return None
    seconds = trace.total_ns(kept) / 1e9
    return 100.0 * flops / (seconds * view.peak["bf16_flops_per_s"])
