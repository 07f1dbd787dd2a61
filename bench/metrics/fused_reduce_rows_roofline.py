"""Kernels: the compensated row-reduction kernel's share of its roofline
over the logit-statistics passes of the traced steps: two launches over
[max_slots, vocab] f32 per decode step and two over [1, vocab] per first
token (``bench.work.fused_reduce_rows``). A step whose trace does not
hold exactly those launches is left out."""

from bench import trace, views
from bench.work import fused_reduce_rows


def read(view):
    v, slots = view.dims.vocab, view.engine["max_slots"]
    launches, kept = [], []
    for s, evs in views.per_step(view, "XLA Ops",
                                 views.FUSED_REDUCE_ROWS_OP):
        want = fused_reduce_rows.logit_stats(1, v) * s.first_tokens
        if s.decode_launch:
            want += fused_reduce_rows.logit_stats(slots, v)
        if not want or len(evs) != len(want):
            continue
        launches += want
        kept += evs
    if not kept:
        return None
    flops = sum(f for f, _ in launches)
    nbytes = sum(b for _, b in launches)
    return views.roofline_pct(flops, nbytes, trace.total_ns(kept) / 1e9,
                              view.peak)
