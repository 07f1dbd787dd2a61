"""Kernels: the paged-attention kernel's share of its roofline. The work
of each traced decode step (the cell's family's ``attention_work``, from
the decoding sequences' actual lengths, times its ``attention_launches``)
over the kernel's device time in that step. Memory bound at decode: one
query row per sequence. A step whose trace does not hold every launch is
left out."""

from bench import trace, views


def read(view):
    m, family = view.dims, view.family
    launches = family.attention_launches(m)
    flops = nbytes = 0.0
    kept = []
    for s, evs in views.per_step(view, "XLA Ops", views.PAGED_ATTENTION_OP):
        if not s.decode_launch or len(evs) != launches:
            continue
        f, b = family.attention_work(m, s.decode_lens,
                                     view.engine["kv_dtype"])
        flops += f * launches
        nbytes += b * launches
        kept += evs
    if not kept:
        return None
    return views.roofline_pct(flops, nbytes, trace.total_ns(kept) / 1e9,
                              view.peak)
