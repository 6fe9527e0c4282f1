"""Paged decode attention's share of its roofline, in %: the least time for
the work the live context needs (each live K and V row once per layer, plus
q and the output, against 819 GB/s; its FLOPs against the bf16 peak) over
the kernel's device time in the window."""

import work

KERNEL = r"^%paged_gqa_decode\.\d+ = "


def read(rec):
    t = rec.op_s(KERNEL)
    if not t:
        return None
    c = rec.counts
    flops, nbytes = work.paged_attn_work(rec.model, c["decode_steps"],
                                         c["decode_rows"])
    return work.roofline_share(flops, nbytes, t, rec.peak)
