"""Whole-step model FLOP utilisation of the window, in % of the chip's bf16
peak: (2 x matmul parameters per generated token, LM head included, plus
attention over the live context) x tokens over the window's length."""

import work


def read(rec):
    c = rec.counts
    if not c["tokens"]:
        return None
    flops = work.model_flops(rec.model, c["tokens"], c["decode_rows"])
    return 100.0 * flops / c["window_s"] / rec.peak["bf16_flops_per_s"]
