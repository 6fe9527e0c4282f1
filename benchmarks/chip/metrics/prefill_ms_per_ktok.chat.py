"""Device time of the prefill and page-write programs per 1000 prompt tokens
admitted in the window, in ms. The prefill is `jit__lambda`; the page write
is the `jit__unknown` program that writes the pool without running the
paged attention kernel."""

KERNEL = r"^%paged_gqa_decode\.\d+ = "
POOL_WRITE = r"dynamic-update-slice|scatter"


def read(rec):
    n = rec.counts["prompt_tokens"]
    t = rec.program_s(r"^jit__lambda") + rec.program_s(
        r"^jit__unknown", holding=POOL_WRITE, lacking=KERNEL)
    return 1e3 * t / (n / 1000.0) if n and t else None
