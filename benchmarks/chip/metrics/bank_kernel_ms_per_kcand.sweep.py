"""Device time of the Stage-II bank-energy kernels per 1000 candidates of
the grid evaluated in the window, in ms."""

KERNELS = r"^%_(exact_bank_stats|bank_activity_stats)_jit\.\d+ = .*custom-call"


def read(rec):
    n = rec.counts["candidates"]
    t = rec.op_s(KERNELS)
    return 1e3 * t / (n / 1000.0) if n and t else None
