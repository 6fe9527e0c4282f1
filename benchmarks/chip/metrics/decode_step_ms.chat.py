"""Device time of the decode-chunk program per decode step, in ms: the time
of the programs that run the paged attention kernel, over chunks x
chunk_steps, in the window's trace."""

PROGRAM, KERNEL = r"^jit_", r"^%paged_gqa_decode\.\d+ = "


def read(rec):
    steps = rec.counts["stats"].get("chunks", 0) * rec.counts["chunk_steps"]
    t = rec.program_s(PROGRAM, holding=KERNEL)
    return 1e3 * t / steps if steps and t else None
