"""Share of decode-slot steps that decoded a live request: decode steps over
chunks x chunk_steps x slots, from the engine's `PagedStats` over the window.
Below 100% the admission and page ledger left slots empty."""


def read(rec):
    s, c = rec.counts["stats"], rec.counts
    lanes = s.get("chunks", 0) * c["chunk_steps"] * c["num_slots"]
    return 100.0 * s["decode_steps"] / lanes if lanes else None
