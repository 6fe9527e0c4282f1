"""Mean wall time, in ms, that a first token waits on the host between the
prefill that made it and the return of the engine's `run()` that hands it
back: `PagedStats.first_token_hold_s` over `prefills`, over the window."""


def read(rec):
    s = rec.counts.get("stats") or {}
    hold, n = s.get("first_token_hold_s"), s.get("prefills")
    return 1e3 * hold / n if hold is not None and n else None
