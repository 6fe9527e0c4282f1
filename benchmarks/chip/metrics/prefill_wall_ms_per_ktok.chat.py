"""Host wall time of the engine's `serve.prefill` spans (prefill and page
write dispatched, first token read back) per 1000 prompt tokens they
prefilled, in ms: `PagedStats.prefill_wall_s` over `prefill_tokens`, over
the window. Its gap to `prefill_ms_per_ktok.chat` (device time) is the
host's dispatch and sync per admission."""


def read(rec):
    s = rec.counts.get("stats") or {}
    wall, n = s.get("prefill_wall_s"), s.get("prefill_tokens")
    return 1e3 * wall / (n / 1000.0) if wall is not None and n else None
