"""Share of admission passes, one per decode chunk, that stopped with a
slot free because the queue head's worst-case pages exceeded the pages
available: 100 x `PagedStats.page_waits` over `chunks`, over the window."""


def read(rec):
    s = rec.counts.get("stats") or {}
    waits, n = s.get("page_waits"), s.get("chunks")
    return 100.0 * waits / n if waits is not None and n else None
