"""Mean wall time, in ms, from a request's submit to the start of its
admission: `PagedStats.queue_wait_s` over `admitted`, over the window."""


def read(rec):
    s = rec.counts.get("stats") or {}
    wait, n = s.get("queue_wait_s"), s.get("admitted")
    return 1e3 * wait / n if wait is not None and n else None
