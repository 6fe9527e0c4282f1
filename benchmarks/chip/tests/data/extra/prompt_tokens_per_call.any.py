"""A dummy per-layer metric for the test that a new file is enough."""


def read(rec):
    c = rec.counts
    return c.get("prompt_tokens", 0) / max(c.get("calls", 0), 1) or None
