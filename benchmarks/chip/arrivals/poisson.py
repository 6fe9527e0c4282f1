"""Poisson arrivals: independent users at `rate_per_s`."""


def mean_rate(p: dict) -> float:
    return float(p["rate_per_s"])


def gaps(rng, n: int, p: dict):
    return rng.exponential(1.0 / p["rate_per_s"], n)
