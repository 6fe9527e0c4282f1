"""A dummy open-loop arrival process for the test that a new file is
enough: one request every 1 / `rate_per_s` seconds."""
import numpy as np


def mean_rate(p: dict) -> float:
    return float(p["rate_per_s"])


def gaps(rng, n: int, p: dict):
    return np.full(n, 1.0 / p["rate_per_s"])
