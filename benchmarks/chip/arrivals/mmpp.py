"""Bursty arrivals: a Markov-modulated Poisson process. The process stays in
state k for an exponential time of mean `mean_dwell_s[k]` and meanwhile
sends Poisson arrivals at `rates_per_s[k]`, then moves to the next state in
turn; e.g. `{"rates_per_s": [8.0, 0.5], "mean_dwell_s": [5.0, 20.0]}` for
bursts between quiet spells."""
import numpy as np


def mean_rate(p: dict) -> float:
    r = np.asarray(p["rates_per_s"], np.float64)
    d = np.asarray(p["mean_dwell_s"], np.float64)
    return float((r * d).sum() / d.sum())


def gaps(rng, n: int, p: dict):
    rates, dwell = p["rates_per_s"], p["mean_dwell_s"]
    out = np.zeros(n)
    t = last = 0.0
    k, i = 0, 0
    end = rng.exponential(dwell[0])
    while i < n:
        nxt = t + rng.exponential(1.0 / rates[k]) if rates[k] > 0 else end
        if nxt < end:
            out[i], last, t = nxt - last, nxt, nxt
            i += 1
        else:
            # memoryless: the next state starts afresh at the switch
            t, k = end, (k + 1) % len(rates)
            end = t + rng.exponential(dwell[k])
    return out
