"""The one traffic generator: reads a mix file's parameters and draws requests.

A mix (`mixes/<name>.json`) is data only:

  * `prompt`, `output`: lengths as clamped lognormals (`median`, `sigma`,
    `min`, `max`), prompts rounded up to `grid`. Or `classes`: a list of
    `{"weight", "prompt", "output"}`, each request drawn from one class, for
    a mix of short and long requests.
  * `arrivals`: `{"process": "closed", ...}` for a backlog that is all due
    at once, or an open-loop process and its parameters, e.g.
    `{"process": "poisson", "rate_per_s": 2.0}` or
    `{"process": "mmpp", "rates_per_s": [8, 0.5], "mean_dwell_s": [5, 20]}`.
    An open-loop process is `arrivals/<process>.py` in the benchmark's
    directory, found by name: a
    `gaps(rng, n, params)` that draws n inter-arrival gaps and a
    `mean_rate(params)`.
  * `prefix` (optional): `{"groups": G, "tokens": L}`: each request's prompt
    starts with one of G shared prefixes of L tokens (a system prompt), the
    rest of its length its own.

Every seed gets the same lengths and inter-arrival gaps, drawn once from the
mix's `base_seed`; `--seed` only reorders them within consecutive blocks of
`shuffle_block` requests, and draws the token ids. So two seeds differ in
order and in token ids, never in the amount of work, and a window that sees
only the first blocks sees the same requests under every seed.

The lognormal arithmetic is copied from the program's traffic generators so
that a later change there cannot move this yardstick.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

import spec

HERE = Path(__file__).resolve().parent


@dataclass
class Planned:
    """One request: due time (s, from the start of the schedule; 0 for a
    backlog), prompt tokens and output budget."""
    rid: int
    due_s: float
    prompt: np.ndarray
    output_len: int


def clamped_lognorm(rng: np.random.Generator, median: float, sigma: float,
                    n: int, lo: int, hi: int) -> np.ndarray:
    v = np.exp(rng.normal(np.log(median), sigma, size=n))
    return np.clip(np.rint(v).astype(np.int64), lo, hi)


def lengths(rng: np.random.Generator, p: dict, n: int) -> np.ndarray:
    """Clamped lognormal lengths, rounded up to `p["grid"]` where given."""
    x = clamped_lognorm(rng, p["median"], p["sigma"], n, p["min"], p["max"])
    g = p.get("grid")
    if g:
        x = np.minimum(-(-x // g) * g, p["max"])
    return x


def classes(mix: dict) -> List[dict]:
    return mix.get("classes") or [{"weight": 1.0, "prompt": mix["prompt"],
                                   "output": mix["output"]}]


def grid_lengths(mix: dict) -> List[int]:
    """Every prompt length a mix can produce, shared prefix included: set-up
    warms exactly these."""
    out = set()
    for c in classes(mix):
        p = c["prompt"]
        g = p.get("grid")
        if not g:
            raise ValueError("a served mix needs a prompt grid")
        first = -(-p["min"] // g) * g
        out.update(range(first, p["max"] + 1, g))
    pre = prefix_tokens(mix)
    return sorted(pre + n for n in out)


def prefix_tokens(mix: dict) -> int:
    return int(mix.get("prefix", {}).get("tokens", 0))


def block_permutation(rng: np.random.Generator, n: int,
                      block: int) -> np.ndarray:
    """A permutation of range(n) that moves items only within consecutive
    blocks of `block`."""
    return np.concatenate([i + rng.permutation(min(block, n - i))
                           for i in range(0, n, block)])


def seed_rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  tag])


def process(mix: dict, here: Path = HERE):
    """The open-loop arrival process `arrivals/<process>.py` under `here`, or
    None for a closed backlog."""
    name = mix["arrivals"]["process"]
    if name == "closed":
        return None
    return spec.load(f"arrivals_{name}", here / "arrivals" / f"{name}.py")


def count(mix: dict, here: Path = HERE) -> int:
    """Requests the mix draws: `requests` for a backlog, the mean rate times
    `schedule_s` for an open loop."""
    proc = process(mix, here)
    if proc is None:
        return int(mix["requests"])
    return int(np.ceil(proc.mean_rate(mix["arrivals"]) * mix["schedule_s"]))


def requests(mix: dict, seed: int, vocab: int,
             here: Path = HERE) -> List[Planned]:
    """The mix's requests, due times relative to the schedule's start."""
    n = count(mix, here)
    proc = process(mix, here)
    base = np.random.default_rng(mix["base_seed"])
    cls = classes(mix)
    if len(cls) == 1:
        p_len = lengths(base, cls[0]["prompt"], n)
        o_len = lengths(base, cls[0]["output"], n)
    else:
        w = np.asarray([c["weight"] for c in cls], np.float64)
        pick = base.choice(len(cls), n, p=w / w.sum())
        p_len = np.zeros(n, np.int64)
        o_len = np.zeros(n, np.int64)
        for k, c in enumerate(cls):
            at = pick == k
            p_len[at] = lengths(base, c["prompt"], int(at.sum()))
            o_len[at] = lengths(base, c["output"], int(at.sum()))
    gaps = proc.gaps(base, n, mix["arrivals"]) if proc else None
    pre = prefix_tokens(mix)
    group = (base.integers(0, mix["prefix"]["groups"], n) if pre
             else np.zeros(n, np.int64))
    rng = seed_rng(seed, 1)
    block = mix.get("shuffle_block", n)
    order = block_permutation(rng, n, block)
    due = (np.cumsum(np.asarray(gaps)[block_permutation(rng, n, block)])
           if gaps is not None else np.zeros(n))
    tok = seed_rng(seed, 2)
    shared = (seed_rng(seed, 6).integers(
        0, vocab, (mix["prefix"]["groups"], pre), dtype=np.int32) if pre
        else None)
    out = []
    for i, j in enumerate(order):
        own = tok.integers(0, vocab, int(p_len[j]), dtype=np.int32)
        prompt = np.concatenate([shared[group[j]], own]) if pre else own
        out.append(Planned(rid=i, due_s=float(due[i]), prompt=prompt,
                           output_len=int(o_len[j])))
    return out


def occupancy_trace(t: dict, base_seed: int, seed: int,
                    bytes_per_token: int):
    """Occupancy of a served load, one segment per decode step: requests
    arrive by Poisson at `rate_per_s`, take a free slot of `slots` in order,
    hold their prompt's rows at once and one row more per step, and leave
    when their output is done. Lengths and gaps are a fixed set drawn from
    `base_seed`, ordered by `seed`. Returns (durations, occupancy bytes,
    rows read, rows written)."""
    n = int(t["rate_per_s"] * t["horizon_s"])
    base = np.random.default_rng(base_seed)
    p_len = lengths(base, t["prompt"], n)
    o_len = lengths(base, t["output"], n)
    gaps = base.exponential(1.0 / t["rate_per_s"], n)
    rng = seed_rng(seed, 5)
    order = rng.permutation(n)
    p_len, o_len = p_len[order], o_len[order]
    due = np.cumsum(gaps[rng.permutation(n)])
    steps = int(round(t["horizon_s"] / t["step_s"]))
    ctx = np.zeros(t["slots"], np.int64)       # rows held per slot
    left = np.zeros(t["slots"], np.int64)      # output tokens to go
    occ = np.zeros(steps, np.int64)
    nxt = reads = writes = 0
    for k in range(steps):
        now = k * t["step_s"]
        for s in np.flatnonzero(left == 0):
            if nxt >= n or due[nxt] > now:
                break
            ctx[s], left[s] = p_len[nxt], o_len[nxt]
            writes += int(p_len[nxt])
            nxt += 1
        busy = left > 0
        ctx[busy] += 1
        left[busy] -= 1
        occ[k] = int(ctx[busy].sum())
        reads += int(occ[k])
        writes += int(busy.sum())
        ctx[left == 0] = 0
    return (np.full(steps, t["step_s"]), occ * bytes_per_token,
            reads, writes)
