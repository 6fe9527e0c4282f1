"""Driver for served traffic: the paged engine under a backlog or a schedule.

It uses only the engine's public API: `build_model`, `model.init` (for the
tree's shapes), `PagedContinuousBatcher(...)`, `submit`, `run(max_chunks=1)`,
`Request` and `stats`. Each `run(max_chunks=1)` admits what fits and runs
one decode chunk; its return is stamped with `time.perf_counter()`, and
every token a request gained in that call gets that stamp.

Arrivals (the mix's `arrivals`, drawn by `traffic.py`):
  * `closed`: a closed backlog that keeps `in_flight_per_slot` x slots
    requests in flight, more than the slots hold. The window opens after
    the first call, which fills the slots.
  * any open-loop process: a schedule of due times. Arrivals start
    `preroll_s` before the window so that it sees a loaded engine; each
    request is timed from its due time.

After the window, a sample of the requests served in it (drawn from the
seed, the longest among them) is run through the float32 reference, and the
widest gap by which a served token's reference logit lies below the
reference's best is compared with the configuration's limit. With
`control`, the control's gap is judged in its place as well (`control.py`).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import sys
import time

import numpy as np

import traffic
import weights
import work

SAMPLE = 8          # requests compared with the reference, besides the longest


@dataclasses.dataclass
class Track:
    """What the harness saw of one request."""
    spec: traffic.Planned
    due: float                      # perf_counter time it was due
    req: object = None
    seen: int = 0
    first: float = float("nan")
    last: float = float("nan")
    in_window: int = 0              # tokens that arrived in the window
    win_first: float = float("nan")


def build(ctx):
    """Model, weights on the device, and the batcher."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    from repro.serve import PagedContinuousBatcher

    m = ctx.cfg["model"]
    eng = dict(ctx.cfg["engine"])
    kv_block = eng.pop("kv_block")
    model = build_model(ArchConfig(**m), compute_dtype=jnp.bfloat16,
                        remat="none", kv_block=kv_block)
    params = weights.make(m, ctx.seed)
    weights.check_tree(params, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    jax.block_until_ready(params)
    return PagedContinuousBatcher(model, params, **eng)


def warm(cb, ctx):
    """One request per prompt length of the mix, two tokens each: compiles
    every prefill and page write the window will use, and the decode
    chunk."""
    from repro.serve import Request
    rng = traffic.seed_rng(ctx.seed, 3)
    vocab = ctx.cfg["model"]["vocab_size"]
    grid = traffic.grid_lengths(ctx.mix)
    for i, n in enumerate(grid):
        cb.submit(Request(rid=-1 - i, tokens=rng.integers(
            0, vocab, n, dtype=np.int32), max_new_tokens=2))
    while cb.stats.finished < len(grid):
        cb.run(max_chunks=1)


def run(ctx, control: bool = False):
    from repro.serve import Request

    mix, m = ctx.mix, ctx.cfg["model"]
    eng = ctx.cfg["engine"]
    with ctx.annotate("bench.build"):
        cb = build(ctx)
    with ctx.annotate("bench.warm"):
        warm(cb, ctx)
    open_loop = traffic.process(mix, ctx.spec.here) is not None
    specs = traffic.requests(mix, ctx.seed, m["vocab_size"], ctx.spec.here)
    backlog = int(mix["arrivals"].get("in_flight_per_slot", 0)
                  * eng["num_slots"])

    tracks, live = {}, {}
    nxt = 0
    t_sched = time.perf_counter()

    def submit_due(now):
        nonlocal nxt
        while nxt < len(specs) and (
                (open_loop and t_sched + specs[nxt].due_s <= now)
                or (not open_loop and len(live) < backlog)):
            s = specs[nxt]
            tr = Track(s, t_sched + s.due_s if open_loop else now)
            tr.req = Request(rid=s.rid, tokens=s.prompt,
                             max_new_tokens=s.output_len)
            cb.submit(tr.req)
            tracks[s.rid] = live[s.rid] = tr
            nxt += 1

    acc = dict(tokens=0, decode_steps=0, decode_rows=0, prompt_tokens=0,
               calls=0)

    def call(in_window: bool):
        with ctx.annotate("bench.submit"):
            submit_due(time.perf_counter())
        if not live:
            # idle open loop: wait for the next arrival
            due = t_sched + specs[nxt].due_s if nxt < len(specs) else 0.0
            time.sleep(min(max(0.0, due - time.perf_counter()), 0.05))
            return
        with ctx.annotate("bench.run"):
            done = cb.run(max_chunks=1)
        t = time.perf_counter()
        with ctx.annotate("bench.stamp"):
            for tr in list(live.values()):
                k = len(tr.req.output)
                g = k - tr.seen
                if g:
                    if tr.seen == 0:
                        tr.first = t
                    tr.last = t
                    if in_window:
                        if tr.in_window == 0:
                            tr.win_first = t
                        tr.in_window += g
                        acc["tokens"] += g
                        steps = g - (tr.seen == 0)
                        acc["decode_steps"] += steps
                        acc["decode_rows"] += work.decode_rows(
                            len(tr.spec.prompt), max(tr.seen, 1), steps)
                        if tr.seen == 0:
                            acc["prompt_tokens"] += len(tr.spec.prompt)
                    tr.seen = k
            for r in done:
                live.pop(r.rid, None)
        if in_window:
            acc["calls"] += 1

    # before the window: fill the slots (backlog) or run the preroll
    preroll_end = t_sched + mix.get("preroll_s", 0.0)
    call(False)
    while time.perf_counter() < preroll_end:
        call(False)
    stats0 = dataclasses.asdict(cb.stats)
    live_open = len(live)
    with ctx.window() as w:
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < ctx.seconds:
            call(True)
        t_close = time.perf_counter()
    peak = ctx.memory_peak()
    stats1 = dataclasses.asdict(cb.stats)
    window_s = t_close - t_open

    metrics = {"setup_s": w.setup_s,
               "tokens_per_s": acc["tokens"] / window_s}
    sent = [tr for tr in tracks.values() if t_open <= tr.due < t_close]
    if open_loop:
        ttft = [(tr.first if tr.first < t_close else t_close) - tr.due
                for tr in sent]
        tpot = [(tr.last - tr.win_first) / (tr.in_window - 1) * 1e3
                for tr in sent if tr.in_window > 1]
        if ttft:
            metrics["ttft_p90_s"] = float(np.percentile(ttft, 90))
        if tpot:
            metrics["tpot_p90_ms"] = float(np.percentile(tpot, 90))
        print(f"requests sent in the window: {len(sent)}; with two or "
              f"more tokens: {len(tpot)}", file=sys.stderr)
    served = [tr for tr in tracks.values() if tr.in_window]
    attempted = len(sent) if open_loop else len(served)
    counts = dict(acc, window_s=window_s,
                  stats={k: stats1[k] - stats0[k] for k in stats0
                         if isinstance(stats0[k], (int, float))},
                  num_slots=eng["num_slots"], chunk_steps=eng["chunk_steps"],
                  live_open=live_open, live_close=len(live))
    print(f"window {window_s:.3f} s: {acc['calls']} calls, "
          f"{acc['tokens']} tokens, {counts['stats'].get('chunks')} chunks, "
          f"{len(served)} requests served, peak {peak} B", file=sys.stderr)

    sample = pick_sample(served, ctx.seed)
    seqs = [(np.asarray(tr.spec.prompt), np.asarray(tr.req.output))
            for tr in sample]
    del cb, tracks, live, served, sample, sent
    gc.collect()
    t = time.perf_counter()
    gap, n_tok = widest_gap(ctx, seqs)
    print(f"reference over {n_tok} served tokens: "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    limit = ctx.cfg["limits"]["logit_gap"]
    out = {"attempted": attempted, "failed": 0, "metrics": metrics,
           "memory_peak_bytes": int(peak), "compiles": w.compiles,
           "counts": counts, "checks": {"logit_gap": (gap, limit)}}
    if control:
        out["control_checks"] = {
            "logit_gap": (widest_gap(ctx, seqs, True)[0], limit)}
    return out


def pick_sample(served, seed: int):
    """The longest served request and SAMPLE others drawn from the seed."""
    if not served:
        return []
    longest = max(served, key=lambda tr: len(tr.spec.prompt)
                  + len(tr.req.output))
    rest = [tr for tr in served if tr is not longest]
    rng = traffic.seed_rng(seed, 4)
    k = min(SAMPLE, len(rest))
    return [longest] + [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                           replace=False))]


def padded_len(ctx) -> int:
    """A slot's capacity: the longest sequence the engine can hold (a
    request's last token, which no step attends over, may lie past it)."""
    e = ctx.cfg["engine"]
    return e["max_pages_per_slot"] * e["page_size"]


def widest_gap(ctx, seqs, control: bool = False):
    """Widest reference-logit gap over every served token of `seqs`, with
    weights made again from the seed. With `control`, the gap of the token
    the float8 forward puts first at the same positions. Returns
    (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp
    from reference import decoder

    m = ctx.cfg["model"]
    params = weights.make(m, ctx.seed)
    fn = decoder.control_gaps if control else decoder.gaps
    f = jax.jit(functools.partial(fn, m))
    T = padded_len(ctx)
    worst, n_tok = 0.0, 0
    for prompt, out in seqs:
        toks = np.concatenate([prompt, out])[:T].astype(np.int32)
        n = len(toks)
        g = np.asarray(f(params, jnp.asarray(np.pad(toks, (0, T - n)))))
        rows = g[len(prompt) - 1:n - 1]
        if not np.isfinite(rows).all():
            return float("inf"), n_tok
        worst = max(worst, float(rows.max(initial=0.0)))
        n_tok += len(rows)
    return worst, n_tok
