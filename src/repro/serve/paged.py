"""Paged KV-cache serving: free-list page allocator + device-resident batcher.

This is the production face of the paper's occupancy analysis: KV memory is
allocated in fixed-size pages rather than dense ``max_len`` slabs, so a
slot's resident bytes track its *true* context length (quantized to one
page), GQA shrinks the page itself, and fragmentation / page residency
become first-class time-resolved signals. Three pieces:

  * :class:`PageAllocator` — host-side free list over the global page pool
    (page 0 is reserved as the null page inactive slots point at);
  * :class:`PagedKVLedger` — page accounting + page-granular
    `OccupancyTrace` emission (alloc/free events integrate to zero at
    drain; occupancy is always ``pages x page_bytes``);
  * :class:`PagedContinuousBatcher` — priority continuous batching (FIFO
    within a class; strictly-higher-priority arrivals may preempt) where
    the decode hot path is device-resident: one jitted ``lax.scan`` advances
    every slot ``chunk_steps`` tokens per host round-trip (donated cache
    buffers, no per-token sync), admission *maps the prompt's pages into
    the slot's table* instead of re-prefilling, and per-slot positions are
    exact — no max-length mask.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_gqa_decode import pages_per_block
from repro.kernels.quant import kv_dtype_spec
from repro.models.transformer import (init_paged_cache, prefix_tail_rows,
                                      self_spec_draft, write_prefill_to_pages)
from repro.obs.slo import (RequestTimeline, SLOSummary, SLOTracker,
                           attach_energy_percentiles)
from repro.obs.telemetry import (default_registry, host_span,
                                 noop_registry)
from repro.serve.scheduler import AdmissionQueue, Request, SchedulerStats
from repro.sim.trace import AccessStats, OccupancyTrace, TraceBundle


class OutOfPages(RuntimeError):
    """The page pool cannot cover a request's worst-case page demand."""


def page_bytes(cfg, page_size: int, kv_dtype_bytes: int = 2,
               scale_bytes_per_row: int = 0) -> int:
    """Bytes one KV page pins across all full-attention layers (K + V).

    `scale_bytes_per_row` adds the per-(token row, kv head) quantization
    scale storage (4 for int8's float32 per-row scales, 0 for float and
    scale-free fp8 pools) so quantized ledgers account the true physical
    footprint, scales included."""
    n_full = sum(1 for k in cfg.layer_kinds() if k == "full")
    b = n_full * 2 * page_size * cfg.kv_dim * kv_dtype_bytes
    if scale_bytes_per_row:
        b += n_full * 2 * page_size * cfg.num_kv_heads * scale_bytes_per_row
    return b


def pages_for(tokens: int, page_size: int) -> int:
    return max(0, -(-tokens // page_size))


# ---------------------------------------------------------------------------
# Allocator + ledger (host side, model-free — hypothesis-testable)
# ---------------------------------------------------------------------------

class PageAllocator:
    """LIFO free-list allocator over `num_pages` pages; page 0 is the
    reserved null page and is never handed out."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the null page)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._allocated: set = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return len(self._allocated)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfPages(f"requested {n} pages, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._allocated.update(out)
        return out

    def free(self, pages) -> None:
        for p in pages:
            if p not in self._allocated:
                raise ValueError(f"double free / foreign page {p}")
            self._allocated.remove(p)
            self._free.append(p)


class PagedKVLedger:
    """Per-slot page ownership + page-granular occupancy trace.

    Every `admit`/`grow` emits a positive delta of ``n_pages x page_bytes``
    on the trace at the given logical time, every `retire` the matching
    negative delta — so the integrated trace equals the allocator's
    outstanding pages at all times, and drains to zero."""

    def __init__(self, num_pages: int, page_bytes_: int,
                 page_size: Optional[int] = None):
        self.allocator = PageAllocator(num_pages)
        self.page_bytes = page_bytes_
        self.page_size = page_size
        self.trace = OccupancyTrace("kv", (num_pages - 1) * page_bytes_)
        self.slot_pages: Dict[int, List[int]] = {}
        # Speculative-decoding draft lane: per-slot private pages drawn from
        # the same allocator/page-id space as the target lane, accounted at
        # the draft model's (smaller) per-page byte width.
        self.draft_pages: Dict[int, List[int]] = {}
        self.draft_page_bytes: Optional[int] = None
        # optional streaming energy meter (obs.energy.BankEnergyMeter):
        # every trace delta is mirrored to it, tagged with the slot's
        # request/tenant and the ledger verb that caused it
        self.meter = None
        self.slot_meta: Dict[int, tuple] = {}

    def set_slot_meta(self, slot: int, rid, tenant=None) -> None:
        """Tag a slot so mirrored meter events attribute to its request."""
        self.slot_meta[slot] = (rid, tenant)

    def _mark(self, t: float, delta: int, slot: int,
              cause: Optional[str]) -> None:
        self.trace.event(t, delta, 0)
        if self.meter is not None:
            rid, tenant = self.slot_meta.get(slot, (None, None))
            self.meter.record(t, delta, 0, rid=rid, tenant=tenant,
                              cause=cause)

    def occupancy_bytes(self) -> int:
        nd = sum(len(p) for p in self.draft_pages.values())
        db = (self.draft_page_bytes if self.draft_page_bytes is not None
              else self.page_bytes)
        return (self.allocator.n_allocated - nd) * self.page_bytes + nd * db

    def logical_bytes(self) -> int:
        """Without sharing, logical (per-slot demand) == physical bytes."""
        return self.occupancy_bytes()

    def admit(self, slot: int, n_pages: int, t: float) -> List[int]:
        assert slot not in self.slot_pages, f"slot {slot} already admitted"
        pages = self.allocator.alloc(n_pages)
        self.slot_pages[slot] = list(pages)
        if n_pages:
            self._mark(t, n_pages * self.page_bytes, slot, "admission")
        return pages

    def grow(self, slot: int, total_pages: int, t: float,
             cause: str = "decode_growth") -> List[int]:
        have = self.slot_pages[slot]
        extra = total_pages - len(have)
        if extra <= 0:
            return []
        pages = self.allocator.alloc(extra)
        have.extend(pages)
        self._mark(t, extra * self.page_bytes, slot, cause)
        return pages

    def retire(self, slot: int, t: float) -> int:
        pages = self.slot_pages.pop(slot)
        self.allocator.free(pages)
        if pages:
            self._mark(t, -len(pages) * self.page_bytes, slot, None)
        dpages = self.draft_pages.pop(slot, [])
        if dpages:
            self.allocator.free(dpages)
            db = (self.draft_page_bytes if self.draft_page_bytes is not None
                  else self.page_bytes)
            self._mark(t, -len(dpages) * db, slot, None)
        self.slot_meta.pop(slot, None)
        return len(pages) + len(dpages)

    # ------------------------------------------------- speculative draft lane
    def enable_draft_lane(self, draft_page_bytes: int) -> None:
        """Declare the byte width of draft-lane pages (the draft model's
        per-page KV footprint)."""
        self.draft_page_bytes = int(draft_page_bytes)

    def admit_draft(self, slot: int, n_pages: int, t: float) -> List[int]:
        assert slot not in self.draft_pages, \
            f"slot {slot} already has a draft lane"
        pages = self.allocator.alloc(n_pages)
        self.draft_pages[slot] = list(pages)
        db = (self.draft_page_bytes if self.draft_page_bytes is not None
              else self.page_bytes)
        if n_pages:
            self._mark(t, n_pages * db, slot, "admission")
        return pages

    def grow_draft(self, slot: int, total_pages: int, t: float) -> List[int]:
        have = self.draft_pages[slot]
        extra = total_pages - len(have)
        if extra <= 0:
            return []
        pages = self.allocator.alloc(extra)
        have.extend(pages)
        db = (self.draft_page_bytes if self.draft_page_bytes is not None
              else self.page_bytes)
        self._mark(t, extra * db, slot, "decode_growth")
        return pages

    def truncate_rows(self, slot: int, n_rows: int, t: float
                      ) -> "Tuple[List[int], List[int]]":
        """Rollback-by-page-truncation: free every page past
        `pages_for(n_rows)` in both lanes (target + draft). The negative
        mid-stream trace deltas this emits are the speculative-rollback
        occupancy signature. Returns the (target, draft) pages freed."""
        if self.page_size is None:
            raise ValueError("truncate_rows needs a ledger page_size")
        keep = pages_for(n_rows, self.page_size)
        freed_t: List[int] = []
        have = self.slot_pages[slot]
        if keep < len(have):
            freed_t = have[keep:]
            del have[keep:]
            self.allocator.free(freed_t)
            self._mark(t, -len(freed_t) * self.page_bytes, slot,
                       "spec_rollback")
        freed_d: List[int] = []
        dhave = self.draft_pages.get(slot)
        if dhave is not None and keep < len(dhave):
            freed_d = dhave[keep:]
            del dhave[keep:]
            self.allocator.free(freed_d)
            db = (self.draft_page_bytes if self.draft_page_bytes is not None
                  else self.page_bytes)
            self._mark(t, -len(freed_d) * db, slot, "spec_rollback")
        return freed_t, freed_d


# ---------------------------------------------------------------------------
# Device decode loop
# ---------------------------------------------------------------------------

# traced once per XLA compilation of the chunk loop — tests assert the
# continuous batcher never recompiles it across chunks/admissions; counted
# on the process-wide registry (loop_compile_count() is the shim view)
_COMPILES = default_registry().counter("serve.paged.loop_compiles")


def _decode_loop(model, steps: int, attn_backend: str, collect_logits: bool,
                 params, cache, tok, eos, remaining):
    """Greedy multi-token decode: `steps` tokens for every slot in one
    on-device `lax.scan`. Slots retire in-scan (EOS or token budget) via the
    cache's `active` mask; inactive lanes emit -1 and stop advancing. With
    `collect_logits` the scan additionally emits every step's last-position
    logits (exactness debugging / the bit-identity regression)."""
    _COMPILES.inc()

    def step(carry, _):
        cache, tok, remaining = carry
        logits, cache = model.decode_step_paged(params, cache, tok,
                                                attn_backend=attn_backend)
        active = cache["active"]
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        emit = jnp.where(active, nxt, -1)
        remaining = remaining - active.astype(jnp.int32)
        done = active & ((remaining <= 0) | ((eos >= 0) & (nxt == eos)))
        cache = dict(cache)
        cache["active"] = active & ~done
        tok = jnp.where(active[:, None], nxt[:, None], tok)
        out = (emit, logits[:, -1, :]) if collect_logits else emit
        return (cache, tok, remaining), out

    (cache, tok, remaining), emitted = jax.lax.scan(
        step, (cache, tok, remaining), None, length=steps)
    return emitted, cache, tok, remaining


def _spec_decode_loop(model, draft_model, rounds, spec_k, attn_backend,
                      params, draft_params, cache, draft_cache, tok, eos,
                      remaining):
    """Speculative greedy decode: `rounds` draft-then-verify rounds in one
    on-device `lax.scan`. Each round the draft proposes `spec_k` tokens
    (sequential small-model decode over its own page lane), the target
    scores the pending token plus all candidates in ONE batched
    `verify_step_paged` call (V = spec_k + 1 rows), and the longest
    accepted prefix advances both lanes' positions. Every emitted token is
    the TARGET's argmax — draft quality moves the acceptance rate, never
    the output, so the accepted stream is bit-identical to `_decode_loop`.
    A rejected suffix "rolls back" by pos arithmetic alone: its rows sit
    past `pos` as garbage the next round overwrites before reading.

    Emits a (rounds, num_slots, V) block of accepted tokens, -1 padded:
    within a round the accepted prefix is contiguous from column 0, and
    rounds after a slot retires are all -1, so ravel-and-filter recovers
    the stream in order."""
    _COMPILES.inc()
    V = spec_k + 1

    def round_step(carry, _):
        cache, dcache, tok, remaining = carry
        active = cache["active"]
        pos0 = dcache["pos"]

        def draft_step(dc, _):
            dcache, dtok = dc
            dlogits, dcache = draft_model.decode_step_paged(
                draft_params, dcache, dtok, attn_backend=attn_backend)
            nxt = jnp.argmax(dlogits[:, -1, :], axis=-1).astype(jnp.int32)
            dtok = jnp.where(active[:, None], nxt[:, None], dtok)
            return (dcache, dtok), nxt

        (dcache, dtok), drafted = jax.lax.scan(
            draft_step, (dcache, tok), None, length=spec_k)
        # catch-up: write the last candidate's draft KV row so a fully
        # accepted round leaves no hole in the draft lane (logits discarded)
        _, dcache = draft_model.decode_step_paged(
            draft_params, dcache, dtok, attn_backend=attn_backend)
        drafted = drafted.reshape(spec_k, -1).T            # (B, k)
        cand = jnp.concatenate([tok, drafted], axis=1)     # (B, V)
        vlogits, cache = model.verify_step_paged(
            params, cache, cand, attn_backend=attn_backend)
        g = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # (B, V) target
        # candidate v+1 survives iff it equals the target's continuation g_v
        match = (drafted == g[:, :spec_k]).astype(jnp.int32)
        m_full = 1 + jnp.cumprod(match, axis=1).sum(axis=1)      # in [1, V]
        eos_hit = (eos[:, None] >= 0) & (g == eos[:, None])
        first_eos = jnp.where(eos_hit.any(axis=1),
                              jnp.argmax(eos_hit, axis=1).astype(jnp.int32),
                              jnp.int32(V))
        m = jnp.minimum(jnp.minimum(m_full, first_eos + 1), remaining)
        m = jnp.where(active, m, 0)
        col = jnp.arange(V, dtype=jnp.int32)[None, :]
        emit = jnp.where(col < m[:, None], g, -1)
        new_tok = jnp.take_along_axis(g, jnp.maximum(m - 1, 0)[:, None],
                                      axis=1)
        tok = jnp.where(active[:, None], new_tok, tok)
        remaining = remaining - m
        eos_fired = eos_hit.any(axis=1) & (first_eos < m)
        done = active & ((remaining <= 0) | eos_fired)
        cache = dict(cache)
        dcache = dict(dcache)
        cache["pos"] = cache["pos"] + m
        dcache["pos"] = pos0 + m          # rollback: rejected rows orphaned
        cache["active"] = active & ~done
        dcache["active"] = cache["active"]
        return (cache, dcache, tok, remaining), emit

    (cache, draft_cache, tok, remaining), emitted = jax.lax.scan(
        round_step, (cache, draft_cache, tok, remaining), None, length=rounds)
    return emitted, cache, draft_cache, tok, remaining


# ---------------------------------------------------------------------------
# Continuous batcher
# ---------------------------------------------------------------------------

@dataclass
class PagedStats(SchedulerStats):
    pages_allocated: int = 0
    pages_freed: int = 0
    peak_pages: int = 0
    chunks: int = 0
    # prefix-sharing counters (stay zero without prefix_cache)
    cow_splits: int = 0
    evicted_pages: int = 0
    # chunked-prefill slices executed (zero without prefill_chunk_tokens)
    prefill_slices: int = 0
    # speculative-decoding counters (stay zero without speculate_k)
    spec_rounds: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    rolled_back_pages: int = 0
    # wall-clock counters, taken at the boundaries of the host_span of the
    # same name (serve.run, serve.prefill, serve.chunk.wait) on
    # time.perf_counter, the clock a profiler trace's host lines use
    run_wall_s: float = 0.0          # inside run()
    device_wait_s: float = 0.0       # of which blocked on device results
    queue_wait_s: float = 0.0        # sum of admission start - submit
    prefill_wall_s: float = 0.0      # serve.prefill spans
    prefill_tokens: int = 0          # prompt tokens those spans prefilled
    first_token_hold_s: float = 0.0  # sum of run() return - first token
    page_waits: int = 0              # admission passes stopped on pages
    # compute blocks of the paged decode kernel (bf16 / fp8 pages), summed
    # over decode steps: blocks it ran (those holding a live row of an
    # active slot) and blocks in its grid (slots x table blocks)
    attn_blocks_live: int = 0
    attn_blocks_total: int = 0


class PagedContinuousBatcher:
    """Priority continuous batching over a paged KV cache.

    Admission pops the highest-priority queued request (FIFO within a
    class). When the head would otherwise wait — no free slot, or the pool
    cannot cover its worst-case pages — it may *preempt* strictly-lower-
    priority active slots: the victim's pages free through the retire path,
    its partial output is discarded, and the request requeues behind its
    own class for a from-scratch re-prefill (greedy restart keeps its
    tokens bit-identical to an uncontended run). Equal priorities never
    preempt each other, so the default ``priority=0`` workload behaves
    exactly like the old FCFS batcher.

    Chunked prefill (``prefill_chunk_tokens``, pure full-attention stacks):
    prompts longer than the chunk admit in page-aligned slices with one
    decode chunk for the other slots interleaved between slices, so a long
    prompt stops stalling every active stream's time-between-tokens. Slices
    chain through the shared-prefix machinery (gather resident pages →
    suffix-only prefill at fixed attention width), which keeps the emitted
    tokens bit-identical to one monolithic prefill. Composes with
    ``prefix_cache``: on a prefix hit only the *suffix* past the match is
    chunked — the first slice is sized to re-align the (possibly mid-page)
    match boundary to a page multiple, every later slice gathers the
    slot's own resident pages, and the fixed-attention-width property
    keeps the result bit-identical to the monolithic suffix prefill.

    Speculative decoding (``speculate_k``, pure full-attention stacks,
    greedy only): a draft model proposes `speculate_k` tokens per round
    through its own *draft page lane* (same allocator/page-id space,
    smaller per-page bytes), the target scores the pending token plus all
    candidates in one batched ``paged_gqa_verify`` kernel call, and the
    longest target-agreeing prefix is accepted. Every emitted token is the
    target's argmax, so the accepted stream is bit-identical to the
    non-speculative loop — the draft only moves the acceptance rate, i.e.
    accepted-tokens/s. Rejected suffixes roll back by page truncation at
    chunk boundaries (`ledger.truncate_rows`): both lanes' tail pages past
    the accepted context free mid-stream, which is the negative-delta
    occupancy signature Stage I sees. With ``draft_model=None`` the draft
    is `self_spec_draft(model, params, skip=2)` — the target's own weights
    at every 2nd layer.

    Admission prefills the prompt once (batch=1), then scatters its KV rows
    into freshly allocated pages of the global pool — older slots are never
    touched. Decode runs in device-resident chunks of `chunk_steps` tokens
    (one jitted, donated `lax.scan` per chunk; the host syncs once per chunk
    to collect tokens, retire finished slots, free their pages, and admit
    queued requests). A request is admitted only when the pool can cover its
    worst-case page demand (prompt + max_new_tokens), so growth allocations
    between chunks never fail mid-stream.

    Emits the same Stage-I artifact as `ContinuousBatcher`, but at page
    granularity: `occupancy_bundle()` is a `TraceBundle` whose "kv" trace
    steps in units of `page_bytes` — feed it to `core.explorer.sweep` /
    `core.candidates.evaluate_candidates` unchanged.

    Compile discipline: the chunk decode loop compiles exactly once (shapes
    are fixed by the pool geometry). Admission prefill, like the dense
    batcher's, still traces per distinct (prompt length, page count) — pad
    or bucket prompts client-side if admission latency matters. With
    `prefix_cache` the hit path traces per (matched length, suffix length)
    pair instead.

    Prefix sharing (`prefix_cache=True`, pure full-attention stacks only):
    admission probes a `RadixPrefixIndex` with the prompt, maps matched
    pages read-only into the slot's table, runs a *suffix-only* prefill
    against the gathered prefix KV (bit-exact vs the full prefill), and
    caches every admitted run for later requests. The last page of a shared
    run is copy-on-write split on the first divergent write; unreferenced
    cached prefixes are LRU-evicted under page pressure. The ledger then
    emits dual Stage-I traces — "kv" (physical: unique referenced pages,
    cache-resident pages as obsolete) and "kv_logical" (per-slot demand sum)
    — so Stage II can price the gating headroom sharing unlocks.
    """

    def __init__(self, model, params, *, num_slots: int = 4,
                 page_size: int = 16, num_pages: int = 64,
                 max_pages_per_slot: Optional[int] = None,
                 chunk_steps: int = 16, attn_backend: str = "auto",
                 step_time_s: float = 1e-3, prefill_tok_s: float = 5e-5,
                 prefix_cache: bool = False, collect_logits: bool = False,
                 kv_dtype: str = "native",
                 prefill_chunk_tokens: Optional[int] = None,
                 on_long_prompt: str = "reject",
                 speculate_k: Optional[int] = None, draft_model=None,
                 draft_params=None, telemetry=None, meter=None):
        if not hasattr(model, "decode_step_paged"):
            raise TypeError("model lacks a paged decode path")
        if on_long_prompt not in ("reject", "truncate"):
            raise ValueError("on_long_prompt must be 'reject' or 'truncate'")
        if prefill_chunk_tokens is not None:
            if prefill_chunk_tokens < page_size or \
                    prefill_chunk_tokens % page_size:
                raise ValueError(
                    "prefill_chunk_tokens must be a positive multiple of "
                    f"page_size={page_size} so every slice boundary is "
                    "page-aligned (the chained slice prefill gathers whole "
                    f"pages); got {prefill_chunk_tokens}")
        if speculate_k is not None:
            if speculate_k < 1:
                raise ValueError(f"speculate_k must be >= 1, got "
                                 f"{speculate_k}")
            if collect_logits:
                raise NotImplementedError(
                    "collect_logits emits one logits row per decode step; "
                    "the speculative loop emits V verify rows per round "
                    "(rejected rows included) — use the non-speculative "
                    "loop for logits-level debugging")
            if kv_dtype == "int8":
                raise NotImplementedError(
                    "speculative verify scatters V rows per slot; the int8 "
                    "page pool's per-row requantization under that scatter "
                    "is not wired up (fp8/native pools are)")
            if (draft_model is None) != (draft_params is None):
                raise ValueError("pass draft_model and draft_params "
                                 "together (or neither for self-spec)")
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages_per_slot = max_pages_per_slot or \
            max(1, (num_pages - 1) // max(1, num_slots))
        self.chunk_steps = chunk_steps
        self.step_time_s = step_time_s
        self.prefill_tok_s = prefill_tok_s
        self.prefix_cache = prefix_cache
        self.collect_logits = collect_logits
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.on_long_prompt = on_long_prompt
        self.speculate_k = speculate_k

        # spans and SLOs record on the batcher's logical sim clock — the
        # time base the ledger's occupancy trace uses — so a passed-in
        # registry has its clock bound here: the Perfetto export then shows
        # request spans and the KV counter track on one timeline. bind_clock
        # raises if another engine already owns the registry's clock (a
        # second batcher would silently corrupt the first one's timelines).
        # The serve.* host_spans and the wall counters of PagedStats are the
        # other clock: time.perf_counter, on the profiler's host line.
        self.tel = telemetry if telemetry is not None else noop_registry()
        if telemetry is not None:
            telemetry.bind_clock(lambda: self._sim_t, owner=self)
        tel = self.tel
        self._slo = (SLOTracker(tel, "serve.paged") if tel.enabled else None)
        self._c_admitted = tel.counter("serve.paged.admitted")
        self._c_retired = tel.counter("serve.paged.retired")
        self._c_prefills = tel.counter("serve.paged.prefills")
        self._c_chunks = tel.counter("serve.paged.chunks")
        self._c_steps = tel.counter("serve.paged.decode_steps")
        self._c_alloc = tel.counter("serve.paged.pages_allocated")
        self._c_freed = tel.counter("serve.paged.pages_freed")
        self._c_evicted = tel.counter("serve.paged.pages_evicted")
        self._c_cow = tel.counter("serve.paged.cow_splits")
        self._c_hits = tel.counter("serve.paged.prefix_hits")
        self._c_miss = tel.counter("serve.paged.prefix_misses")
        self._c_reused = tel.counter("serve.paged.prefix_tokens_reused")
        self._c_wait = tel.counter("serve.paged.backpressure_waits")
        self._c_preempt = tel.counter("serve.paged.preemptions")
        self._c_slices = tel.counter("serve.paged.prefill_slices")
        self._c_spec_rounds = tel.counter("serve.paged.spec_rounds")
        self._c_drafted = tel.counter("serve.paged.spec_drafted")
        self._c_accepted = tel.counter("serve.paged.spec_accepted")
        self._c_rollback = tel.counter("serve.paged.spec_rolled_back_pages")
        self._c_dequant = tel.counter("quant.dequant_pages")
        self._g_pages = tel.gauge("serve.paged.pages_in_use")
        self._g_kv_phys = tel.gauge("serve.paged.kv_bytes_physical")
        self._g_kv_logical = tel.gauge("serve.paged.kv_bytes_logical")

        kv_spec = kv_dtype_spec(kv_dtype, native=model.compute_dtype)
        self.kv_dtype = kv_spec.name
        self.kv_quantized = kv_spec.quantized
        self.page_bytes = page_bytes(self.cfg, page_size, kv_spec.itemsize,
                                     kv_spec.scale_bytes_per_row)
        self.row_bytes = self.page_bytes // page_size
        if prefix_cache:
            from repro.serve.prefix import SharedKVLedger
            self.ledger = SharedKVLedger(
                num_pages, self.page_bytes, page_size,
                num_slots=num_slots,
                max_pages_per_slot=self.max_pages_per_slot,
                telemetry=tel)
        else:
            self.ledger = PagedKVLedger(num_pages, self.page_bytes,
                                        page_size)
        # optional streaming BankEnergyMeter: rides the ledger so every page
        # event is mirrored on the same sim clock, tagged with the causing
        # request — the batcher only supplies slot->request metadata
        self.meter = meter
        self.ledger.meter = meter
        self.access = AccessStats()
        self.stats = PagedStats()
        ppb = pages_per_block(self.cfg.num_kv_heads, page_size,
                              self.cfg.head_dim, self.max_pages_per_slot)
        self._attn_block_rows = ppb * page_size
        self._attn_grid = num_slots * -(-self.max_pages_per_slot // ppb)
        # int8 pools go through the one-page kernel, which has no blocks
        self._attn_blocks = kv_spec.name != "int8"

        self.queue = AdmissionQueue()
        self.slots: List[Optional[Request]] = [None] * num_slots
        self._tokens_by_rid: Dict[int, int] = {}   # retired, for J/token
        self._reserved = [0] * num_slots        # worst-case pages not yet held
        self._ctx = np.zeros(num_slots, np.int64)
        self._next_tok = np.zeros(num_slots, np.int32)
        self._table = np.zeros((num_slots, self.max_pages_per_slot), np.int32)
        self._sim_t = 0.0
        # first-token wall stamps of this run(), held until it returns
        self._first_tokens: List[float] = []

        self._cache = init_paged_cache(
            self.cfg, num_slots, num_pages, page_size,
            self.max_pages_per_slot, dtype=model.compute_dtype,
            kv_dtype=self.kv_dtype)
        self._prefill = jax.jit(
            lambda p, b, L: model.prefill(p, b, cache_len=L),
            static_argnums=(2,))
        self._write = jax.jit(functools.partial(write_prefill_to_pages,
                                                self.cfg),
                              donate_argnums=(0,))
        self._loop = jax.jit(
            functools.partial(_decode_loop, model, chunk_steps, attn_backend,
                              collect_logits),
            donate_argnums=(1,))
        if prefix_cache or prefill_chunk_tokens is not None:
            from repro.models.transformer import (_require_pure_full,
                                                  gather_prefix_pages,
                                                  write_shared_prefill_to_pages)
            _require_pure_full(model.cfg, "prefix_cache" if prefix_cache
                               else "prefill_chunk_tokens")
            self._gather = jax.jit(
                functools.partial(gather_prefix_pages, self.cfg),
                static_argnums=(2,))
            # fixed attention width = slot capacity: makes the suffix
            # prefill's reduction tree independent of who computed the
            # prefix (donor-exact KV, see _apply_block_shared_prefill) —
            # the same property makes chained chunked-prefill slices
            # bit-exact vs one monolithic prefill
            pad_to = self.max_pages_per_slot * page_size
            self._prefill_shared = jax.jit(
                lambda p, t, pfx: model.prefill_shared(
                    p, {"tokens": t}, pfx, pad_to=pad_to))
            self._write_shared = jax.jit(
                functools.partial(write_shared_prefill_to_pages, self.cfg),
                donate_argnums=(0,))
        if prefix_cache:
            from repro.models.transformer import copy_pages
            self._copy = jax.jit(functools.partial(copy_pages, self.cfg),
                                 donate_argnums=(0,))
        if speculate_k is not None:
            from repro.models.transformer import _require_pure_full
            _require_pure_full(model.cfg, "speculate_k")
            if draft_model is None:
                draft_model, draft_params = self_spec_draft(model, params,
                                                            skip=2)
            self.draft_model = draft_model
            self.draft_params = draft_params
            dcfg = draft_model.cfg
            self.draft_page_bytes = page_bytes(dcfg, page_size,
                                               kv_spec.itemsize,
                                               kv_spec.scale_bytes_per_row)
            self.draft_row_bytes = self.draft_page_bytes // page_size
            self.ledger.enable_draft_lane(self.draft_page_bytes)
            self._draft_table = np.zeros(
                (num_slots, self.max_pages_per_slot), np.int32)
            # the draft lane's pool arrays are indexed by the SAME page ids
            # as the target's (one allocator, one id space), so the draft
            # cache spans the full pool too — at the draft's smaller dims
            self._draft_cache = init_paged_cache(
                dcfg, num_slots, num_pages, page_size,
                self.max_pages_per_slot, dtype=draft_model.compute_dtype,
                kv_dtype=self.kv_dtype)
            self.spec_rounds_per_chunk = max(
                1, chunk_steps // (speculate_k + 1))
            # sim-clock cost of one draft-then-verify round vs one plain
            # decode step: the batched verify streams the target weights
            # once (~= one step), plus k+1 sequential draft steps at the
            # draft's layer fraction
            self.draft_cost_frac = (dcfg.num_layers
                                    / max(1, self.cfg.num_layers))
            self.spec_round_time_s = step_time_s * (
                1.0 + (speculate_k + 1) * self.draft_cost_frac)
            self._draft_prefill = jax.jit(
                lambda p, b, L: draft_model.prefill(p, b, cache_len=L),
                static_argnums=(2,))
            self._draft_write = jax.jit(
                functools.partial(write_prefill_to_pages, dcfg),
                donate_argnums=(0,))
            self._spec_loop = jax.jit(
                functools.partial(_spec_decode_loop, model, draft_model,
                                  self.spec_rounds_per_chunk, speculate_k,
                                  attn_backend),
                donate_argnums=(2, 3))

    # ------------------------------------------------------------ client API
    def submit(self, req: Request) -> None:
        S = int(len(req.tokens))
        cap = self.max_pages_per_slot * self.page_size
        # speculation writes up to V - 1 = speculate_k rows past the final
        # accepted context before the last rollback truncates them
        spec_extra = (self.speculate_k if self.speculate_k is not None
                      and req.max_new_tokens > 1 else 0)
        if S + max(req.max_new_tokens - 1, 0) + spec_extra > cap \
                and self.on_long_prompt == "truncate":
            # keep the decode budget, give the prompt whatever table
            # capacity remains (mirrors the dense batcher's max_len cut)
            keep = cap - max(req.max_new_tokens - 1, 0) - spec_extra
            if keep >= 1:
                req.tokens = np.asarray(req.tokens)[:keep]
                S = keep
        worst = pages_for(S + max(req.max_new_tokens - 1, 0) + spec_extra,
                          self.page_size)
        # speculation doubles the lane count: the draft mirrors the target's
        # page demand row-for-row (same page_size, smaller page_bytes)
        lanes = 2 if self.speculate_k is not None else 1
        # prefix mode reserves one extra pool page for the deferred COW
        # split of a mid-page prompt boundary; it never occupies a table
        # slot (COW swaps an entry in place), but it must fit the pool or
        # admission could wait forever on a demand no drain can satisfy
        pool_worst = worst * lanes + (
            1 if self.prefix_cache and S % self.page_size
            and req.max_new_tokens > 1 else 0)
        if worst > self.max_pages_per_slot or pool_worst > self.num_pages - 1:
            raise OutOfPages(
                f"request {req.rid} needs {worst} table / {pool_worst} pool "
                f"pages; slot tables hold {self.max_pages_per_slot}, pool "
                f"holds {self.num_pages - 1}")
        req.submitted_wall_s = time.perf_counter()
        req.submitted_s = self._sim_t
        if self.tel.enabled:
            req.timeline = RequestTimeline(rid=req.rid, submit_t=self._sim_t)
        self.queue.push(req)

    def run(self, max_chunks: int = 10_000) -> List[Request]:
        done: List[Request] = []
        with host_span("serve.run", self.stats, "run_wall_s"):
            for _ in range(max_chunks):
                if not self.queue and all(s is None for s in self.slots):
                    break
                with host_span("serve.admit"):
                    self._admit(done)
                self._decode_chunk(done)
            if self._slo is not None:
                self.slo_summary()       # refresh stats percentiles once
        t = time.perf_counter()
        self.stats.first_token_hold_s += sum(t - f for f in self._first_tokens)
        self._first_tokens.clear()
        return done

    def slo_summary(self) -> SLOSummary:
        """Percentile view of per-request TTFT / TBT / e2e on the sim clock
        (zeros when the batcher runs without an enabled registry). Quantiles
        are computed here, at read time — never inside the decode loop, so
        enabled telemetry stays off the serving hot path."""
        if self._slo is None:
            return SLOSummary()
        s = self._slo.summary()
        # bytes-based physical occupancy (page count x quantized page_bytes)
        # next to the latency percentiles — page counts alone hide the
        # footprint reduction a quantized kv_dtype buys
        s.kv_peak_bytes = float(self.ledger.trace.peak_needed())
        s.kv_mean_bytes = float(self.ledger.trace.time_weighted_mean(
            max(self._sim_t, self.step_time_s)))
        st = self.stats
        st.ttft_p50_s, st.ttft_p99_s = s.ttft_p50_s, s.ttft_p99_s
        st.tbt_p50_s, st.tbt_p99_s = s.tbt_p50_s, s.tbt_p99_s
        st.e2e_p50_s, st.e2e_p99_s = s.e2e_p50_s, s.e2e_p99_s
        if self.meter is not None:
            attach_energy_percentiles(s, self.meter.request_energy_j(),
                                      self._tokens_by_rid)
        return s

    def occupancy_bundle(self) -> TraceBundle:
        """Page-granular Stage-II view: feed to explorer.sweep() unchanged.

        With `prefix_cache` the bundle carries the dual traces: "kv" is the
        *physical* occupancy (unique referenced pages as needed, cached
        pages as obsolete — what Stage II should gate against) and
        "kv_logical" the per-slot demand sum a non-sharing allocator would
        pin; their gap is the headroom sharing unlocked."""
        traces = {"kv": self.ledger.trace}
        name = f"{self.cfg.name}-paged-serve"
        if self.prefix_cache:
            traces["kv_logical"] = self.ledger.logical
            name = f"{self.cfg.name}-prefix-serve"
        return TraceBundle(graph_name=name,
                           total_time=max(self._sim_t, self.step_time_s),
                           traces=traces, access=self.access)

    # ------------------------------------------------------------- internals
    def _available_pages(self) -> int:
        return self.ledger.allocator.n_free - sum(self._reserved)

    def _worst_pages(self, S: int, max_new: int) -> int:
        """Worst-case page demand of one lane for a prompt of `S` tokens:
        prompt rows + decode rows + the up-to-`speculate_k` overshoot rows a
        verify window can write past the final accepted context."""
        extra = (self.speculate_k if self.speculate_k is not None
                 and max_new > 1 else 0)
        return pages_for(S + max(max_new - 1, 0) + extra, self.page_size)

    @property
    def _lanes(self) -> int:
        return 2 if self.speculate_k is not None else 1

    def _dequeue(self) -> Request:
        """Pop the queue head: its admission starts now."""
        req = self.queue.pop()
        self.stats.queue_wait_s += time.perf_counter() - req.submitted_wall_s
        return req

    def _first_token(self, logits) -> int:
        """The prefill's greedy token, read back to the host (the one sync
        of an admission, counted as device wait)."""
        t = time.perf_counter()
        tok = int(jnp.argmax(logits[0, -1]))
        self.stats.device_wait_s += time.perf_counter() - t
        return tok

    def _set_page_gauges(self) -> None:
        """Page-count plus bytes-based occupancy gauges: physical = pool
        pages held x page_bytes (quantization shrinks page_bytes), logical =
        the per-slot demand a non-sharing allocator would pin."""
        n = self.ledger.allocator.n_allocated
        self._g_pages.set(n)
        self._g_kv_phys.set(n * self.page_bytes)
        self._g_kv_logical.set(self.ledger.logical_bytes())

    def _retire(self, i: int, req: Request, done: List[Request],
                t: float) -> None:
        req.finished_wall_s = time.perf_counter()
        req.finished_s = t
        done.append(req)
        self.slots[i] = None
        n = self.ledger.retire(i, t)
        self.stats.pages_freed += n
        self.stats.retired_kv_bytes += n * self.page_bytes
        self.stats.finished += 1
        self._reserved[i] = 0
        self._ctx[i] = 0
        self._table[i, :] = 0
        if self.speculate_k is not None:
            self._draft_table[i, :] = 0
        self._c_retired.inc()
        self._c_freed.inc(n)
        self._set_page_gauges()
        if self.meter is not None:
            self._tokens_by_rid[req.rid] = len(req.output)
        tl = req.timeline
        if tl is not None and self.meter is not None:
            # final at retire: the request holds no pages past this event,
            # so no later charge can land on it
            tl.energy_j = self.meter.request_energy_live(req.rid)
        if tl is not None and self._slo is not None:
            tl.finish_t = t
            self._slo.observe(tl)
            self.tel.add_span("request", tl.submit_t, t, rid=req.rid,
                              tokens=len(req.output))
            if np.isfinite(tl.first_token_t) and t > tl.first_token_t:
                self.tel.add_span("decode", tl.first_token_t, t, slot=i,
                                  rid=req.rid)

    def _preempt_victim(self, priority: int) -> Optional[int]:
        """Pick the slot to evict for a `priority`-class admission: the
        lowest-priority active slot strictly below the admitting class
        (equal classes never preempt each other — no livelock), least
        decode progress first within a class (least work discarded)."""
        best = None
        best_key = None
        for i, r in enumerate(self.slots):
            if r is None or r.priority >= priority:
                continue
            key = (r.priority, len(r.output))
            if best is None or key < best_key:
                best, best_key = i, key
        return best

    def _preempt(self, i: int, t: float) -> None:
        """Evict slot `i` and requeue its request. Pages return through the
        ordinary retire path (the occupancy trace stays conservative); the
        partial output is discarded and the prompt re-prefills from scratch
        on re-admission — resuming mid-decode would not be bit-exact (the
        prefill reduction tree differs from the decode kernel's), while a
        greedy restart reproduces the uncontended tokens exactly."""
        req = self.slots[i]
        req.output.clear()
        req.logits.clear()
        req.preemptions += 1
        self.slots[i] = None
        n = self.ledger.retire(i, t)
        self.stats.pages_freed += n
        self.stats.retired_kv_bytes += n * self.page_bytes
        self.stats.preemptions += 1
        self._reserved[i] = 0
        self._ctx[i] = 0
        self._table[i, :] = 0
        if self.speculate_k is not None:
            self._draft_table[i, :] = 0
        self._c_preempt.inc()
        self._c_freed.inc(n)
        self._set_page_gauges()
        if req.timeline is not None:
            req.timeline.reset_admission()
        if self.tel.enabled:
            self.tel.add_span("preempt", t, t, slot=i, rid=req.rid)
        self.queue.push(req)     # fresh seq: re-enters behind its own class

    def _preempt_for(self, priority: int, worst: int) -> bool:
        """Free pages for a `priority`-class admission by preempting
        strictly-lower-priority slots, lowest class / least progress first.
        Returns False when eligible victims run out before `worst` pages
        are coverable (the head then backpressure-waits as before)."""
        while worst > self._available_pages():
            v = self._preempt_victim(priority)
            if v is None:
                return False
            self._preempt(v, self._sim_t)
        return True

    def _admit(self, done: List[Request]) -> None:
        while self.queue:
            i = next((k for k, s in enumerate(self.slots) if s is None), None)
            if i is None:
                # every slot is busy: a strictly-higher-priority head may
                # evict the lowest-priority slot instead of queueing
                v = self._preempt_victim(self.queue.peek().priority)
                if v is None:
                    break
                self._preempt(v, self._sim_t)
                continue
            if self.prefix_cache:
                if not self._admit_prefix(i, done):
                    break                  # wait for pages to free up
                continue
            req = self.queue.peek()
            prompt_len = int(len(req.tokens))
            worst = self._worst_pages(prompt_len, req.max_new_tokens) \
                * self._lanes
            if worst > self._available_pages() \
                    and not self._preempt_for(req.priority, worst):
                self._c_wait.inc()
                self.stats.page_waits += 1
                break                      # wait for pages to free up
            self._dequeue()
            if (self.prefill_chunk_tokens is not None
                    and prompt_len > self.prefill_chunk_tokens):
                self._admit_chunked(i, req, done, worst)
                continue
            npg = pages_for(prompt_len, self.page_size)
            t_pre = self._sim_t

            with host_span("serve.prefill", self.stats, "prefill_wall_s",
                           rid=req.rid, tokens=prompt_len):
                batch = {"tokens": jnp.asarray(
                    np.asarray(req.tokens)[None, :], jnp.int32)}
                logits, dense = self._prefill(self.params, batch,
                                              npg * self.page_size)
                tok = self._first_token(logits)
                self._sim_t += prompt_len * self.prefill_tok_s
                if self.meter is not None:
                    self.ledger.set_slot_meta(i, req.rid, req.tenant)
                pages = self.ledger.admit(i, npg, self._sim_t)
                self._reserved[i] = worst - npg
                self.stats.pages_allocated += npg
                self.stats.peak_pages = max(self.stats.peak_pages,
                                            self.ledger.allocator.n_allocated)
                self.stats.admitted_kv_bytes += npg * self.page_bytes
                self.access.add_write("kv", prompt_len * self.row_bytes)
                self._c_alloc.inc(npg)

                self._cache = self._write(self._cache, dense, i,
                                          jnp.asarray(pages, jnp.int32))
            self.stats.prefill_tokens += prompt_len
            self._commit_admission(i, req, done, tok, logits, prompt_len,
                                   pages, t_pre)

    def _admit_chunked(self, i: int, req: Request, done: List[Request],
                       worst: int) -> None:
        """Chunked prefill: admit `req` into slot `i` in page-aligned
        slices of `prefill_chunk_tokens`, running one decode chunk for the
        other active slots between consecutive slices so a long prompt no
        longer stalls their token cadence. Slice 0 is a plain prefill;
        every later slice gathers the slot's own pages as a prefix and runs
        the suffix-only shared prefill at fixed attention width — the
        donor-exact property from prefix sharing, so the emitted tokens are
        bit-identical to one monolithic prefill. The slot stays invisible
        to the decode loop (host `active` mask) until the last slice
        commits; the page reservation made up-front keeps interleaved
        chunks from stealing this slot's worst-case pages.

        Tracing: each distinct (resident rows, slice length) pair traces
        once — every slice but the last is exactly `prefill_chunk_tokens`
        long, so long prompts bucket naturally."""
        prompt = np.asarray(req.tokens)
        S = int(len(prompt))
        ps = self.page_size
        C = self.prefill_chunk_tokens
        t_pre = self._sim_t
        pos = 0
        while pos < S:
            take = min(C, S - pos)
            # one serve.prefill span per slice: the decode chunks between
            # slices are the other slots' time, not this prefill's
            with host_span("serve.prefill", self.stats, "prefill_wall_s",
                           rid=req.rid, tokens=take):
                sl = jnp.asarray(prompt[None, pos:pos + take], jnp.int32)
                t0 = self._sim_t
                if pos == 0:
                    new_n = pages_for(take, ps)
                    logits, dense = self._prefill(
                        self.params, {"tokens": sl}, new_n * ps)
                    self._sim_t += take * self.prefill_tok_s
                    if self.meter is not None:
                        self.ledger.set_slot_meta(i, req.rid, req.tenant)
                    pages = self.ledger.admit(i, new_n, self._sim_t)
                    self._reserved[i] = worst - new_n
                    self._cache = self._write(self._cache, dense, i,
                                              jnp.asarray(pages, jnp.int32))
                else:
                    held = list(self.ledger.slot_pages[i])
                    prefix = self._gather(self._cache,
                                          jnp.asarray(held, jnp.int32), pos)
                    if self.kv_quantized:
                        self._c_dequant.inc(len(held))
                    head = prefix_tail_rows(prefix, 0)  # pos is page-aligned
                    logits, suffix = self._prefill_shared(self.params, sl,
                                                          prefix)
                    self._sim_t += take * self.prefill_tok_s
                    fresh = self.ledger.grow(i, pages_for(pos + take, ps),
                                             self._sim_t, cause="admission")
                    self._reserved[i] -= len(fresh)
                    new_n = len(fresh)
                    self._cache = self._write_shared(
                        self._cache, suffix, head, jnp.int32(i),
                        jnp.asarray(held, jnp.int32),
                        jnp.asarray(fresh, jnp.int32))
                self.stats.pages_allocated += new_n
                self.stats.admitted_kv_bytes += new_n * self.page_bytes
                self.stats.peak_pages = max(self.stats.peak_pages,
                                            self.ledger.allocator.n_allocated)
                self.stats.prefill_slices += 1
                self.access.add_write("kv", take * self.row_bytes)
                self._c_alloc.inc(new_n)
                self._c_slices.inc()
                if self.tel.enabled:
                    self.tel.add_span("prefill_slice", t0, self._sim_t,
                                      slot=i, rid=req.rid, tokens=take)
                pos += take
                if pos == S:
                    tok = self._first_token(logits)
            self.stats.prefill_tokens += take
            if pos < S:
                # let the active slots stream tokens before the next slice
                self._decode_chunk(done)
        self._commit_admission(i, req, done, tok, logits, S,
                               self.ledger.slot_pages[i], t_pre)

    def _commit_admission(self, i: int, req: Request, done: List[Request],
                          tok: int, logits, ctx: int,
                          table_pages: List[int], t_pre: float) -> None:
        """Shared admission tail for the plain and prefix paths: host
        mirrors, stats, the prefill-produced first token, and the immediate
        retire when that token already satisfies the request. `t_pre` is
        the sim time before the prefill advance (the span start)."""
        self.slots[i] = req
        self._c_admitted.inc()
        self._c_prefills.inc()
        self._set_page_gauges()
        if self.tel.enabled:
            self.tel.add_span("prefill", t_pre, self._sim_t, slot=i,
                              rid=req.rid, tokens=ctx)
            tl = req.timeline
            if tl is not None:
                tl.admit_t = t_pre
                tl.first_token_t = self._sim_t
                tl.token_ts.append(self._sim_t)
        self._ctx[i] = ctx
        self._next_tok[i] = tok
        self._table[i, :] = 0
        self._table[i, :len(table_pages)] = table_pages
        req.output.append(tok)
        req.first_token_wall_s = time.perf_counter()
        self._first_tokens.append(req.first_token_wall_s)
        if self.collect_logits:
            req.logits.append(np.asarray(logits[0, -1]))
        self.stats.admitted += 1
        self.stats.prefills += 1
        self.stats.peak_active_slots = max(
            self.stats.peak_active_slots,
            sum(s is not None for s in self.slots))
        if (req.max_new_tokens <= 1
                or (req.eos_id is not None and tok == req.eos_id)):
            self._retire(i, req, done, self._sim_t)
        elif self.speculate_k is not None:
            self._admit_draft_lane(i, req)

    def _admit_draft_lane(self, i: int, req: Request) -> None:
        """Prefill the draft model over the full prompt into the slot's
        draft page lane. The draft never shares pages (no radix entry, no
        COW) — a prefix-cache hit only accelerates the target lane; the
        draft re-prefills its own (much smaller) KV from scratch."""
        prompt = np.asarray(req.tokens)
        S = int(len(prompt))
        dn = pages_for(S, self.page_size)
        self._sim_t += S * self.prefill_tok_s * self.draft_cost_frac
        dpages = self.ledger.admit_draft(i, dn, self._sim_t)
        self._reserved[i] -= dn
        self._draft_table[i, :] = 0
        self._draft_table[i, :dn] = dpages
        batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
        _, ddense = self._draft_prefill(self.draft_params, batch,
                                        dn * self.page_size)
        self._draft_cache = self._draft_write(
            self._draft_cache, ddense, i, jnp.asarray(dpages, jnp.int32))
        self.stats.pages_allocated += dn
        self.stats.peak_pages = max(self.stats.peak_pages,
                                    self.ledger.allocator.n_allocated)
        self.stats.admitted_kv_bytes += dn * self.draft_page_bytes
        self.access.add_write("kv", S * self.draft_row_bytes)
        self._c_alloc.inc(dn)
        self._set_page_gauges()

    def _admit_prefix(self, i: int, done: List[Request]) -> bool:
        """Prefix-cache admission of the queue head into slot `i`.

        Returns False when the pool (after LRU-evicting cached prefixes and
        preempting strictly-lower-priority slots) still cannot cover the
        request's worst-case *fresh* page demand — the head then waits. The
        worst case reserves the pages the match did not cover, plus one
        page for the deferred COW split of a mid-page prompt boundary."""
        req = self.queue.peek()
        prompt = np.asarray(req.tokens)
        S = int(len(prompt))
        ps = self.page_size
        worst_total = self._worst_pages(S, req.max_new_tokens)
        cow_extra = 1 if (S % ps and req.max_new_tokens > 1) else 0
        # the draft lane never shares: a hit only accelerates the target
        # lane, the draft's full worst case is fresh demand
        draft_extra = worst_total if self.speculate_k is not None else 0

        def demand(match):
            return (worst_total - len(match.pages) + cow_extra
                    + draft_extra)

        match = self.ledger.index.probe(prompt, limit=S - 1)
        short = demand(match) - self._available_pages()
        while short > 0:
            freed = self.ledger.evict_for(short, self._sim_t)
            if freed:
                self.stats.evicted_pages += freed
                self._c_evicted.inc(freed)
            else:
                # nothing cached left to drop: preempt a lower-priority
                # slot before giving up (pages free via the retire path)
                v = self._preempt_victim(req.priority)
                if v is None:
                    self._c_wait.inc()
                    self.stats.page_waits += 1
                    return False
                self._preempt(v, self._sim_t)
            # eviction/preemption may have changed the matched path: re-probe
            match = self.ledger.index.probe(prompt, limit=S - 1)
            short = demand(match) - self._available_pages()
        self._dequeue()

        n_full, j = len(match.pages), match.tail_tokens
        m = n_full * ps + j
        t_pre = self._sim_t
        C = self.prefill_chunk_tokens
        suffix_len = S - m
        if C is not None and suffix_len > C:
            # chunk the suffix-only prefill: slice 0 is sized to re-align
            # the (possibly mid-page) match boundary to a page multiple so
            # every later slice boundary is page-aligned — the chained
            # gather → fixed-width shared prefill then keeps the result
            # bit-identical to one monolithic suffix prefill
            slices = [min(C - (m % ps), suffix_len)]
            while sum(slices) < suffix_len:
                slices.append(min(C, suffix_len - sum(slices)))
        else:
            slices = [suffix_len]

        pos = m
        for si, take in enumerate(slices):
            with host_span("serve.prefill", self.stats, "prefill_wall_s",
                           rid=req.rid, tokens=take):
                sl = jnp.asarray(prompt[None, pos:pos + take], jnp.int32)
                t0 = self._sim_t
                if si == 0:
                    gather_ids = list(match.pages) + \
                        ([match.tail_page] if j else [])
                    prefix = self._gather(
                        self._cache, jnp.asarray(gather_ids, jnp.int32), m)
                    if self.kv_quantized and gather_ids:
                        self._c_dequant.inc(len(gather_ids))
                    head = prefix_tail_rows(prefix, j)
                    logits, suffix = self._prefill_shared(self.params, sl,
                                                          prefix)
                    self._sim_t += take * self.prefill_tok_s  # suffix only
                    new_n = pages_for(m + take, ps) - n_full
                    if self.meter is not None:
                        self.ledger.set_slot_meta(i, req.rid, req.tenant)
                    fresh = self.ledger.admit(i, new_n, self._sim_t,
                                              shared=match.pages)
                    self._reserved[i] = demand(match) - new_n
                    self._cache = self._write_shared(
                        self._cache, suffix, head, jnp.int32(i),
                        jnp.asarray(match.pages, jnp.int32),
                        jnp.asarray(fresh, jnp.int32))
                else:
                    held = list(self.ledger.slot_pages[i])
                    prefix = self._gather(self._cache,
                                          jnp.asarray(held, jnp.int32), pos)
                    if self.kv_quantized:
                        self._c_dequant.inc(len(held))
                    head = prefix_tail_rows(prefix, 0)  # pos is page-aligned
                    logits, suffix = self._prefill_shared(self.params, sl,
                                                          prefix)
                    self._sim_t += take * self.prefill_tok_s
                    fresh = self.ledger.grow(i, pages_for(pos + take, ps),
                                             self._sim_t, cause="admission")
                    self._reserved[i] -= len(fresh)
                    new_n = len(fresh)
                    self._cache = self._write_shared(
                        self._cache, suffix, head, jnp.int32(i),
                        jnp.asarray(held, jnp.int32),
                        jnp.asarray(fresh, jnp.int32))
                self.stats.pages_allocated += new_n
                self.stats.peak_pages = max(self.stats.peak_pages,
                                            self.ledger.allocator.n_allocated)
                self.stats.admitted_kv_bytes += new_n * self.page_bytes
                self.access.add_write("kv", take * self.row_bytes)
                self._c_alloc.inc(new_n)
                if len(slices) > 1:
                    self.stats.prefill_slices += 1
                    self._c_slices.inc()
                    if self.tel.enabled:
                        self.tel.add_span("prefill_slice", t0, self._sim_t,
                                          slot=i, rid=req.rid, tokens=take)
                pos += take
                if pos == S:
                    tok = self._first_token(logits)
            self.stats.prefill_tokens += take
            if pos < S:
                # let the active slots stream tokens before the next slice
                self._decode_chunk(done)
        if m:
            self.stats.prefix_hits += 1
            self.stats.prefix_tokens_reused += m
            self._c_hits.inc()
            self._c_reused.inc(m)
        else:
            self._c_miss.inc()
        # cache this run for later requests (index refs its pages)
        self.ledger.insert_run(prompt, self.ledger.slot_pages[i], self._sim_t)
        self._commit_admission(i, req, done, tok, logits, S,
                               self.ledger.slot_pages[i], t_pre)
        return True

    def _cow_for_chunk(self, i: int, steps_i: int, t: float) -> None:
        """Copy-on-write split every shared page this chunk will write.

        Decode appends rows [ctx, ctx + steps_i); only the page holding the
        prompt's mid-page boundary can be shared (with the prefix index, or
        with slots that mapped the same run), so at most one split fires per
        slot — but the scan is range-exact regardless. The reservation made
        at admission covers the extra page, so `alloc` cannot fail."""
        ps = self.page_size
        ctx = int(self._ctx[i])
        pages = self.ledger.slot_pages[i]
        first, last = ctx // ps, (ctx + steps_i - 1) // ps
        for idx in range(first, min(last + 1, len(pages))):
            page = pages[idx]
            if self.ledger.allocator.refcount(page) <= 1:
                continue
            new = self.ledger.cow(i, idx, t)
            self._cache = self._copy(self._cache, jnp.int32(page),
                                     jnp.int32(new))
            self._table[i, idx] = new
            self._reserved[i] -= 1
            self.stats.cow_splits += 1
            self.stats.pages_allocated += 1
            self._c_cow.inc()
            self._c_alloc.inc()
            self.tel.add_span("cow", t, t, slot=i, page=new)

    def _decode_chunk(self, done: List[Request]) -> None:
        if self.speculate_k is not None:
            return self._spec_chunk(done)
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return
        with host_span("serve.chunk.prep"):
            t0 = self._sim_t
            # grow page tables to cover this chunk's worst case (reservation
            # at admission guarantees these allocations succeed)
            remaining = np.zeros(self.num_slots, np.int32)
            for i in live:
                req = self.slots[i]
                remaining[i] = req.max_new_tokens - len(req.output)
                steps_i = min(self.chunk_steps, int(remaining[i]))
                new_pages = self.ledger.grow(
                    i, pages_for(int(self._ctx[i]) + steps_i, self.page_size),
                    t0)
                if new_pages:
                    npg_have = len(self.ledger.slot_pages[i])
                    self._table[i, npg_have - len(new_pages):npg_have] = \
                        new_pages
                    self._reserved[i] -= len(new_pages)
                    self.stats.pages_allocated += len(new_pages)
                    self.stats.admitted_kv_bytes += \
                        len(new_pages) * self.page_bytes
                    self._c_alloc.inc(len(new_pages))
                if self.prefix_cache:
                    self._cow_for_chunk(i, steps_i, t0)
            self.stats.peak_pages = max(self.stats.peak_pages,
                                        self.ledger.allocator.n_allocated)

            cache = self._cache
            # host is the source of truth between chunks: push the page-table
            # mirror and the liveness mask (covers slots retired host-side at
            # admission, whose device `active` flag was never flipped in-scan)
            cache["page_table"] = jnp.asarray(self._table)
            cache["active"] = jnp.asarray(
                [s is not None for s in self.slots])
            emitted, cache, tok, _ = self._loop(
                self.params, cache, jnp.asarray(self._next_tok[:, None]),
                jnp.asarray([(self.slots[i].eos_id
                              if self.slots[i] is not None
                              and self.slots[i].eos_id is not None else -1)
                             for i in range(self.num_slots)], jnp.int32),
                jnp.asarray(remaining))
        self._cache = cache
        self.stats.chunks += 1
        with host_span("serve.chunk.wait", self.stats, "device_wait_s"):
            step_logits = None
            if self.collect_logits:
                emitted, step_logits = emitted
                step_logits = np.asarray(step_logits)  # (steps, slots, V)
            emitted = np.asarray(emitted)                # (steps, num_slots)
            self._next_tok = np.array(tok[:, 0])
            still_active = np.array(cache["active"])
        self._sim_t = t0 + self.chunk_steps * self.step_time_s
        self._c_chunks.inc()
        self.tel.add_span("decode_chunk", t0, self._sim_t, slots=len(live))

        with host_span("serve.chunk.emit"):
            for i in live:
                req = self.slots[i]
                col = emitted[:, i]
                neg = np.nonzero(col < 0)[0]
                g = int(neg[0]) if len(neg) else len(col)
                req.output.extend(int(t) for t in col[:g])
                if step_logits is not None:
                    req.logits.extend(step_logits[:g, i])
                self.stats.decode_steps += g
                # page-granular access accounting: each step streams the
                # resident pages and appends one row
                ctxs = int(self._ctx[i]) + 1 + np.arange(g)
                pages_read = int((np.ceil(ctxs / self.page_size)).sum())
                if self._attn_blocks:
                    self.stats.attn_blocks_live += int(
                        (-(-ctxs // self._attn_block_rows)).sum())
                self.access.add_read("kv", pages_read * self.page_bytes)
                self.access.add_write("kv", g * self.row_bytes)
                if self.kv_quantized and pages_read:
                    # every page the fused kernel streams is dequantized
                    # in-register
                    self._c_dequant.inc(pages_read)
                self._c_steps.inc(g)
                if req.timeline is not None and g:
                    req.timeline.token_ts.extend(
                        (t0 + self.step_time_s * np.arange(1, g + 1)).tolist())
                self._ctx[i] += g
                if not still_active[i]:
                    self._retire(i, req, done, t0 + g * self.step_time_s)
            if self._attn_blocks:
                self.stats.attn_blocks_total += \
                    self.chunk_steps * self._attn_grid

    def _spec_chunk(self, done: List[Request]) -> None:
        """One speculative decode chunk: `spec_rounds_per_chunk` draft-then-
        verify rounds for every live slot in one donated `lax.scan`, then a
        host sync that harvests the accepted tokens and *rolls back* both
        lanes by page truncation — every page past the accepted context
        frees mid-stream (the negative occupancy deltas Stage I sees as the
        speculative burst/rollback signature) and returns to the slot's
        reservation for later re-growth."""
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return
        with host_span("serve.chunk.prep"):
            t0 = self._sim_t
            V = self.speculate_k + 1
            R = self.spec_rounds_per_chunk
            ps = self.page_size
            remaining = np.zeros(self.num_slots, np.int32)
            for i in live:
                req = self.slots[i]
                remaining[i] = req.max_new_tokens - len(req.output)
                # worst rows this chunk can touch: every round writes V rows at
                # pos..pos+V-1 and advances >= 1, and the last active round
                # starts with >= 1 token remaining
                rows = int(self._ctx[i]) + min(R * V,
                                               int(remaining[i]) + V - 1)
                npg = pages_for(rows, ps)
                new_pages = self.ledger.grow(i, npg, t0)
                if new_pages:
                    have = len(self.ledger.slot_pages[i])
                    self._table[i, have - len(new_pages):have] = new_pages
                    self._reserved[i] -= len(new_pages)
                    self.stats.pages_allocated += len(new_pages)
                    self.stats.admitted_kv_bytes += \
                        len(new_pages) * self.page_bytes
                    self._c_alloc.inc(len(new_pages))
                dnew = self.ledger.grow_draft(i, npg, t0)
                if dnew:
                    dhave = len(self.ledger.draft_pages[i])
                    self._draft_table[i, dhave - len(dnew):dhave] = dnew
                    self._reserved[i] -= len(dnew)
                    self.stats.pages_allocated += len(dnew)
                    self.stats.admitted_kv_bytes += \
                        len(dnew) * self.draft_page_bytes
                    self._c_alloc.inc(len(dnew))
                if self.prefix_cache:
                    # the verify window writes past ctx: COW every shared page
                    # in the full speculative write range, not just chunk_steps
                    self._cow_for_chunk(i, rows - int(self._ctx[i]), t0)
            self.stats.peak_pages = max(self.stats.peak_pages,
                                        self.ledger.allocator.n_allocated)

            cache = self._cache
            dcache = self._draft_cache
            cache["page_table"] = jnp.asarray(self._table)
            dcache["page_table"] = jnp.asarray(self._draft_table)
            # two separate transfers: both caches are donated, so the liveness
            # masks must be distinct buffers even though their contents match
            act_np = np.array([s is not None for s in self.slots])
            cache["active"] = jnp.asarray(act_np)
            dcache["active"] = jnp.asarray(act_np.copy())
            emitted, cache, dcache, tok, _ = self._spec_loop(
                self.params, self.draft_params, cache, dcache,
                jnp.asarray(self._next_tok[:, None]),
                jnp.asarray([(self.slots[i].eos_id if self.slots[i] is not None
                              and self.slots[i].eos_id is not None else -1)
                             for i in range(self.num_slots)], jnp.int32),
                jnp.asarray(remaining))
        self._cache = cache
        self._draft_cache = dcache
        self.stats.chunks += 1
        with host_span("serve.chunk.wait", self.stats, "device_wait_s"):
            emitted = np.asarray(emitted)        # (rounds, num_slots, V)
            self._next_tok = np.array(tok[:, 0])
            still_active = np.array(cache["active"])
        self._sim_t = t0 + R * self.spec_round_time_s
        self._c_chunks.inc()
        self.tel.add_span("decode_chunk", t0, self._sim_t, slots=len(live))

        with host_span("serve.chunk.emit"):
            for i in live:
                req = self.slots[i]
                block = emitted[:, i, :]             # (rounds, V), -1 padded
                m_r = (block >= 0).sum(axis=1)       # per-round accepted count
                rounds_used = int((m_r > 0).sum())
                toks = block.ravel()
                toks = toks[toks >= 0]
                g = int(len(toks))
                req.output.extend(int(t) for t in toks)
                self.stats.decode_steps += g
                self.stats.spec_rounds += rounds_used
                self.stats.drafted_tokens += rounds_used * self.speculate_k
                self.stats.accepted_tokens += g
                self._c_spec_rounds.inc(rounds_used)
                self._c_drafted.inc(rounds_used * self.speculate_k)
                self._c_accepted.inc(g)
                # page-granular access accounting, per round: the verify kernel
                # streams the target's resident pages once; the draft streams
                # its own lane for each of its k+1 sequential steps
                ctx = int(self._ctx[i])
                pos = ctx
                pages_t = 0
                pages_d = 0
                for r in range(rounds_used):
                    per_round = -(-(pos + V) // ps)
                    pages_t += per_round
                    pages_d += (self.speculate_k + 1) * per_round
                    pos += int(m_r[r])
                self.access.add_read("kv", pages_t * self.page_bytes
                                     + pages_d * self.draft_page_bytes)
                self.access.add_write(
                    "kv", rounds_used * (V * self.row_bytes
                                         + (self.speculate_k + 1)
                                         * self.draft_row_bytes))
                if self.kv_quantized and pages_t:
                    self._c_dequant.inc(pages_t + pages_d)
                self._c_steps.inc(g)
                if req.timeline is not None and g:
                    ts: List[float] = []
                    for r in range(rounds_used):
                        ts.extend([t0 + (r + 1) * self.spec_round_time_s]
                                  * int(m_r[r]))
                    req.timeline.token_ts.extend(ts)
                self._ctx[i] = ctx + g
                t_end = t0 + rounds_used * self.spec_round_time_s
                # rollback-by-page-truncation: both lanes drop every page past
                # the accepted context; freed pages rejoin the reservation
                ft, fd = self.ledger.truncate_rows(i, int(self._ctx[i]), t_end)
                nf = len(ft) + len(fd)
                if nf:
                    keep = pages_for(int(self._ctx[i]), ps)
                    self._table[i, keep:] = 0
                    self._draft_table[i, keep:] = 0
                    self._reserved[i] += nf
                    self.stats.pages_freed += nf
                    self.stats.rolled_back_pages += nf
                    self._c_freed.inc(nf)
                    self._c_rollback.inc(nf)
                if not still_active[i]:
                    self._retire(i, req, done, t_end)


def loop_compile_count() -> int:
    """How many times the chunk decode loop has been traced/compiled
    process-wide (tests assert it does not grow across chunks) —
    compatibility shim over the `serve.paged.loop_compiles` registry
    counter (the old module-global it replaced)."""
    return int(_COMPILES.value)
