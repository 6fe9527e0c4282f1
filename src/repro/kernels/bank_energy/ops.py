"""Backend dispatch for the Stage-II trace analytics.

Two entry points, each evaluating a whole (C, B, alpha) candidate grid in
one call:

  * `bank_activity_stats` — cheap lower-bound stats (bank-seconds, toggles).
  * `exact_bank_stats`    — exact idle-run stats for the batched evaluator.

Backends: "numpy" (float64, bit-exact vs the scalar reference — the default
on CPU hosts), "ref" (jnp, jit), "pallas" (TPU kernel, the default when a
TPU is attached), "interpret" (Pallas interpret mode, for tests).

Precision: occupancy is byte-valued and reaches 10^8 for the paper's
128 MiB arrays — beyond float32's exact-integer range (2^24), so an f32
cast drops sub-16-byte deltas and can flip ceil() at bank boundaries. The
f32 paths therefore normalize occupancy and usable to KiB before the kernel
(keeping the common KiB-granular occupancies exactly representable up to
2^34 bytes; the ratio, and hence bank activity, is unchanged because the
rescale is a power of two), and "auto" on CPU routes to the float64 numpy
path, which is exact for any byte value.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bank_energy.kernel import (bank_energy_kernel,
                                              exact_bank_stats_kernel)
from repro.kernels.bank_energy.ref import (bank_energy_np, bank_energy_ref,
                                           exact_bank_stats_np,
                                           exact_bank_stats_ref)

KIB = 1024.0


def resolve_backend(backend: str) -> str:
    """"auto" is a pure platform switch: the Pallas kernel on a TPU, the
    float64 numpy path anywhere else."""
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "numpy"


def _pad(durations, occupancy, block_s: int):
    S = durations.shape[0]
    Sp = max(block_s, ((S + block_s - 1) // block_s) * block_s)
    pad = Sp - S
    if pad:
        durations = jnp.concatenate(
            [durations, jnp.zeros((pad,), durations.dtype)])
        last = occupancy[-1] if S else jnp.zeros((), occupancy.dtype)
        occupancy = jnp.concatenate(
            [occupancy, jnp.full((pad,), last, occupancy.dtype)])
    return durations, occupancy


@functools.partial(jax.jit, static_argnames=("backend", "block_s"))
def _bank_activity_stats_jit(durations, occupancy, usable, nbanks, *,
                             backend: str, block_s: int):
    durations = jnp.asarray(durations, jnp.float32)
    occupancy = jnp.asarray(occupancy, jnp.float32) / KIB
    usable = jnp.asarray(usable, jnp.float32) / KIB
    nbanks = jnp.asarray(nbanks, jnp.float32)
    if backend == "ref":
        return bank_energy_ref(durations, occupancy, usable, nbanks)
    d, o = _pad(durations, occupancy, block_s)
    return bank_energy_kernel(d, o, usable, nbanks, block_s=block_s,
                              interpret=(backend == "interpret"))


def bank_activity_stats(durations, occupancy, usable, nbanks, *,
                        backend: str = "auto", block_s: int = 2048):
    """(C, 2): [active bank-seconds, on/off transition count] per candidate."""
    backend = resolve_backend(backend)
    if backend == "numpy":
        return bank_energy_np(durations, occupancy, usable, nbanks)
    return _bank_activity_stats_jit(durations, occupancy, usable, nbanks,
                                    backend=backend, block_s=block_s)


@functools.partial(jax.jit,
                   static_argnames=("bmax", "backend", "block_s"))
def _exact_bank_stats_jit(durations, occupancy, usable, nbanks, threshold, *,
                          bmax: int, backend: str, block_s: int):
    durations = jnp.asarray(durations, jnp.float32)
    occupancy = jnp.asarray(occupancy, jnp.float32) / KIB
    usable = jnp.asarray(usable, jnp.float32) / KIB
    nbanks = jnp.asarray(nbanks, jnp.float32)
    threshold = jnp.asarray(threshold, jnp.float32)
    if backend == "ref":
        return exact_bank_stats_ref(durations, occupancy, usable, nbanks,
                                    threshold, bmax=bmax)
    d, o = _pad(durations, occupancy, block_s)
    return exact_bank_stats_kernel(d, o, usable, nbanks, threshold,
                                   bmax=bmax, block_s=block_s,
                                   interpret=(backend == "interpret"))


def exact_bank_stats(durations, occupancy, usable, nbanks, threshold, *,
                     backend: str = "auto", block_s: int = 2048):
    """(C, 5) exact idle-run stats per candidate: [active bank-seconds,
    idle runs >= threshold, their seconds, idle runs < threshold, their
    seconds]. See `exact_bank_stats_np` for the reference semantics."""
    backend = resolve_backend(backend)
    if backend == "numpy":
        return exact_bank_stats_np(durations, occupancy, usable, nbanks,
                                   threshold)
    n_cand, n_seg = len(np.asarray(usable)), len(np.asarray(durations))
    if n_cand == 0 or n_seg == 0:
        return np.zeros((n_cand, 5), np.float32)
    bmax = int(np.max(np.asarray(nbanks)))
    return _exact_bank_stats_jit(durations, occupancy, usable, nbanks,
                                 threshold, bmax=bmax, backend=backend,
                                 block_s=block_s)


def candidate_grid(capacities_bytes: Sequence[int], banks: Sequence[int],
                   alpha: float) -> Tuple[np.ndarray, np.ndarray, list]:
    """Flatten a (C x B) sweep into the kernel's candidate arrays."""
    usable, nb, meta = [], [], []
    for c in capacities_bytes:
        for b in banks:
            usable.append(alpha * (c / b))
            nb.append(float(b))
            meta.append((int(c), int(b)))
    return np.asarray(usable, np.float64), np.asarray(nb, np.float64), meta
