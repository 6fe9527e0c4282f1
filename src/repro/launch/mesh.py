"""Production mesh factory.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax init,
and smoke tests must keep seeing 1 device.

Production topology: one pod = 16x16 = 256 chips, axes ("data", "model");
multi-pod adds a leading "pod" axis (2 x 256 = 512 chips). Designed so DP
spans ("pod","data") — the slowest collectives (cross-pod) carry only
gradient all-reduces, while TP stays inside the pod's fast ICI.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """Mesh with `Auto` axes: the model code places data with
    `with_sharding_constraint`, which `Explicit` axes (the default of
    `jax.make_mesh`) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate mesh over whatever devices exist (CPU smoke tests,
    elastic re-mesh on partial failures)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
