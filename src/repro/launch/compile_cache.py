"""Where JAX keeps its persistent compilation cache.

Called first by every command-line entry point and by `chip_smoke.py`,
never at import. If `JAX_COMPILATION_CACHE_DIR` is set, that directory is
the cache and no other is configured. Otherwise the cache lives at the
fixed path `<checkout>/.jax_cache`, so every later run from the same
checkout finds the programs that earlier runs compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return the path.
    Must run before the first compile of the process."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
