"""Persistent XLA compilation cache.

Leaf-search programs take seconds to minutes to compile for a TPU; paying
that once per *process* puts it on every node's first queries. JAX's
persistent compilation cache keys executables by HLO fingerprint, so every
process after the first loads the compiled binary instead. The reference
has no analogue (tantivy is interpreted); this is TPU-build-specific
operability.

The directory is `JAX_COMPILATION_CACHE_DIR` where that is set (JAX reads
it itself; no directory is set in code), else `<checkout>/.jax_cache`. The
path is fixed because it is part of what a cache hit depends on: a
directory that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_persistent_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.
    A directory that cannot be created raises."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every compile, however fast: the steady state is many small
    # per-signature executables, and a threshold would make what a restart
    # finds depend on how long each compile happened to take
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
