"""Small cross-cutting helpers.

``to_device_copy`` exists because of a real flake (DESIGN.md §7):
``jnp.asarray(np_buf)``'s host-to-device transfer may *alias* the source
buffer and read it asynchronously after dispatch returns. Handing it a
buffer the caller mutates right afterwards (the next prefill token, an
in-place position bump, a reused staging array) races the pending
execution — flakily, since the window depends on dispatch latency. Every
dispatch site that feeds a host buffer it does not exclusively own into
a jitted call must snapshot through this helper.

``enable_compile_cache`` turns on JAX's persistent compilation cache;
the launchers call it before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# <checkout>/.jax_cache: fixed, because the cache directory is part of
# what a later run must find again (listed in .gitignore)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Keep compiled programs across runs; returns the cache directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise an accelerator's programs go to
    ``DEFAULT_COMPILE_CACHE``. Host-CPU runs (tests, rehearsals) compile
    in seconds and cache nothing (None)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def to_device_copy(buf, dtype=None) -> jnp.ndarray:
    """Snapshot a host buffer into a device array via a fresh, never
    mutated copy. Safe against the async host-to-device aliasing race;
    also normalizes non-contiguous views (np slices) before transfer."""
    return jnp.asarray(np.array(buf, dtype=dtype, copy=True))
