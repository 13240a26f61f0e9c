"""Where JAX's persistent compilation cache lives.

A run on the chip compiles every kernel and step program it touches, and
the next process (or the next call on the same machine) can reuse them
from JAX's persistent cache. The cache key includes its directory, so the
directory is fixed:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  here sets another location;
* otherwise the cache is ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``) — never a temporary, per-process or per-time name.

Only TPU runs turn it on: on the CPU (the test suite, compile rehearsals)
:func:`enable_compile_cache` leaves JAX's configuration untouched.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache for a TPU run; returns its
    directory, or None when the backend is not a TPU."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
