"""JAX persistent compilation cache for the entry points.

Called by the launchers (``serve``, ``eval_accuracy``) and
``chip_smoke.py``, never on import, so tests and library users keep
whatever cache setting they have.
"""

from __future__ import annotations

import os
from pathlib import Path

# fixed, checkout-relative: the directory is part of the cache key, so a
# path that moved (temporary, pid- or time-derived) would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache lives at ``<checkout>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
