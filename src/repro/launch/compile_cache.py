"""Placement of JAX's persistent compilation cache for the entry points.

Called by the entry points (``launch/serve.py``, ``chip_smoke.py``) before
they compile anything — never on import.  A cache key includes its
directory, so the directory must not move between runs: it is either the
one ``JAX_COMPILATION_CACHE_DIR`` names (which JAX reads itself; nothing is
set in code then) or a fixed directory inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compile_cache"]

#: ``<checkout>/.jax_cache`` — listed in .gitignore
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
