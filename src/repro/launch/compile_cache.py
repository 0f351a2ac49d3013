"""JAX's persistent compilation cache, placed from outside the program.

A cold start compiles every step program of a full-width model; the
persistent cache lets the next process of the same checkout skip that.
The cache key includes the directory, so it stays at one fixed path.
"""

from __future__ import annotations

import os

import jax

#: The in-checkout default (listed in ``.gitignore``).
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself) and nothing else is set; otherwise the cache lives at
    ``DEFAULT_DIR`` inside the checkout.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
