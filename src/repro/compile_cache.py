"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.serve.store_server.main``, ``chip_smoke.py``) call
:func:`configure_compile_cache` once before they compile anything; importing
a module never does. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that
directory itself and nothing is set here. Otherwise the cache lives at a
fixed path inside the checkout (``.jax_cache/``, git-ignored): the path is
part of what makes a cached entry found again, so it is never built from a
temporary name, a pid or the time.
"""

from __future__ import annotations

import os

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache"]

DEFAULT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
