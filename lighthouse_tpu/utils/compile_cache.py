"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`configure` before its first compile.  A
cache placed from outside (``JAX_COMPILATION_CACHE_DIR``) wins and
nothing else is set in code; otherwise the cache lives at
``<checkout>/.jax_cache``, a path fixed by this file's location so a
later run of the same checkout finds what an earlier one compiled (the
choice is exported to the environment, so child processes agree).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    outside = os.environ.get(ENV)
    if outside:
        return outside
    path = os.path.join(CHECKOUT, ".jax_cache")
    os.environ[ENV] = path          # children of this process agree
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
