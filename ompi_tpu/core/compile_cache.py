"""JAX persistent compilation cache: one fixed place per checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads
it on its own; nothing else is set then. Otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path is part
of the cache key, so it never depends on a temp name, a pid or the
time. Call ``enable()`` before the first compile.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Point JAX's compilation cache at its directory; returns it."""
    path = os.environ.get(_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT


__all__ = ["enable"]
