"""Where the persistent XLA compile cache lives — decided in ONE place.

``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this sets no
directory in code, so whoever runs the program places the cache. Unset:
``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the path is
part of the cache key's environment: a directory named after a pid, a time
or a temp dir never hits on the next run.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
