"""Where JAX keeps its persistent compilation cache.

A compiled program is keyed, among other things, by the cache directory,
so a directory that moves between runs never hits.  The rule is:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment decides, and JAX
  reads it itself — nothing is set in code;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored),
  one fixed path per checkout.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on the persistent cache and return its directory.

    ``checkout`` is the root of the repository checkout the caller runs
    from; it is used only when ``JAX_COMPILATION_CACHE_DIR`` is unset.
    """
    import jax

    placed = os.environ.get(ENV)
    if placed:
        return placed
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
