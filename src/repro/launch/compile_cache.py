"""JAX's persistent compilation cache, set up once by each launcher's main.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise, on an accelerator, the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of every
cache key, so a directory that moved between runs would never hit.
``.gitignore`` lists it. CPU runs (the tests) keep no cache: XLA:CPU
results reloaded from it log machine-feature mismatches, and the CPU
compiles are cheap.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def configure_compile_cache() -> str | None:
    """Turn the persistent cache on; return the directory it writes to
    (None on the CPU backend, which keeps no cache)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
