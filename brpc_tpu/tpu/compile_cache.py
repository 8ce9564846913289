"""Where compiled XLA programs are kept between processes.

Every entry point that compiles on the chip calls
:func:`enable_compile_cache` before its first compile, so the processes
of one run (and the next run from the same checkout) share one
persistent cache instead of each starting cold.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — fixed, git-ignored. The directory is part of
# what makes a cached program findable again, so it is never built from
# a temp name, a pid or a time.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in
    use. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
    itself; no directory is set in code); otherwise the cache goes to
    ``<checkout>/.jax_cache``. Either way every program is kept, however
    quick its compile, so a second run adds no entries."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
