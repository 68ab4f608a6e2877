"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point that compiles (the ``launch`` CLIs, the benchmark runner
and its worker, ``chip_smoke.py``) calls :func:`enable` before its first
compile, so a second run of the same programs loads them instead of
compiling them again.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: where the cache lives unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    directory is left alone.  Otherwise the cache goes to
    ``<repo>/.jax_cache``: a fixed path, never a temporary name, because the
    path is part of what makes a later run find the entries again.

    Either way every compile is kept: JAX's default keeps only compiles
    over one second, and the solver's many short per-rung compiles then
    miss the cache on every run.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
