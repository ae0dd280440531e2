"""JAX's persistent compilation cache for the command-line entry points.

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()   # first thing in main(), before any jit runs

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at one fixed path
inside the checkout, `<repo>/.jax_cache` (git-ignored): the path is part
of the cache's key, so it never comes from a temp name, a pid or a time.

Entry points call this (`chip_smoke.py`, `examples/quickstart.py`,
`python -m repro.launch.fedserve`, `python -m benchmarks.run`); library
imports and the tests never do.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Every compile is kept, however short: an entry point's
    many small programs add up to most of a cold start."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
