"""Placement of JAX's persistent compilation cache.

At 10M-key shapes a cold compile of the Q5 job is about a minute on a
v5e; the persistent cache turns a second start in the same checkout into
seconds. Entry points (chip_smoke.py, bench.py, the CLI) call
``place_compile_cache()`` before their first compile. Tests do not.

This is JAX's own XLA-executable cache. The AOT executable cache
(runtime/aot.py, ``aot.dir``) is a separate, verified, per-job artifact
store and is not touched here.
"""

from __future__ import annotations

import os

__all__ = ["place_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Return the directory JAX's persistent compile cache lives in.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing is
    touched and the program writes its cache there and nowhere else.
    Unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
    because the directory is part of what a later run must find again."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
