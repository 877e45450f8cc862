"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``__main__`` of
:mod:`repro.launch.rpc` and :mod:`repro.launch.serve_selector`) call
:func:`configure_compile_cache` before their first compile; library code
never does. A cache whose directory moves never hits, so the directory is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
the variable itself, and nothing is set here), else ``<checkout>/.jax_cache``
(git-ignored).
"""
from __future__ import annotations

import os

__all__ = ["configure_compile_cache", "CACHE_DIRNAME"]

CACHE_DIRNAME = ".jax_cache"


def configure_compile_cache(root: str) -> str:
    """Return the directory of JAX's persistent compilation cache, placing
    it first when the environment does not. ``root`` is the checkout the
    entry point runs from. With ``JAX_COMPILATION_CACHE_DIR`` set, nothing
    is configured here. Otherwise the cache goes to ``<root>/.jax_cache``
    and keeps programs however fast they compiled: a solve compiles
    hundreds of sub-second kernels, which JAX's default one-second floor
    would leave out."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
