"""JAX's persistent compilation cache, for the entry points.

Scripts that compile (``chip_smoke.py``, ``benchmarks/``,
``repro.launch.serve``, ``repro.launch.train``) call
:func:`use_compile_cache` once at start-up; importing ``repro`` never
does, so a library user keeps whatever cache their program chose.
"""
from __future__ import annotations

import os

__all__ = ["use_compile_cache", "DEFAULT_DIR"]

#: the fallback cache directory: ``.jax_cache/`` at the root of the
#: checkout (listed in ``.gitignore``)
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads
    it itself and no other directory is set here.  Otherwise the cache
    is :data:`DEFAULT_DIR`, a fixed path — a directory that moves
    between runs never hits.  Every compile is cached, however quick:
    a kernel that compiles in under the default one-second threshold
    still costs a process start-up its compile.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
