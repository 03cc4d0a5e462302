"""The start every entry script shares: the compile cache, the chips,
the program on the path."""
from __future__ import annotations

import os
import sys

from harness.spec import ROOT

#: JAX's persistent compile cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def start(chips: int, who: str) -> list | None:
    """Point the compile cache at ``CACHE_DIR``, then return the first
    ``chips`` TPU chips JAX sees, or None (saying why on standard error)
    when the first device is not a TPU or there are fewer chips."""
    import jax

    # JAX read the variable when it was imported, so the directory is
    # set in its config too; the variable keeps use_compile_cache() to it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{who}: needs {chips} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    return devices[:chips]

