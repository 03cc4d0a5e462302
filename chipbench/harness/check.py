"""The comparison that decides ``correct``.

After the window, every sampled frame's outputs, as the timed path gave
them to the client, are compared with the app's plain reference run on
the same frame set in the configuration's precision.  For each output
plane the gap is the largest pixel difference over the mean magnitude
of the reference plane; a run's ``gap`` is the widest over every sampled
frame and output.  A wrong shape or a value that is not finite reads as
an infinite gap.  A frame that never came back, or failed, is
``missing``; a sampled frame whose outputs were not kept is
``uncompared``.  Each number has its limit, and the run is correct when
none is over it.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gap(got, want):
    scale = jnp.mean(jnp.abs(want))
    return jnp.max(jnp.abs(got - want)) / jnp.maximum(scale, 1e-30)


def plane_gap(got: Any, want: jax.Array) -> float:
    if tuple(np.shape(got)) != tuple(want.shape):
        return math.inf
    got = jax.device_put(got, want.devices().pop()) \
        if isinstance(got, np.ndarray) else got
    g = float(_gap(jnp.asarray(got, jnp.float32), want))
    return g if math.isfinite(g) else math.inf


def compare(outputs: dict[int, dict[str, Any]], pool_of: dict[int, int],
            pool: list[dict], ref, dtype=jnp.float32) -> float:
    """The widest gap over the sampled frames.  ``pool_of`` maps a
    frame's index to the frame set it was made from; the reference runs
    once per frame set used."""
    run_ref = jax.jit(functools.partial(ref.reference, dtype=dtype))
    wants: dict[int, dict] = {}
    worst = 0.0
    for idx in sorted(outputs):
        p = pool_of[idx]
        if p not in wants:
            wants[p] = {k: v.astype(jnp.float32)
                        for k, v in run_ref(pool[p]).items()}
        for name in ref.OUTPUTS:
            got = outputs[idx].get(name)
            gap = math.inf if got is None else plane_gap(got, wants[p][name])
            worst = max(worst, gap)
    return worst


def verdict(checks: dict[str, dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
