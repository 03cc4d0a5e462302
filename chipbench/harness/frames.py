"""Camera frames made on the device from the run's seed.

A frame set holds one plane per app input.  Input ``k`` of a set is one
procedural scene seen ``k`` frames later, moved by the set's motion, so
a two-input app (``optical_flow_lk``) gets two consecutive frames of one
camera.  A scene is a sum of ``GRATINGS`` sinusoidal gratings of random
orientation, period and phase around mid-grey, plus sensor noise, then
clipped and rounded to whole 8-bit levels (0..255) and held as float32:
what an 8-bit camera delivers, widened for a float32 pipeline.

Every set has the same sizes; the seed draws the gratings, the motion
and the noise.  All sets come out of one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GRATINGS = 6
#: grating periods in pixels, amplitudes in 8-bit levels
PERIOD_PX = (6.0, 48.0)
AMPLITUDE = (8.0, 32.0)
#: largest motion between consecutive frames, in pixels per axis
MOTION_PX = 1.5
NOISE_LEVELS = 2.0


def jax_key(seed: int):
    """A JAX key from any whole number: every bit of a large seed counts
    (``jax.random.key`` keeps only the low 32 bits of some)."""
    words = np.random.SeedSequence(seed & (2 ** 64 - 1)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, n: int, h: int, w: int, n_inputs: int):
    kf, kt, kp, ka, km, kn = jax.random.split(key, 6)
    period = jax.random.uniform(kf, (n, GRATINGS), minval=PERIOD_PX[0],
                                maxval=PERIOD_PX[1])
    theta = jax.random.uniform(kt, (n, GRATINGS), maxval=np.pi)
    phase = jax.random.uniform(kp, (n, GRATINGS), maxval=2 * np.pi)
    amp = jax.random.uniform(ka, (n, GRATINGS), minval=AMPLITUDE[0],
                             maxval=AMPLITUDE[1])
    motion = jax.random.uniform(km, (n, 2), minval=-MOTION_PX,
                                maxval=MOTION_PX)
    ys = jnp.arange(h, dtype=jnp.float32)[None, :, None]
    xs = jnp.arange(w, dtype=jnp.float32)[None, None, :]
    noise_keys = jax.random.split(kn, n_inputs)
    planes = []
    for k in range(n_inputs):
        y = ys - k * motion[:, 0, None, None]
        x = xs - k * motion[:, 1, None, None]
        acc = jnp.full((n, h, w), 128.0, jnp.float32)
        for g in range(GRATINGS):
            f = (2 * np.pi / period[:, g])[:, None, None]
            c = jnp.cos(theta[:, g])[:, None, None]
            s = jnp.sin(theta[:, g])[:, None, None]
            acc = acc + amp[:, g, None, None] * jnp.cos(
                f * (c * x + s * y) + phase[:, g, None, None])
        acc = acc + NOISE_LEVELS * jax.random.normal(noise_keys[k],
                                                     (n, h, w))
        planes.append(jnp.round(jnp.clip(acc, 0.0, 255.0)))
    # one output buffer per plane: no slicing dispatch afterwards
    return [plane[i] for i in range(n) for plane in planes]


def frame_sets(seed: int, n: int, shape: tuple[int, int],
               input_names: tuple[str, ...], device=None) -> list[dict]:
    """``n`` frame sets ``{input name: (h, w) float32 device array}``."""
    h, w = shape
    key = jax_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    k = len(input_names)
    planes = _make(key, n, h, w, k)
    return [dict(zip(input_names, planes[i * k:(i + 1) * k]))
            for i in range(n)]
