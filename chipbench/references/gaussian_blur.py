"""Plain reference of the Table-I app ``gaussian_blur`` (FLOWER,
arXiv:2112.07789): one 5x5 binomial blur, (1 4 6 4 1)/16 in each
direction, of a zero-padded plane.

Written in straightforward ``jax.numpy``, importing nothing of the
system under test.  The stencil is a correlation accumulated tap by tap
in row-major order.  ``dtype`` is the precision every value is held and
computed in: float32 is the configuration's, bfloat16 the control's.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

INPUTS = ("img",)
OUTPUTS = ("out",)

BINOMIAL5 = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1]) / 256.0


def reference(inputs: dict, dtype=jnp.float32) -> dict:
    x = jnp.asarray(inputs["img"]).astype(dtype)
    h, w = x.shape
    xp = jnp.pad(x, ((2, 2), (2, 2)))
    acc = None
    for i in range(5):
        for j in range(5):
            term = xp[i:i + h, j:j + w] * float(BINOMIAL5[i, j])
            acc = term if acc is None else acc + term
    return {"out": acc}


def min_ops(h: int, w: int) -> int:
    """Arithmetic operations per frame: 25 multiplies and 24 adds a pixel."""
    return (25 + 24) * h * w
