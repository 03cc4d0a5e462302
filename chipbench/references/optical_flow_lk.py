"""Plain reference of the Table-I app ``optical_flow_lk`` (FLOWER,
arXiv:2112.07789, Fig. 4): single-scale Lucas-Kanade optical flow.

Written from the paper's description in straightforward ``jax.numpy``,
importing nothing of the system under test:

- ``ix``, ``iy``: 3x3 Sobel of the first frame, divided by 8 (a
  centred difference);
- ``it``: second frame minus the first;
- the five products ``ix*ix``, ``iy*iy``, ``ix*iy``, ``ix*it``,
  ``iy*it``, each summed over a 5x5 binomial window (1 4 6 4 1)/16 in
  each direction;
- the 2x2 solve ``v = -A^-1 b`` per pixel, and 0 where the window's
  determinant is not above ``EPS`` in magnitude.

Every stencil reads its input zero-padded at the image border.  The
stencils are correlations (tap ``[i][j]`` weighs pixel
``(y + i - 1, x + j - 1)``) accumulated tap by tap in row-major order,
skipping zero taps.  ``dtype`` is the precision every value is held
and computed in: float32 is the configuration's, bfloat16 the control's.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

INPUTS = ("f1", "f2")
OUTPUTS = ("vx", "vy")
EPS = 1e-3

SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float64) / 8.0
SOBEL_Y = SOBEL_X.T.copy()
BINOMIAL5 = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1]) / 256.0
STENCILS = (SOBEL_X, SOBEL_Y) + (BINOMIAL5,) * 5


def correlate(x, taps: np.ndarray):
    """Zero-padded 'same' correlation of the plane ``x`` with ``taps``."""
    kh, kw = taps.shape
    h, w = x.shape
    xp = jnp.pad(x, ((kh // 2, kh // 2), (kw // 2, kw // 2)))
    acc = None
    for i in range(kh):
        for j in range(kw):
            t = float(taps[i, j])
            if t == 0.0:
                continue
            view = xp[i:i + h, j:j + w]
            term = view if t == 1.0 else view * t
            acc = term if acc is None else acc + term
    return acc


def reference(inputs: dict, dtype=jnp.float32) -> dict:
    f1 = jnp.asarray(inputs["f1"]).astype(dtype)
    f2 = jnp.asarray(inputs["f2"]).astype(dtype)
    ix = correlate(f1, SOBEL_X)
    iy = correlate(f1, SOBEL_Y)
    it = f2 - f1
    wxx = correlate(ix * ix, BINOMIAL5)
    wyy = correlate(iy * iy, BINOMIAL5)
    wxy = correlate(ix * iy, BINOMIAL5)
    wxt = correlate(ix * it, BINOMIAL5)
    wyt = correlate(iy * it, BINOMIAL5)
    det = wxx * wyy - wxy * wxy
    ok = jnp.abs(det) > EPS
    zero = jnp.zeros_like(det)
    vx = jnp.where(ok, (-wyy * wxt + wxy * wyt) / det, zero)
    vy = jnp.where(ok, (wxy * wxt - wxx * wyt) / det, zero)
    return {"vx": vx, "vy": vy}


def min_ops(h: int, w: int) -> int:
    """Arithmetic operations per frame, as the algorithm states them."""
    per_pixel = 0
    for taps in STENCILS:
        nz = int(np.count_nonzero(taps))
        per_pixel += nz + (nz - 1)      # multiplies + adds
    per_pixel += 1                      # it
    per_pixel += 5                      # the five products
    per_pixel += 3                      # det
    per_pixel += 2 * 4                  # vx, vy: two products, a sum, a divide
    per_pixel += 4                      # |det|, the compare, a select each
    return per_pixel * h * w
