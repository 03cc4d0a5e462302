"""Shared kernel library: every coefficient table and pointwise
formula used by the Table-I apps, defined exactly once.

Hoisted out of ``repro.core.apps`` so the traced single-source
builders, the hand-built oracle graphs, the examples, the benchmarks
and the tests all reference the *same objects*.  That sharing is
load-bearing, not just tidy: stage-function identity feeds
:meth:`repro.core.graph.DataflowGraph.signature`, so a traced app and
its hand-built oracle can only hash equal because both sides draw
their stage bodies from here.

Three families:

- **taps** — the classic stencil coefficient tables (``GAUSS3`` …),
  plus :func:`conv_taps` which unrolls a table into a patch function
  with zero-taps elided (what an FPGA synthesizer does to fixed
  coefficients).
- **local operators** — patch functions for ``stencil`` stages
  (:func:`sobel_mag`, :func:`bilateral`).
- **pointwise formulas** — ``@pointfn``-lifted elementwise math
  (:data:`luma_rec601`, :func:`harris_response`, …): call them on
  arrays to compute, on Planes to record one stage.

The canonical operator bodies (``add``, ``sub``, ``scale(c)``, …)
are re-exported from :mod:`repro.frontend.tracer` for the hand-built
graphs to use.
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.frontend.tracer import (add, div, mul, neg, offset, pointfn,
                                   powc, scale, square, sub, subc)

__all__ = [
    "GAUSS3", "GAUSS5", "MEAN5", "SOBEL_X", "SOBEL_Y", "LAPLACE3",
    "JACOBI3", "PYR_UP5",
    "conv_taps", "sobel_mag", "bilateral", "pyr_down_taps", "pyr_up_taps",
    "luma_rec601", "harris_response", "lam_min", "lk_vx", "lk_vy",
    "mask_blend",
    # canonical elementwise ops (tracer re-exports)
    "add", "sub", "mul", "div", "square", "neg", "offset", "scale",
    "subc", "powc",
]


# ----------------------------------------------------------------------
# coefficient tables
# ----------------------------------------------------------------------
GAUSS3 = np.array([[1, 2, 1], [2, 4, 2], [1, 2, 1]], np.float32) / 16.0
GAUSS5 = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1]).astype(np.float32) / 256.0
MEAN5 = np.ones((5, 5), np.float32) / 25.0
SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32)
SOBEL_Y = SOBEL_X.T.copy()
LAPLACE3 = np.array([[0, 1, 0], [1, -4, 1], [0, 1, 0]], np.float32)
JACOBI3 = np.array([[0, 1, 0], [1, 0, 1], [0, 1, 0]], np.float32) / 4.0
#: EXPAND's taps (Burt & Adelson): GAUSS5 times 4, since three of every
#: four samples of the zero-stuffed plane are zeros
PYR_UP5 = GAUSS5 * 4.0


def conv_taps(weights: np.ndarray) -> Callable:
    """Patch function for a fixed coefficient table.

    Taps are unrolled as scalar multiplies (zeros elided) — the same
    constant folding an FPGA synthesizer applies to fixed
    coefficients, and it keeps stage fns free of captured array
    constants (a Pallas kernel requirement).
    """
    taps = [float(v) for v in np.asarray(weights).reshape(-1)]

    def fn(p):
        acc = None
        for i, t in enumerate(taps):
            if t == 0.0:
                continue
            term = p[i] if t == 1.0 else p[i] * t
            acc = term if acc is None else acc + term
        return acc

    return fn


# ----------------------------------------------------------------------
# local (stencil) operators
# ----------------------------------------------------------------------
def sobel_mag(p):
    """Gradient magnitude from one 3x3 patch set (both Sobel taps)."""
    gx = conv_taps(SOBEL_X)(p)
    gy = conv_taps(SOBEL_Y)(p)
    return jnp.sqrt(gx * gx + gy * gy + 1e-12)


def bilateral(sigma_s: float = 2.0, sigma_r: float = 0.25) -> Callable:
    """5x5 bilateral filter patch function (range kernel unrolled)."""
    kh = kw = 5
    ds = np.array([[(i - 2) ** 2 + (j - 2) ** 2 for j in range(kw)]
                   for i in range(kh)], np.float32).reshape(-1)
    ws = [float(v) for v in np.exp(-ds / (2 * sigma_s ** 2))]
    inv2r = 1.0 / (2 * sigma_r ** 2)

    def fn(p):
        center = p[kh * kw // 2]
        sum_w = None
        sum_wp = None
        for i, wsi in enumerate(ws):  # unrolled taps (scalar consts)
            wr = jnp.exp(-(p[i] - center) ** 2 * inv2r) * wsi
            sum_w = wr if sum_w is None else sum_w + wr
            term = wr * p[i]
            sum_wp = term if sum_wp is None else sum_wp + term
        return sum_wp / (sum_w + 1e-12)

    return fn


#: the patch functions of REDUCE and EXPAND (OpenCV's ``pyrDown`` and
#: ``pyrUp`` filters), shared by the frontend and the hand-built graphs
pyr_down_taps = conv_taps(GAUSS5)
pyr_up_taps = conv_taps(PYR_UP5)


# ----------------------------------------------------------------------
# pointwise formulas
# ----------------------------------------------------------------------
@pointfn
def luma_rec601(r, gc, b):
    """ITU-R BT.601 luma from RGB planes."""
    return 0.299 * r + 0.587 * gc + 0.114 * b


def harris_response(k: float = 0.04):
    """Harris corner response over the windowed structure tensor."""
    @pointfn
    def response(a, c, b):
        return (a * c - b * b) - k * (a + c) * (a + c)

    return response


@pointfn
def lam_min(a, c, b):
    """Smaller eigenvalue of the 2x2 structure tensor (Shi-Tomasi)."""
    tr2 = (a + c) * 0.5
    det = a * c - b * b
    return tr2 - jnp.sqrt(jnp.maximum(tr2 * tr2 - det, 0.0) + 1e-12)


def mask_blend(levels: float = 255.0):
    """Mix two planes under a mask plane of ``levels`` + 1 steps:
    ``w*a + (1-w)*b`` with ``w = m/levels``."""
    @pointfn
    def mix(m, a, b):
        w = m / levels
        return w * a + (1.0 - w) * b

    return mix


def lk_vx(eps: float = 1e-3):
    """Lucas-Kanade horizontal flow from the windowed moments."""
    @pointfn
    def vx(a, c, b, tx, ty):
        det = a * c - b * b
        return jnp.where(jnp.abs(det) > eps, (-c * tx + b * ty) / det, 0.0)

    return vx


def lk_vy(eps: float = 1e-3):
    """Lucas-Kanade vertical flow from the windowed moments."""
    @pointfn
    def vy(a, c, b, tx, ty):
        det = a * c - b * b
        return jnp.where(jnp.abs(det) > eps, (b * tx - a * ty) / det, 0.0)

    return vy
