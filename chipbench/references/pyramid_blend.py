"""Plain reference of ``pyramid_blend``: multi-band blending of two RGB
images under one 8-bit mask (Burt & Adelson, "A Multiresolution Spline
With Application to Image Mosaics", ACM TOG 2(4), 1983), at the 4
levels of PolyMage's "Pyramid Blending" benchmark.

Written from the paper's equations in straightforward ``jax.numpy``,
importing nothing of the system under test:

- ``reduce(x)``: correlate the zero-padded plane with the 5x5 binomial
  ``(1 4 6 4 1)^T (1 4 6 4 1) / 256``, keep the even rows and columns
  (OpenCV's ``pyrDown`` with a zero boundary);
- ``expand(x)``: ``x`` on the even rows and columns of a zero plane
  twice as large, correlated with the same taps times 4 (``pyrUp``);
- Gaussian pyramids ``G_{l+1} = reduce(G_l)`` of each channel of ``A``
  and ``B`` and of the mask ``M``; Laplacian ``L_l = G_l -
  expand(G_{l+1})`` for ``l < 3`` and ``L_3 = G_3``;
- per level, with the mask level as a weight ``w = GM_l / 255``:
  ``LS_l = w * LA_l + (1 - w) * LB_l``; collapse ``S_3 = LS_3``,
  ``S_l = LS_l + expand(S_{l+1})``; the output is ``S_0``.

The mask's pyramid is taken on its 8-bit levels and each level scaled
where it weighs the blend, as the program does; the paper scales first,
which ``reduce`` being linear makes the same up to rounding.  Every
correlation is accumulated tap by tap in row-major order, skipping zero
taps.  ``dtype`` is the precision every value is held and computed in:
float32 is the configuration's, bfloat16 the control's.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

INPUTS = ("a_r", "a_g", "a_b", "b_r", "b_g", "b_b", "mask")
OUTPUTS = ("out_r", "out_g", "out_b")
LEVELS = 4
MASK_LEVELS = 255.0

BINOMIAL5 = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1]) / 256.0


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


def reduce(x):
    return correlate(x, BINOMIAL5)[::2, ::2]


def expand(x):
    h, w = x.shape
    up = jnp.zeros((2 * h, 2 * w), x.dtype).at[::2, ::2].set(x)
    return correlate(up, 4.0 * BINOMIAL5)


def gaussian(x):
    pyr = [x]
    for _ in range(LEVELS - 1):
        pyr.append(reduce(pyr[-1]))
    return pyr


def blend(m, a, b):
    w = m / MASK_LEVELS
    return w * a + (1.0 - w) * b


def reference(inputs: dict, dtype=jnp.float32) -> dict:
    x = {k: jnp.asarray(inputs[k]).astype(dtype) for k in INPUTS}
    gm = gaussian(x["mask"])
    out = {}
    for c in "rgb":
        ga, gb = gaussian(x[f"a_{c}"]), gaussian(x[f"b_{c}"])
        s = blend(gm[-1], ga[-1], gb[-1])
        for lv in reversed(range(LEVELS - 1)):
            la = ga[lv] - expand(ga[lv + 1])
            lb = gb[lv] - expand(gb[lv + 1])
            s = blend(gm[lv], la, lb) + expand(s)
        out[f"out_{c}"] = s
    return out


def min_ops(h: int, w: int) -> int:
    """Arithmetic operations per frame, as the algorithm states them.

    With ``n_l = h * w / 4**l`` pixels at level ``l``:

    - REDUCE, computed only where it is kept: 25 multiplies and 24 adds
      per output pixel, for 7 planes at levels 1..3;
    - EXPAND, only the products with the samples (three of every four
      stuffed samples are zeros): per 2x2 output block 9 + 6 + 6 + 4 = 25
      multiplies and 8 + 5 + 5 + 3 = 21 adds, so 46/4 per output pixel,
      for 6 Laplacian planes and 3 collapse planes at levels 0..2;
    - per channel, the Laplacian subtractions (2 at levels 0..2), the
      blend (the weight's divide, ``1 - w``, two multiplies and an add:
      5 at levels 0..3) and the collapse add (1 at levels 0..2).
    """
    n = [h * w / 4 ** lv for lv in range(LEVELS)]
    reduce_ops = 7 * (25 + 24) * sum(n[1:])
    expand_ops = 9 * (46 / 4) * sum(n[:-1])
    point_ops = 3 * ((2 + 1) * sum(n[:-1]) + 5 * sum(n))
    return int(reduce_ops + expand_ops + point_ops)
