"""The paper's benchmark applications (Table I), and a multi-rate
pyramid app, as single-source traced programs.

Each builder is now exactly what the paper promises: a plain Python
array function — operators for point math, :func:`fe.conv` /
:func:`fe.window` for local operators, shared formulas from
:mod:`repro.frontend.lib` — handed to :func:`fe.trace`, which
extracts, canonicalizes and validates the dataflow graph.  No
channels, no ``split`` stages, no reader/writer bookkeeping anywhere
below.

The hand-assembled stage-DSL graphs live on in
:mod:`repro.core.handbuilt` as the equivalence oracle (lightly
adapted: stage bodies now come from the shared library — see that
module's docstring): for every app the traced graph's canonical
:meth:`DataflowGraph.signature` equals the hand-built one's, and
outputs agree bit-exactly on every backend
(``tests/test_frontend.py``).

These graphs are consumed by examples/, benchmarks/fig5_app_latency.py,
benchmarks/fig6_opt_ladder.py and the test-suite — one source program
per app, every backend.
"""
from __future__ import annotations

from typing import Callable

import repro.frontend as fe
from repro.core.graph import DataflowGraph
from repro.core.handbuilt import HAND_BUILT
from repro.frontend import lib
from repro.frontend.lib import (GAUSS3, GAUSS5, JACOBI3, LAPLACE3, MEAN5,
                                SOBEL_X, SOBEL_Y, bilateral, conv_taps,
                                sobel_mag)

__all__ = ["APPS", "HAND_BUILT", "build_app", "compile_app"]

# back-compat aliases: these helpers lived here before they were
# hoisted into the shared kernel library (repro.frontend.lib)
_conv = conv_taps
_sobel_mag = sobel_mag
_bilateral = bilateral


# ----------------------------------------------------------------------
# application builders (traced single-source programs)
# ----------------------------------------------------------------------
def mean_filter(h: int, w: int) -> DataflowGraph:
    def mean_filter_src(img):
        return fe.conv(img, MEAN5)

    return fe.trace(mean_filter_src, (h, w), name="mean_filter")


def gaussian_blur(h: int, w: int) -> DataflowGraph:
    def gaussian_blur_src(img):
        return fe.conv(img, GAUSS5)

    return fe.trace(gaussian_blur_src, (h, w), name="gaussian_blur")


def bilateral_filter(h: int, w: int) -> DataflowGraph:
    def bilateral_src(img):
        return fe.window(img, (5, 5), lib.bilateral(), ii=4.0, fill=64.0)

    return fe.trace(bilateral_src, (h, w), name="bilateral_filter")


def sobel_luma(h: int, w: int) -> DataflowGraph:
    def sobel_luma_src(r, g, b):
        luma = lib.luma_rec601(r, g, b)
        return fe.window(luma, (3, 3), lib.sobel_mag)

    return fe.trace(sobel_luma_src, (h, w), (h, w), (h, w),
                    name="sobel_luma")


def unsharp_mask(h: int, w: int, amount: float = 1.5) -> DataflowGraph:
    def unsharp_src(img):
        blur = fe.conv(img, GAUSS5)
        return img + amount * (img - blur)

    return fe.trace(unsharp_src, (h, w), name="unsharp_mask")


def filter_chain(h: int, w: int) -> DataflowGraph:
    def filter_chain_src(img):
        c = img
        for _ in range(3):
            c = fe.conv(c, GAUSS3)
        return c

    return fe.trace(filter_chain_src, (h, w), name="filter_chain")


def jacobi(h: int, w: int) -> DataflowGraph:
    def jacobi_src(img):
        return fe.conv(img, JACOBI3)

    return fe.trace(jacobi_src, (h, w), name="jacobi")


def laplace(h: int, w: int) -> DataflowGraph:
    def laplace_src(img):
        return fe.conv(img, LAPLACE3)

    return fe.trace(laplace_src, (h, w), name="laplace")


def square(h: int, w: int) -> DataflowGraph:
    def square_src(img):
        return img * img

    return fe.trace(square_src, (h, w), name="square")


def sobel(h: int, w: int) -> DataflowGraph:
    def sobel_src(img):
        return fe.window(img, (3, 3), lib.sobel_mag)

    return fe.trace(sobel_src, (h, w), name="sobel")


def harris(h: int, w: int, k: float = 0.04) -> DataflowGraph:
    def harris_src(img):
        ix = fe.conv(img, SOBEL_X)
        iy = fe.conv(img, SOBEL_Y)
        ixx = ix * ix
        iyy = iy * iy
        ixy = ix * iy
        wxx = fe.conv(ixx, GAUSS5)
        wyy = fe.conv(iyy, GAUSS5)
        wxy = fe.conv(ixy, GAUSS5)
        return lib.harris_response(k)(wxx, wyy, wxy)

    return fe.trace(harris_src, (h, w), name="harris")


def shi_tomasi(h: int, w: int) -> DataflowGraph:
    def shi_tomasi_src(img):
        ix = fe.conv(img, SOBEL_X)
        iy = fe.conv(img, SOBEL_Y)
        ixx = ix * ix
        iyy = iy * iy
        ixy = ix * iy
        wxx = fe.conv(ixx, GAUSS5)
        wyy = fe.conv(iyy, GAUSS5)
        wxy = fe.conv(ixy, GAUSS5)
        return lib.lam_min(wxx, wyy, wxy)

    return fe.trace(shi_tomasi_src, (h, w), name="shi_tomasi")


def optical_flow_lk(h: int, w: int, eps: float = 1e-3) -> DataflowGraph:
    """Lucas-Kanade optical flow (paper Fig. 4): 16 compute stages."""
    def optical_flow_lk_src(f1, f2):
        ix = fe.conv(f1, SOBEL_X / 8.0)   # sobel/8 ~= centered difference
        iy = fe.conv(f1, SOBEL_Y / 8.0)
        it = f2 - f1
        ixx = ix * ix
        iyy = iy * iy
        ixy = ix * iy
        ixt = ix * it
        iyt = iy * it
        wxx = fe.conv(ixx, GAUSS5)
        wyy = fe.conv(iyy, GAUSS5)
        wxy = fe.conv(ixy, GAUSS5)
        wxt = fe.conv(ixt, GAUSS5)
        wyt = fe.conv(iyt, GAUSS5)
        vx = lib.lk_vx(eps)(wxx, wyy, wxy, wxt, wyt)
        vy = lib.lk_vy(eps)(wxx, wyy, wxy, wxt, wyt)
        return {"vx": vx, "vy": vy}

    return fe.trace(optical_flow_lk_src, (h, w), (h, w),
                    name="optical_flow_lk")


def pyramid_blend(h: int, w: int, levels: int = 4) -> DataflowGraph:
    """Multi-band blending of two RGB images under one 8-bit mask
    (Burt & Adelson, ACM TOG 2(4), 1983): per channel, blend the two
    Laplacian pyramids under the mask's Gaussian pyramid and collapse.
    PolyMage's "Pyramid Blending" benchmark runs it at 2048x2048, 4
    levels.

    The mask's pyramid is taken on its 8-bit levels and each level is
    scaled to [0, 1] where it weighs the blend (``REDUCE`` is linear, so
    this is ``REDUCE^l(M/255)`` up to rounding).  A scale ahead of a
    correlation would be folded into its taps by XLA's simplifier
    wherever the two share a fusion, and a kernel boundary keeps them
    apart, so the backends would round differently."""
    mix = lib.mask_blend(255.0)

    def pyramid_blend_src(a_r, a_g, a_b, b_r, b_g, b_b, mask):
        def gaussian(x):
            pyr = [x]
            for _ in range(levels - 1):
                pyr.append(fe.pyr_down(pyr[-1]))
            return pyr

        gm = gaussian(mask)
        out = {}
        for c, a, b in (("r", a_r, b_r), ("g", a_g, b_g), ("b", a_b, b_b)):
            ga, gb = gaussian(a), gaussian(b)
            s = mix(gm[-1], ga[-1], gb[-1])
            for lv in reversed(range(levels - 1)):
                la = ga[lv] - fe.pyr_up(ga[lv + 1])
                lb = gb[lv] - fe.pyr_up(gb[lv + 1])
                s = mix(gm[lv], la, lb) + fe.pyr_up(s)
            out[f"out_{c}"] = s
        return out

    return fe.trace(pyramid_blend_src, *[(h, w)] * 7, name="pyramid_blend")


#: name -> (builder, table-I stage count, n_inputs)
APPS: dict[str, tuple[Callable[..., DataflowGraph], int, int]] = {
    "mean_filter": (mean_filter, 1, 1),
    "gaussian_blur": (gaussian_blur, 1, 1),
    "bilateral_filter": (bilateral_filter, 1, 1),
    "sobel_luma": (sobel_luma, 2, 3),
    "unsharp_mask": (unsharp_mask, 3, 1),
    "filter_chain": (filter_chain, 3, 1),
    "jacobi": (jacobi, 1, 1),
    "optical_flow_lk": (optical_flow_lk, 16, 2),
    "harris": (harris, 9, 1),
    "shi_tomasi": (shi_tomasi, 9, 1),
    "laplace": (laplace, 1, 1),
    "square": (square, 1, 1),
    "sobel": (sobel, 1, 1),
    # not in Table I: REDUCE and EXPAND, the multi-rate stages (stage
    # count as written: 21 REDUCE, 27 EXPAND, 18 Laplacian subtractions,
    # 12 blends, 9 collapse adds)
    "pyramid_blend": (pyramid_blend, 87, 7),
}


def build_app(name: str, h: int = 1024, w: int = 1024) -> DataflowGraph:
    if name not in APPS:
        raise KeyError(f"unknown app {name!r}; choose from {sorted(APPS)}")
    return APPS[name][0](h, w)


def compile_app(name: str, h: int = 1024, w: int = 1024,
                backend="pallas", **kw):
    """Build + compile a Table-I app through the full pass pipeline.

    ``backend`` is a registered name or a
    :class:`~repro.backends.Backend` spec, forwarded verbatim to
    :func:`repro.core.compiler.compile_graph`.
    """
    from repro.core.compiler import compile_graph
    return compile_graph(build_app(name, h, w), backend=backend, **kw)
