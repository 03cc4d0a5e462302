"""Single-source tracing frontend: plain array code in, dataflow app out.

This package is the paper's programmer-facing layer: instead of
hand-assembling a :class:`~repro.core.graph.DataflowGraph` (naming
channels, inserting splits, minding the single-reader contract), the
user writes an ordinary Python function over arrays and the frontend
*extracts* the graph — operator overloading records point ops, the
library ops (:func:`conv`, :func:`window`, :func:`pyr_down`,
:func:`pyr_up`, :func:`reduce`, :func:`where`, :func:`custom`) record
the structured stages, and the standard pass pipeline canonicalizes
the result.

Conventional use::

    import repro.frontend as fe
    from repro.frontend.lib import GAUSS5

    @fe.dataflow_fn
    def unsharp(img):
        blur = fe.conv(img, GAUSS5)
        return img + 1.5 * (img - blur)

    out = unsharp(frame)                  # trace+compile+run, memoized
    app = unsharp.compile(fe.spec((512, 1024)), tune="auto")
    graph = unsharp.graph_for({"img": frame})   # for StreamEngine.submit

See ``docs/frontend.md`` for the library surface, the tracing rules,
and what is (and is not) traceable.
"""
from repro.frontend.diagnostics import (TraceControlFlowError,
                                        TraceDtypeError, TraceError,
                                        TraceLeakError, TraceShapeError)
from repro.frontend.tracer import (DataflowFunction, InputSpec, Plane,
                                   PointFn, dataflow_fn, pointfn, trace)
from repro.frontend.ops import (abs, conv, cos, custom, exp, log, maximum,
                                minimum, pyr_down, pyr_up, reduce, select,
                                sign, sin, spec, sqrt, tanh, where, window)
from repro.frontend import lib

__all__ = [
    "Plane", "InputSpec", "PointFn", "pointfn", "trace", "dataflow_fn",
    "DataflowFunction", "spec",
    "conv", "window", "pyr_down", "pyr_up", "reduce", "where", "select",
    "custom",
    "sqrt", "exp", "log", "abs", "tanh", "sin", "cos", "sign",
    "maximum", "minimum", "lib",
    "TraceError", "TraceShapeError", "TraceDtypeError",
    "TraceControlFlowError", "TraceLeakError",
]
