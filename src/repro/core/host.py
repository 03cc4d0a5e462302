"""Host-code generation (FLOWER contribution C4).

The paper generates all XRT boilerplate (context, buffers, ``setArg``,
kernel launch, H2D/D2H copies) from the same single source as the
device code.  The TPU analogue of "host code" is the *launcher*: buffer
placement & sharding, donation, the jitted step function, and the
compile artifacts.  :func:`build_host_app` derives all of it from the
scheduled dataflow graph — the user never writes glue code, and
host/device can never drift apart.  The user-facing entry point is
:func:`repro.core.compiler.compile_graph`, which runs the full
pipeline (canonicalize -> validate -> partition -> lower) and finishes
here.

For fidelity (and debuggability) :meth:`CompiledApp.host_program`
renders the generated launch plan as an XRT-style listing, mirroring
the paper's Section IV-C example.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import DataflowGraph
from repro.core.schedule import Schedule
from repro.obs.tracer import get_tracer, program_span

__all__ = ["CompiledApp", "LaunchHandle", "build_host_app"]


@dataclasses.dataclass
class LaunchHandle:
    """Future-like handle for one asynchronously dispatched execution.

    Holds the (possibly still in-flight) device arrays; ``result()``
    blocks until they are ready.  The software analogue of waiting on
    an XRT event from ``enqueueTask``.
    """

    outputs: dict[str, Any]

    def done(self) -> bool:
        """True when every output buffer has landed (non-blocking)."""
        return all(o.is_ready() for o in self.outputs.values()
                   if hasattr(o, "is_ready"))

    def result(self) -> dict[str, Any]:
        """Block until the computation finishes; return the outputs.

        The wait is the ``app.wait`` program span."""
        with program_span("app.wait", get_tracer()):
            jax.block_until_ready(self.outputs)
        return self.outputs


@dataclasses.dataclass
class BufferDecl:
    name: str
    shape: tuple[int, ...]
    dtype: str
    direction: str        # "in" | "out"
    bundle: int | None
    donated: bool


@dataclasses.dataclass
class CompiledApp:
    """A fully-lowered dataflow application (device + generated host)."""

    graph: DataflowGraph
    schedule: Schedule
    #: the resolved :class:`~repro.backends.Backend` record this app
    #: was lowered for (``app.backend.name`` for the display string)
    backend: Any
    fn: Callable                        # jitted: (*inputs) -> tuple(outputs)
    lowered: Any
    compiled: Any
    buffers: list[BufferDecl]
    input_names: list[str]
    output_names: list[str]
    mesh: Mesh | None = None

    def __call__(self, **inputs: Any) -> dict[str, Any]:
        args = [inputs[n] for n in self.input_names]
        outs = self.fn(*args)
        return dict(zip(self.output_names, outs))

    def launch(self, **inputs: Any) -> "LaunchHandle":
        """Asynchronously dispatch one execution (the XRT ``enqueueTask``).

        Returns immediately with a future-like :class:`LaunchHandle` —
        JAX's async dispatch means the device works while the host
        keeps queuing.  The serving engine
        (:class:`repro.runtime.engine.StreamEngine`) builds its
        double-buffered pipeline on exactly this: launch item k+1
        before blocking on item k.  The dispatch is the ``app.launch``
        program span (no args: it runs once a frame).
        """
        args = [inputs[n] for n in self.input_names]
        with program_span("app.launch", get_tracer()):
            outs = self.fn(*args)
        return LaunchHandle(dict(zip(self.output_names, outs)))

    def signature(self) -> str:
        """Cache/batching identity: canonical graph digest + backend.

        Requests whose apps share a signature are interchangeable for
        the micro-batcher (same topology, shapes, stage bodies and
        backend), and repeated compiles of such graphs hit the
        :class:`repro.runtime.cache.CompileCache`.  The backend half is
        :meth:`~repro.backends.Backend.cache_key` — name plus a digest
        of capabilities and constants — so two registrations under one
        name with different constants never collide.  Memoized: the
        graph is post-canonicalization and does not change under an
        already-compiled app, and the serving engine calls this on
        every request.
        """
        sig = getattr(self, "_signature", None)
        if sig is None:
            from repro.backends import resolve
            sig = f"{self.graph.signature()}:{resolve(self.backend).cache_key()}"
            self._signature = sig
        return sig

    # -- introspection -------------------------------------------------
    def cost(self) -> dict[str, float]:
        ca = self.compiled.cost_analysis() or {}
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "bytes_total": sum(float(v) for k, v in ca.items()
                               if k.startswith("bytes accessed")),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }

    def memory(self) -> dict[str, int]:
        ma = self.compiled.memory_analysis()
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, k):
                out[k] = int(getattr(ma, k))
        return out

    def host_program(self) -> str:
        """Render the generated host code as an XRT-style listing.

        This is the *static* single-shot launch plan.  The dynamic
        counterpart — command queue, backpressure, micro-batching,
        telemetry — is the serving runtime: see
        :class:`repro.runtime.engine.StreamEngine`, which turns this
        app into a long-lived service.
        """
        lines = [
            "// ---- generated host program (XRT-style rendering) ----",
            "auto device = xcl::get_devices()[0];",
            'auto bin = xcl::read_binary_file("%s.xclbin");' % self.graph.name,
            "auto q = cl::CommandQueue(context, device, 0);",
        ]
        for b in self.buffers:
            flag = "CL_MEM_READ_ONLY" if b.direction == "in" else "CL_MEM_WRITE_ONLY"
            lines.append(
                f"cl::Buffer {b.name}(context, {flag}, /*bytes=*/"
                f"{int(np.prod(b.shape))* np.dtype(b.dtype).itemsize}); "
                f"// bundle=mem{b.bundle}"
                + (" donated" if b.donated else ""))
        for b in self.buffers:
            if b.direction == "in":
                lines.append(f"q.enqueueWriteBuffer({b.name}, ...);  // H2D")
        for gi, g in enumerate(self.schedule.groups):
            names = ",".join(s.name for s in g.stages)
            vec = (f" tile={g.tile} vector_factor={g.vector_factor}"
                   if g.tile is not None else "")
            lines.append(f"launch kernel[{gi}]  "
                         f"// dataflow tasks: {names}{vec}")
        for b in self.buffers:
            if b.direction == "out":
                lines.append(f"q.enqueueReadBuffer({b.name}, ...);   // D2H")
        return "\n".join(lines)


def build_host_app(sched: Schedule, run: Callable,
                   *, backend="pallas", mesh: Mesh | None = None,
                   data_axis: str | Sequence[str] = "data",
                   donate: Sequence[str] = (),
                   jit: bool = True) -> CompiledApp:
    """Generate the host launcher around an already-lowered graph.

    ``run`` is the whole-graph function produced by
    :func:`repro.core.fusion.lower_graph`; the graph is taken from the
    schedule (post-canonicalization) so launcher and kernels can never
    disagree about the I/O signature.  When ``mesh`` is given, every
    2-D plane is row-sharded over ``data_axis`` (a TPU "memory bundle"
    at the cluster scale: parallel DAG paths live in different
    per-device HBM shards and transfer concurrently).  Donation lets
    an output reuse an input's HBM.
    """
    from repro.backends import resolve
    backend = resolve(backend)
    graph = sched.graph
    input_names = [c.name for c in graph.graph_inputs]
    output_names = [c.name for c in graph.graph_outputs]

    def step(*args):
        outs = run(dict(zip(input_names, args)))
        return tuple(outs[n] for n in output_names)

    in_avals = [jax.ShapeDtypeStruct(c.shape, c.dtype)
                for c in graph.graph_inputs]

    donate_argnums = tuple(i for i, n in enumerate(input_names)
                           if n in donate)
    jit_kwargs: dict[str, Any] = dict(donate_argnums=donate_argnums)
    if mesh is not None:
        def shard(c):
            spec_dims = [None] * len(c.shape)
            if len(c.shape) >= 1 and c.shape[0] % mesh.shape[_first(data_axis)] == 0:
                spec_dims[0] = data_axis
            return NamedSharding(mesh, P(*spec_dims))
        jit_kwargs["in_shardings"] = tuple(shard(c) for c in graph.graph_inputs)
        jit_kwargs["out_shardings"] = tuple(shard(c) for c in graph.graph_outputs)

    fn = jax.jit(step, **jit_kwargs) if jit else step
    lowered = fn.lower(*in_avals) if jit else None
    compiled = lowered.compile() if jit else None

    buffers = [BufferDecl(c.name, c.shape, str(np.dtype(c.dtype)), "in",
                          c.bundle, c.name in donate)
               for c in graph.graph_inputs]
    buffers += [BufferDecl(c.name, c.shape, str(np.dtype(c.dtype)), "out",
                           c.bundle, False)
                for c in graph.graph_outputs]

    return CompiledApp(graph, sched, backend, fn, lowered, compiled,
                       buffers, input_names, output_names, mesh)


def _first(axis: str | Sequence[str]) -> str:
    return axis if isinstance(axis, str) else axis[0]
