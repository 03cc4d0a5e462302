"""Micro-batching: stack same-signature requests, launch ONE kernel.

Per-request dispatch pays the host-side launch overhead once per
item; a serving engine under load amortizes it by stacking requests
whose apps share a :meth:`~repro.core.host.CompiledApp.signature`
along a new leading axis and launching a single ``vmap``-ped kernel.

Two host-side overheads are engineered out of the hot path:

- **bucketed pad shapes** — padding every batch to ``max_batch``
  makes a 2-request batch pay a 32-wide launch.  ``launch`` instead
  pads to the next power-of-two *bucket* (rounded to a replica
  multiple), and each ``(signature, bucket)`` pair gets its own
  jitted entry in :attr:`_fns` — a small, fixed family of compiled
  shapes per app instead of one oversized one.  ``bucket_launches``
  records which buckets actually ran.
- **zero-copy staging** — request rows are written directly into
  *pinned* per-bucket staging buffers (allocated once, rotated
  ``staging_depth`` deep to stay clear of in-flight transfers)
  instead of re-stacking a fresh host array per batch: one
  ``memcpy`` per row, no per-batch allocation, the software analogue
  of FLOWER's reused XRT buffer objects between command-queue runs.

The batched callable is built per bucket (jit keeps it warm) with
every input donated — the staged device buffers are never reused, so
their HBM can be recycled in place.

With ``replicas > 1`` the padded batch is additionally *sharded* over
a 1-D device mesh: replica ``r`` executes rows ``[r*B/k, (r+1)*B/k)``
of every staging buffer — the batch-parallel farm (FastFlow's
``ff_farm`` worker replication, FLOWER's kernel replication) on top of
the same single-launch dispatch.  Bucket widths are held to a
multiple of the replica count so every launch keeps one compiled
kernel shape per replica.
"""
from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.host import CompiledApp
from repro.obs.tracer import program_span, resolve_tracer

__all__ = ["MicroBatcher"]


class MicroBatcher:
    """Stacks same-signature requests and launches one batched kernel.

    ``launch`` is asynchronous: it returns the stacked device outputs
    without blocking, so the engine can keep further batches in flight
    (slot-pool pipelining) before forcing the first to host memory.
    """

    def __init__(self, max_batch: int = 8, donate: bool = True,
                 replicas: int = 1, replica_axis: str = "replica",
                 devices: list | None = None, staging_depth: int = 2,
                 trace: Any = None, backend=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if max_batch % replicas != 0:
            raise ValueError(
                f"max_batch={max_batch} must divide evenly over "
                f"replicas={replicas}: every replica serves "
                f"max_batch/replicas rows of the padded batch")
        if staging_depth < 1:
            raise ValueError(
                f"staging_depth must be >= 1, got {staging_depth}")
        self.max_batch = max_batch
        self.donate = donate
        self.replicas = replicas
        self.replica_axis = replica_axis
        # donation is categorically ignored on CPU (XLA warns on every
        # call); resolve it per-platform up front so CPU never builds a
        # donating entry — swapping the entry later would recompile it.
        # The decision itself is the backend's donation policy
        # (Backend.resolve_donate); the registry default reproduces the
        # old inline probe bit-for-bit.
        plat = (devices[0] if devices else jax.devices()[0]).platform
        from repro.backends import resolve
        self.backend = resolve(backend) if backend is not None else None
        if self.backend is not None:
            self._donate = self.backend.resolve_donate(donate, plat)
        else:
            self._donate = donate and plat != "cpu"
        #: how many launches of one (sig, width) bucket get distinct
        #: staging buffers before the first is rewritten; keep STRICTLY
        #: greater than the number of concurrently unforced launches —
        #: JAX's CPU backend zero-copy aliases aligned numpy inputs, so
        #: rewriting a rotation mutates the device-side view of any
        #: batch that has not finished executing yet
        self.staging_depth = staging_depth
        self._mesh = None
        if replicas > 1:
            from repro.parallel.sharding import replica_mesh
            self._mesh = replica_mesh(replicas, axis=replica_axis,
                                      devices=devices)
        #: jitted batched kernels, one per (signature, bucket width)
        self._fns: dict[tuple[str, int], Callable] = {}
        #: buckets whose first launch already probed donation support
        self._probed: set[tuple[str, int]] = set()
        #: pinned staging buffers: (sig, width) -> staging_depth
        #: rotations of per-input host arrays
        self._staging: dict[tuple[str, int], list[list[np.ndarray]]] = {}
        self._staging_clock: dict[tuple[str, int], int] = {}
        #: width -> number of launches that used that bucket
        self.bucket_launches: dict[int, int] = {}
        #: launches begun so far; launch k carries ``batch=k`` on its
        #: ``batch.stack`` / ``batch.launch`` spans
        self.launches = 0
        #: flight recorder that also records the stack/launch spans
        #: (None = profiler annotations only; ``False`` opts out even
        #: of the global tracer)
        self.tracer = resolve_tracer(trace) if trace is not False else None

    # ------------------------------------------------------------------
    # bucketed pad widths
    # ------------------------------------------------------------------
    def width(self, n: int, pad_to: int | None = None) -> int:
        """Staged width of an ``n``-request batch: its :meth:`bucket`,
        or ``pad_to`` if wider, rounded up to a replica multiple."""
        width = max(pad_to or 0, self.bucket(n), n)
        return -(-width // self.replicas) * self.replicas

    def bucket(self, n: int) -> int:
        """Padded width for an ``n``-request batch.

        Next power of two >= ``n``, rounded up to a replica multiple
        and capped at ``max_batch`` — so a 2-request batch launches a
        2-wide kernel, not a ``max_batch``-wide one, and the set of
        compiled batch shapes per app stays logarithmic.
        """
        if n < 1:
            raise ValueError(f"bucket width needs n >= 1, got {n}")
        w = 1
        while w < n:
            w <<= 1
        w = -(-w // self.replicas) * self.replicas
        return min(w, self.max_batch)

    def batched_fn(self, app: CompiledApp, width: int | None = None) -> Callable:
        """The jitted, vmapped, input-donating kernel for one bucket.

        Keyed on ``(signature, width)`` so every bucket keeps its own
        compiled entry (``width=None`` keys a single generic entry
        that jit re-specializes per shape).  With replicas, the vmapped
        kernel runs under ``shard_map`` with batch-dim shardings on
        every input/output: each replica's rows sit on its own device
        and the k copies of the kernel run concurrently with no
        cross-device traffic (the farm has no inter-worker channels).
        """
        key = (app.signature(), width if width is not None else -1)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_fn(app, donate=self._donate)
            self._fns[key] = fn
        return fn

    def _build_fn(self, app: CompiledApp, donate: bool) -> Callable:
        donate_argnums = (tuple(range(len(app.input_names)))
                          if donate else ())
        kwargs: dict[str, Any] = dict(donate_argnums=donate_argnums)
        fn = jax.vmap(app.fn)
        if self._mesh is not None:
            # each replica runs the vmapped kernel on its own rows under
            # shard_map: XLA cannot partition a Mosaic kernel itself
            rows = P(self.replica_axis)
            fn = shard_map(fn, mesh=self._mesh,
                           in_specs=tuple(rows for _ in app.input_names),
                           out_specs=tuple(rows for _ in app.output_names),
                           check_vma=False)
            batch_row = NamedSharding(self._mesh, rows)
            kwargs["in_shardings"] = tuple(
                batch_row for _ in app.input_names)
            kwargs["out_shardings"] = tuple(
                batch_row for _ in app.output_names)
        return jax.jit(fn, **kwargs)

    def _call(self, app: CompiledApp, width: int,
              args: Sequence[np.ndarray]) -> Any:
        """Invoke one bucket's kernel; steady state is a bare call.

        CPU resolved donation away at construction, so the common
        path is a single dict lookup + call.  On other backends the
        first launch of each bucket runs under a warning probe: if the
        backend reports it ignored donation anyway (the catch/emit
        machinery costs more than a small batch's kernel), the
        bucket's entry is rebuilt without donation — one extra compile
        there, zero warning overhead ever after.  Backends that honor
        donation never warn and keep their donating entry.
        """
        key = (app.signature(), width)
        fn = self.batched_fn(app, width)
        if not self._donate or key in self._probed:
            return fn(*args)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            outs = fn(*args)
        donation_ignored = False
        for rec in caught:
            if "donated" in str(rec.message):
                donation_ignored = True
            else:                      # not ours: let it through
                warnings.warn_explicit(rec.message, rec.category,
                                       rec.filename, rec.lineno)
        if donation_ignored:
            self._fns[key] = self._build_fn(app, donate=False)
        self._probed.add(key)
        return outs

    # ------------------------------------------------------------------
    # zero-copy staging
    # ------------------------------------------------------------------
    def _staging_bufs(self, app: CompiledApp, width: int) -> list[np.ndarray]:
        """The next rotation of pinned staging buffers for one bucket."""
        key = (app.signature(), width)
        rotations = self._staging.get(key)
        if rotations is None:
            rotations = [
                [np.zeros((width,) + tuple(ch.shape), np.dtype(ch.dtype))
                 for ch in app.graph.graph_inputs]
                for _ in range(self.staging_depth)
            ]
            self._staging[key] = rotations
            self._staging_clock[key] = 0
        clock = self._staging_clock[key]
        self._staging_clock[key] = clock + 1
        return rotations[clock % self.staging_depth]

    def stack(self, app: CompiledApp, requests: Sequence[Any],
              pad_to: int | None = None,
              check_shapes: bool = True) -> list[np.ndarray]:
        """Write each request's inputs into the pinned staging buffers.

        Rows land directly in a preallocated ``(width, *shape)`` host
        buffer (one memcpy per row — no per-batch allocation or
        restack); rows beyond ``len(requests)`` keep whatever the
        previous batch staged (padding rows are computed but sliced
        away, so their values are irrelevant).  ``pad_to`` forces a
        width; by default the power-of-two :meth:`bucket` is used.
        The returned buffers are valid until ``staging_depth`` more
        batches of the same (signature, width) are staged.  Rejects an
        empty request list and per-request shape mismatches with
        precise errors instead of letting the row copy fail obscurely
        — the engine's batch formation can race to empty at shutdown,
        and a 0-d/scalar channel input must stage into a ``(B,)``
        buffer, not crash.
        """
        if not requests:
            raise ValueError(
                "cannot stack an empty request batch (engine shutdown "
                "race?); callers must skip empty batches")
        args = self._staging_bufs(app, self.width(len(requests), pad_to))
        for j, ch in enumerate(app.graph.graph_inputs):
            buf = args[j]
            name = ch.name
            if check_shapes:
                shape = tuple(ch.shape)
                for idx, r in enumerate(requests):
                    row = np.asarray(r.inputs[name])
                    if row.shape != shape:
                        raise ValueError(
                            f"request[{idx}] input {name!r}: expected "
                            f"shape {shape}, got {row.shape}")
                    buf[idx, ...] = row
            else:
                # engine path: rows were shape-checked at submit();
                # numpy's row assignment casts + copies in one shot
                for idx, r in enumerate(requests):
                    buf[idx, ...] = r.inputs[name]
        return args

    def launch(self, app: CompiledApp, requests: Sequence[Any],
               pad_to: int | None = None,
               timings: dict[str, float] | None = None,
               check_shapes: bool = True) -> dict[str, jnp.ndarray]:
        """Dispatch one batched kernel; return stacked outputs, unblocked.

        ``requests`` need only expose ``.inputs`` (a name->array dict);
        they must all share ``app``'s signature.  The batch is padded
        to its power-of-two bucket (or ``pad_to``); output rows beyond
        ``len(requests)`` are padding and must be ignored by the
        caller.  ``timings``, when given, receives the host-side
        ``stack`` (staging-copy) and ``launch`` (dispatch) phase
        durations in seconds.  Both phases are program spans
        (``batch.stack``, ``batch.launch``) carrying this launch's
        ``batch`` id (:attr:`launches` before the call) and ``width``.
        """
        if len(requests) > self.max_batch:
            raise ValueError(
                f"batch of {len(requests)} exceeds max_batch={self.max_batch}")
        if not requests:
            raise ValueError(
                "cannot stack an empty request batch (engine shutdown "
                "race?); callers must skip empty batches")
        seq = self.launches
        self.launches += 1
        width = self.width(len(requests), pad_to)
        t0 = time.perf_counter()
        with program_span("batch.stack", self.tracer, batch=seq,
                          width=width, rows=len(requests)):
            args = self.stack(app, requests, pad_to=pad_to,
                              check_shapes=check_shapes)
        t1 = time.perf_counter()
        with program_span("batch.launch", self.tracer, batch=seq,
                          width=width):
            outs = self._call(app, width, args)
        t2 = time.perf_counter()
        self.bucket_launches[width] = self.bucket_launches.get(width, 0) + 1
        if timings is not None:
            timings["stack"] = t1 - t0
            timings["launch"] = t2 - t1
        return dict(zip(app.output_names, outs))
