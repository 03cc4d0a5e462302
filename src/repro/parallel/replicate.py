"""Spatial replication of compiled dataflow apps (FLOWER "replication").

The paper's hardware-parallelism taxonomy (after de Fine Licht et al.)
has two axes: *vectorization* widens one processing element's datapath
(:mod:`repro.core.vectorize`), *replication* instantiates the whole
pipeline k times and feeds each copy a slice of the plane.  On an FPGA
the copies are duplicated dataflow regions; here they are devices on a
1-D ``replica`` mesh, and the plane is row-partitioned with
``shard_map``.

Stencil stages need rows owned by the neighbouring shard: the
replicator computes the graph-wide cumulative halo (the same backward
DP the scheduler runs per fusion group, extended over the whole stage
DAG), recompiles the app once for the halo-extended local plane, and
exchanges halo rows over the ring before every launch
(:func:`repro.parallel.collectives.halo_exchange_rows`).  Missing
neighbours at the global top/bottom contribute zeros — identical to
the compiler's zero-padding boundary — so a replicated app is
bit-exact against the single-device app.  On one device the exchange
degenerates to pure zero padding and the identical code path runs:
CI on CPU exercises replication without a multi-chip host.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.backends import resolve, resolve_calibrated
from repro.core.fusion import lower_graph
from repro.core.graph import Channel, DataflowGraph, GraphError
from repro.core.host import CompiledApp, LaunchHandle
from repro.core.schedule import Schedule, build_schedule
from repro.parallel.collectives import halo_exchange_rows
from repro.parallel.sharding import replica_mesh

__all__ = ["ReplicatedApp", "replicate_app", "graph_input_halo",
           "replication_kwarg_routing", "UNROUTED_COMPILE_KWARGS"]

#: ``compile_graph`` knobs replication deliberately does NOT forward:
#: the shard_map launcher replaces the generated host launcher (mesh /
#: data_axis / donate / jit), and tracing is engine-level plumbing.
#: Everything else in ``compile_graph``'s signature must route into the
#: scheduler or the lowering — ``replication_kwarg_routing`` derives
#: that split from the live signatures, and a regression test asserts
#: full coverage so a NEW compile kwarg cannot be silently dropped.
UNROUTED_COMPILE_KWARGS = frozenset(
    {"mesh", "data_axis", "donate", "jit", "trace"})

#: kwargs consumed by the tuning/calibration resolution steps
#: themselves (not by the scheduler/lowering signatures)
_TUNE_KWARGS = frozenset({"tune", "tune_cache", "calibrate"})


def replication_kwarg_routing() -> tuple[frozenset, frozenset, frozenset]:
    """Derive ``(known, sched, lower)`` kwarg sets from live signatures.

    ``known`` is every ``compile_graph`` keyword ``replicate_app``
    accepts; ``sched``/``lower`` are the subsets forwarded to
    :func:`~repro.core.schedule.build_schedule` and
    :func:`~repro.core.fusion.lower_graph`.  Derived — not
    hand-maintained — so the three callables cannot drift apart; the
    companion invariant (``known | UNROUTED_COMPILE_KWARGS`` covers
    ``compile_graph``'s whole signature) is enforced by
    ``tests/test_backends.py``.
    """
    from repro.core.compiler import compile_graph
    all_kwargs = frozenset(
        inspect.signature(compile_graph).parameters) - {"graph", "backend"}
    routable = all_kwargs - UNROUTED_COMPILE_KWARGS - _TUNE_KWARGS
    sched = routable & frozenset(
        inspect.signature(build_schedule).parameters)
    lower = routable & frozenset(
        inspect.signature(lower_graph).parameters)
    return sched | lower | _TUNE_KWARGS, sched, lower


def graph_input_halo(graph: DataflowGraph) -> dict[Channel, tuple[int, int]]:
    """Cumulative (hy, hx) halo each *graph input* must carry.

    Backward DP over the whole stage DAG — the line-buffer analysis of
    :func:`repro.core.schedule._halo_analysis` without the fusion-group
    boundary: intermediate planes that round-trip through HBM still
    shrink the valid region of a row-partitioned shard, so replication
    must provision for the end-to-end stencil radius, not the
    per-kernel one.
    """
    halo: dict[Channel, tuple[int, int]] = {}
    for st in reversed(graph.toposort()):
        out_halos = [halo.get(ch, (0, 0)) for ch in st.outputs]
        oh = (max(h[0] for h in out_halos), max(h[1] for h in out_halos))
        ih = (oh[0] + st.halo[0], oh[1] + st.halo[1])
        for ch in st.inputs:
            prev = halo.get(ch, (0, 0))
            halo[ch] = (max(prev[0], ih[0]), max(prev[1], ih[1]))
    return {ch: halo.get(ch, (0, 0)) for ch in graph.graph_inputs}


def _clone_with_height(graph: DataflowGraph, new_h: int) -> DataflowGraph:
    """Rebuild ``graph`` with every plane's height replaced by ``new_h``.

    Stage bodies are shape-polymorphic (they stream tiles), so the
    clone is pure metadata surgery; topology, names, windows and
    timing survive unchanged.
    """
    g2 = DataflowGraph(graph.name)
    cmap: dict[Channel, Channel] = {}
    for ch in graph.channels:
        c2 = g2.channel((new_h, ch.shape[1]), ch.dtype, name=ch.name)
        c2.is_graph_input = ch.is_graph_input
        c2.is_graph_output = ch.is_graph_output
        c2.depth = ch.depth
        cmap[ch] = c2
    for st in graph.stages:
        g2.task(st.name, st.kind, st.fn,
                [cmap[c] for c in st.inputs], [cmap[c] for c in st.outputs],
                window=st.window, ii=st.ii, fill=st.fill, meta=dict(st.meta))
    return g2


@dataclasses.dataclass
class ReplicatedApp:
    """A dataflow app replicated across a 1-D device mesh.

    Call it exactly like the :class:`~repro.core.host.CompiledApp` it
    wraps — same input/output names, global plane shapes — and the
    row shards execute in parallel, one pipeline replica per device.
    """

    schedule: Schedule                  # for the local extended plane
    mesh: Mesh
    n_replicas: int
    halo_rows: int
    plane: tuple[int, int]              # global (H, W)
    fn: Callable                        # jitted sharded step
    input_names: list[str]
    output_names: list[str]

    def __call__(self, **inputs: Any) -> dict[str, Any]:
        args = [inputs[n] for n in self.input_names]
        outs = self.fn(*args)
        return dict(zip(self.output_names, outs))

    def launch(self, **inputs: Any) -> LaunchHandle:
        """Async dispatch across all replicas (XRT ``enqueueTask`` x k)."""
        args = [inputs[n] for n in self.input_names]
        outs = self.fn(*args)
        return LaunchHandle(dict(zip(self.output_names, outs)))

    def describe(self) -> str:
        lines = [f"replicated app {self.schedule.graph.name!r}: "
                 f"{self.n_replicas} replicas over mesh axis "
                 f"{self.mesh.axis_names[0]!r}",
                 f"  global plane {self.plane} -> local "
                 f"({self.plane[0] // self.n_replicas}"
                 f"+2*{self.halo_rows} halo rows, {self.plane[1]})"]
        lines.append(self.schedule.describe())
        return "\n".join(lines)


def replicate_app(source: DataflowGraph | CompiledApp,
                  n_replicas: int | None = None, *,
                  backend=None, axis: str = "replica",
                  devices: list | None = None,
                  **compile_kwargs: Any) -> ReplicatedApp:
    """Replicate a dataflow app across devices by row-partitioning.

    ``source`` is a graph or an already-compiled app (its
    post-canonicalization graph is reused).  ``n_replicas`` defaults to
    every visible device; 1 replica is the supported CI fallback — the
    same shard_map + halo-exchange path on a single-device mesh.

    Requirements: every channel in the graph is a 2-D plane of one
    shape (the streaming-pipeline apps of Table I) and the plane
    height divides evenly by the replica count.

    ``tune="auto"`` (with optional ``tune_cache=``) tunes the *local
    extended* plane each replica runs — the schedule is measured (or
    loaded from the persistent TuningCache) for the shard shape, so a
    replicated deployment also warm-starts at its measured operating
    point; the provenance shows up in ``rapp.describe()``.
    """
    if isinstance(source, CompiledApp):
        graph = source.schedule.graph
        backend = resolve(backend or source.backend)
    else:
        graph = source
        backend = resolve(backend or "pallas")
    backend.require("replication")
    # calibration resolves once, up front: the tuner's prior, the
    # scheduler's budgets and every replica's lowering must all see
    # the same (possibly fitted) constants
    backend = resolve_calibrated(backend, compile_kwargs.get("calibrate"))

    shapes = {ch.shape for ch in graph.channels}
    if len(shapes) != 1 or len(next(iter(shapes))) != 2:
        raise GraphError(
            f"replication row-partitions one 2-D plane; graph "
            f"{graph.name!r} has channel shapes {sorted(shapes)}")
    nonlocal_stages = [s.name for s in graph.stages
                       if s.kind in ("custom", "reduce")]
    if nonlocal_stages:
        raise GraphError(
            f"replication needs local (point/stencil/split) operators "
            f"with a known halo; stages {nonlocal_stages} are opaque "
            f"and could read across the row cut")
    H, W = next(iter(shapes))

    devs = list(devices if devices is not None else jax.devices())
    k = n_replicas if n_replicas is not None else len(devs)
    if k >= 1 and H % k != 0:
        raise GraphError(
            f"plane height {H} does not divide over {k} replicas; "
            f"pick a replica count dividing H or pad the plane")
    mesh = replica_mesh(k, axis=axis, devices=devs)
    h_local = H // k

    halos = graph_input_halo(graph)
    hy = max((h[0] for h in halos.values()), default=0)
    if hy >= h_local:
        raise GraphError(
            f"cumulative stencil halo ({hy} rows) does not fit a "
            f"{h_local}-row shard; use fewer replicas")

    known, sched_names, lower_names = replication_kwarg_routing()
    unknown = set(compile_kwargs) - known
    if unknown:
        raise TypeError(f"replicate_app got unsupported compile kwargs "
                        f"{sorted(unknown)}; supported: {sorted(known)}")
    sched_kwargs = {kw: v for kw, v in compile_kwargs.items()
                    if kw in sched_names}
    lower_kwargs = {kw: v for kw, v in compile_kwargs.items()
                    if kw in lower_names}

    he = h_local + 2 * hy
    clone = _clone_with_height(graph, he)
    tune = compile_kwargs.get("tune")
    notes: list[str] = []
    if tune is not None:
        # tune the *local extended* plane: that is the graph each
        # replica actually runs, and its TuningCache entry is keyed by
        # the extended shape — a k-replica deployment warm-starts from
        # the same persistent cache as its previous runs
        if compile_kwargs.get("vector_factor") is not None:
            raise TypeError("tune= and vector_factor= are mutually "
                            "exclusive in replicate_app")
        if compile_kwargs.get("max_tile") is not None:
            raise TypeError("tune= and max_tile= are mutually exclusive "
                            "in replicate_app: the tile cap is one of "
                            "the tuner's search axes")
        from repro.tune.search import resolve_tuning, tuned_schedule_kwargs
        spec = compile_kwargs.get("spec") or backend.spec
        tuned = resolve_tuning(
            clone, backend, tune=tune, spec=spec,
            cache=compile_kwargs.get("tune_cache"),
            interpret=backend.resolve_interpret(
                compile_kwargs.get("interpret")),
            strict=compile_kwargs.get("strict", False),
            canonicalize=compile_kwargs.get("canonicalize", True),
            passes=compile_kwargs.get("passes"))
        if tuned is not None:
            config, source, notes = tuned
            sched_kwargs.update(tuned_schedule_kwargs(config, source, spec))
    sched = build_schedule(clone, backend=backend, **sched_kwargs)
    sched.diagnostics.extend(notes)
    input_names = [c.name for c in sched.graph.graph_inputs]
    output_names = [c.name for c in sched.graph.graph_outputs]

    def variant(valid_rows: tuple[int, int]) -> Callable:
        # per-stage zero masking must follow the *global* image edges: a
        # shard at the top/bottom owns halo rows that lie outside the
        # image, and intermediates there are zero in the single-device
        # semantics.  One lowering per edge kind, same schedule/tiles.
        run, _ = lower_graph(sched.graph, backend, schedule=sched,
                             valid_rows=valid_rows, **lower_kwargs)

        def step(*xs):
            outs = run(dict(zip(input_names, xs)))
            return tuple(outs[n] for n in output_names)

        return step

    if k == 1:
        runs = [variant((hy, hy + h_local))]
    elif k == 2:
        runs = [variant((hy, he)), variant((0, hy + h_local))]
    else:
        runs = [variant((hy, he)), variant((0, he)),
                variant((0, hy + h_local))]

    def body(*xs):
        exts = [halo_exchange_rows(x, hy, k, axis) for x in xs]
        if k == 1:
            outs = runs[0](*exts)
        else:
            j = jax.lax.axis_index(axis)
            last = len(runs) - 1
            branch = jnp.where(j == 0, 0,
                               jnp.where(j == k - 1, last, 1))
            outs = jax.lax.switch(branch, runs, *exts)
        return tuple(o[hy:hy + h_local] for o in outs)

    sharded = shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(axis, None) for _ in graph.graph_inputs),
        out_specs=tuple(P(axis, None) for _ in graph.graph_outputs),
        check_vma=False)
    fn = jax.jit(sharded)

    return ReplicatedApp(schedule=sched, mesh=mesh, n_replicas=k,
                         halo_rows=hy, plane=(H, W), fn=fn,
                         input_names=[c.name for c in graph.graph_inputs],
                         output_names=[c.name for c in graph.graph_outputs])
