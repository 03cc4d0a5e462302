"""Scheduling: convex DAG fusion, halo accumulation, depths, bundles.

This is FLOWER contribution C2 (top-level kernel generation) plus C3c
(memory-bundle assignment).  Given a :class:`DataflowGraph`, the
scheduler

1. canonicalizes the graph through the pass pipeline
   (:mod:`repro.core.transform`) unless ``strict=True``,
2. topologically sorts the stages (write-before-read order),
3. partitions them into *fusion groups* by **convex-subgraph DAG
   fusion**: every tile-streamable stage starts in its own group and
   groups are merged pairwise — best latency win first, as scored by
   :func:`repro.core.simulate.analytic_latency` — as long as the union
   stays convex (no path leaves the group and re-enters, so the fused
   kernel never deadlocks on an external dependency) and its
   double-buffered working set still fits VMEM
   (:func:`repro.core.vectorize.choose_tile` is the budget oracle).
   Diamond- and branch-shaped DAGs therefore collapse into ONE fused
   streaming kernel instead of fragmenting into per-branch chains;
   ``custom`` and ``reduce`` stages stay group-breaking singletons,
   and so does each ``resample`` stage (REDUCE / EXPAND), which
   becomes a streaming kernel of its own,
4. computes the *cumulative halo* each channel must carry so that
   downstream stencils have their windows available inside the fused
   kernel (the line-buffer analysis),
5. assigns memory bundles to graph I/O channels so parallel DAG paths
   use distinct HBM buffers (paper Fig. 4: ``mem1..4``),
6. budgets VMEM: each live channel inside a group costs
   ``tile_bytes * depth`` (depth-2 FIFO == double buffering).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Sequence

import numpy as np

from repro.core.graph import (Channel, DataflowGraph, GraphError, Stage,
                              resample_up)
from repro.core.simulate import TaskTiming, analytic_latency
from repro.core.transform import Pass, PassPipeline, default_pipeline
from repro.obs.tracer import maybe_span

__all__ = ["FusionGroup", "Schedule", "build_schedule"]

#: stage kinds that can be fused into one streaming kernel
FUSIBLE_KINDS = frozenset({"point", "pointN", "stencil", "split"})

#: items used by the merge cost model (plane size is tile-agnostic here)
_COST_ITEMS = 1 << 20


@dataclasses.dataclass
class FusionGroup:
    """A set of stages lowered to a single streaming kernel."""

    stages: list[Stage]
    #: channels entering the group (read from HBM by the kernel)
    inputs: list[Channel]
    #: channels leaving the group (written to HBM by the kernel)
    outputs: list[Channel]
    #: channels internal to the group (VMEM-only; the FIFO channels)
    internal: list[Channel]
    #: per-channel cumulative halo (hy, hx) required inside the kernel
    halo: dict[Channel, tuple[int, int]]
    #: selected tile (th, tw); filled in by the vectorizer
    tile: tuple[int, int] | None = None
    #: vector factor behind the selected tile (tw == 128 * vector_factor,
    #: 256 * for an EXPAND, see :meth:`tile_unit`);
    #: set by choose_tile/select_tile alongside ``tile``
    vector_factor: int | None = None
    #: why this tile was chosen: "model" (analytic sweep), "forced"
    #: (explicit vector_factor=), "measured"/"cache"/"config" (the
    #: autotuner, fresh / from the TuningCache / an explicit
    #: ScheduleConfig).  Rendered by :meth:`Schedule.describe`.
    tile_source: str = "model"
    #: the kernel's name, ``<app>_g<k>`` for the k-th group of the
    #: schedule (set by :func:`build_schedule`)
    name: str | None = None

    @property
    def kind(self) -> str:
        """What the group lowers to on a fused backend.

        ``dataflow``: one fused streaming kernel; ``resample``: one
        REDUCE or EXPAND, a streaming kernel of its own; ``alias``:
        only ``split`` stages, no kernel at all (every reader takes the
        one HBM buffer); ``custom``: one custom/reduce stage, run as
        whole-array jnp.
        """
        first = self.stages[0].kind
        if len(self.stages) == 1 and first not in FUSIBLE_KINDS:
            return "resample" if first == "resample" else "custom"
        if all(st.kind == "split" for st in self.stages):
            return "alias"
        return "dataflow"

    @property
    def is_kernel(self) -> bool:
        """Whether a fused backend lowers the group to a kernel."""
        return self.kind in ("dataflow", "resample")

    def tile_unit(self, sublane: int, lane: int) -> tuple[int, int]:
        """The step a tile's (th, tw) grows by.  An EXPAND's coarse
        input blocks start at (th/2, tw/2) multiples, which Mosaic takes
        only on whole (sublane, lane) tiles, so its unit is doubled."""
        if self.kind == "resample" and resample_up(self.stages[0]):
            return 2 * sublane, 2 * lane
        return sublane, lane

    def in_window(self, ch: Channel, tile: tuple[int, int]
                  ) -> tuple[int, int]:
        """The window of input ``ch`` one grid step reads for an output
        tile: the tile plus the channel's halo on each side; for a
        REDUCE twice the tile plus the halo, for an EXPAND half the tile
        plus the halo in coarse samples (rounded up)."""
        th, tw = tile
        hy, hx = self.halo.get(ch, (0, 0))
        if self.kind == "resample":
            if resample_up(self.stages[0]):
                return th // 2 + 2 * -(-hy // 2), tw // 2 + 2 * -(-hx // 2)
            return 2 * th + 2 * hy, 2 * tw + 2 * hx
        return th + 2 * hy, tw + 2 * hx

    def vmem_bytes(self, tile: tuple[int, int] | None = None) -> int:
        """Double-buffered VMEM working set for a candidate tile.

        Every channel live inside the kernel holds an expanded tile of
        ``(th + 2hy, tw + 2hx)`` elements at FIFO depth ``ch.depth``;
        stencil stages additionally materialize their ``kh*kw`` shifted
        views (the register-file cost of the window).  A resampling
        stage reads its input window (:meth:`in_window`) and holds its
        ``kh*kw`` views at fine resolution: a REDUCE's on the even rows
        only, an EXPAND's beside the zero-stuffed window.
        """
        tile = tile or self.tile
        if tile is None:
            raise GraphError("no tile selected for group")
        th, tw = tile
        total = 0
        for ch in self.inputs:
            rows, cols = self.in_window(ch, tile)
            total += rows * cols * _itemsize(ch) * ch.depth
        for ch in self.outputs + self.internal:
            hy, hx = self.halo.get(ch, (0, 0))
            total += (th + 2 * hy) * (tw + 2 * hx) * _itemsize(ch) * ch.depth
        for st in self.stages:
            kh, kw = st.window
            out = st.outputs[0] if st.outputs else None
            if st.kind == "stencil":
                hy, hx = self.halo.get(out, (0, 0))
                total += kh * kw * (th + 2 * hy) * (tw + 2 * hx) * _itemsize(out)
            elif st.kind == "resample":
                if resample_up(st):
                    rows, cols = self.in_window(st.inputs[0], tile)
                    total += (kh * kw * th * tw
                              + 4 * rows * cols) * _itemsize(out)
                else:
                    total += kh * kw * th * 2 * tw * _itemsize(out)
        return total


def _itemsize(ch: Channel) -> int:
    return np.dtype(ch.dtype).itemsize


@dataclasses.dataclass
class Schedule:
    """The partitioned program: what the lowering turns into kernels.

    Produced by :func:`build_schedule`; carried by every
    :class:`~repro.core.host.CompiledApp` as ``app.schedule``.  Holds
    the (post-canonicalization) graph, the stage execution order, the
    fusion groups with their selected tiles, the memory-bundle map,
    and the human-readable diagnostics trail of every decision the
    compiler made on the way here.
    """

    graph: DataflowGraph
    order: list[Stage]
    groups: list[FusionGroup]
    #: bundle id per graph-I/O channel (paper: AXI bundles)
    bundles: dict[Channel, int]
    n_bundles: int
    #: human-readable log from the pass pipeline + the fusion search
    diagnostics: list[str] = dataclasses.field(default_factory=list)

    def features(self, items: int = 1) -> dict:
        """Cost-model features of the selected tiles, drift-row ready.

        Delegates to :func:`repro.core.vectorize.schedule_features`:
        per modeled group, the (grid, bytes/step, per-kind compute
        steps) triple that makes the analytic model linear in the
        hardware constants' reciprocals.  Every drift row the engine,
        the tuner and the benchmarks persist carries this dict so the
        calibration fit (:mod:`repro.tune.calibrate`) can re-estimate
        the constants offline.
        """
        from repro.core.vectorize import schedule_features
        return schedule_features(self, items=items)

    def group_counts(self) -> dict[str, int]:
        """How many groups of each :attr:`FusionGroup.kind`."""
        return dict(sorted(collections.Counter(
            g.kind for g in self.groups).items()))

    def interstage_bytes(self) -> int:
        """HBM bytes one frame moves from group to group.

        Each plane a group writes for another is counted once as it is
        written and once for every group input that reads it.  A
        split's copies are the one buffer they copy (a group of splits
        moves nothing, and a fused kernel writes a plane once however
        many splits fan it out), the app's own inputs are never written
        by a group, and the write of an app output is the app's own I/O.
        """
        read, written = 0, set()
        for g in self.groups:
            if g.kind == "alias":
                continue
            for ch in g.inputs:
                buf = split_root(ch)
                if not buf.is_graph_input:
                    read += buf.nbytes
                    written.add(buf)
        return read + sum(b.nbytes for b in written
                          if not b.is_graph_output)

    def describe(self) -> str:
        """Render the schedule: kernels, FIFOs, tiles + provenance.

        Each fused kernel line reports its selected tile and *why* it
        was chosen (``via model`` — analytic sweep, ``via forced`` —
        explicit ``vector_factor=``, ``via measured`` / ``via cache``
        / ``via config`` — the autotuner; see ``docs/tuning.md``),
        followed by the pass-pipeline and ``[tune]`` diagnostics.
        """
        lines = [f"schedule for {self.graph.name!r}: "
                 f"{len(self.order)} stages -> {len(self.groups)} kernels"]
        for gi, g in enumerate(self.groups):
            names = ",".join(s.name for s in g.stages)
            lines.append(f"  kernel[{gi}] ({g.kind}): {names}")
            lines.append(f"    inputs={[c.name for c in g.inputs]} "
                         f"outputs={[c.name for c in g.outputs]} "
                         f"fifo={[c.name for c in g.internal]}")
            if g.tile is not None:
                lines.append(f"    tile={g.tile} "
                             f"vector_factor={g.vector_factor} "
                             f"via {g.tile_source}")
        lines.append("  groups by kind: " + ", ".join(
            f"{k}={n}" for k, n in self.group_counts().items())
            + f"; inter-group HBM {self.interstage_bytes()} B a frame")
        lines.append("  bundles: " + ", ".join(
            f"{c.name}->mem{b}" for c, b in self.bundles.items()))
        if self.diagnostics:
            lines.append("  passes:")
            lines.extend(f"    {d}" for d in self.diagnostics)
        return "\n".join(lines)


def build_schedule(graph: DataflowGraph, n_bundles: int = 4, *,
                   canonicalize: bool = True, strict: bool = False,
                   passes: Sequence[Pass] | PassPipeline | None = None,
                   spec=None, vector_factor: int | None = None,
                   group_vector_factors: Sequence[int | None] | None = None,
                   max_tile: tuple[int, int] | None = None,
                   tile_source: str = "measured", trace=None,
                   backend=None) -> Schedule:
    """Canonicalize, validate and partition ``graph`` into fusion groups.

    ``strict=True`` skips canonicalization and enforces the paper's
    explicit canonical form (multi-reader channels raise).  ``passes``
    overrides the default pipeline; ``spec`` feeds the VMEM feasibility
    check of the fusion search (default: the resolved ``backend``'s
    spec, else TPU v5e).  ``backend`` (a name or
    :class:`~repro.backends.Backend`) supplies the lane/sublane widths
    and default tile cap the vectorizer budgets with.  ``vector_factor``
    forces one datapath width for every group; ``None`` (the default)
    sweeps the factor per group through the DMA cost model
    (:func:`repro.core.vectorize.select_tile`) and logs the choice in
    the schedule diagnostics.

    ``group_vector_factors`` is the autotuner's entry point (see
    :mod:`repro.tune`): one factor per fusion group in schedule order
    (``None`` entries for trivial groups), applied with provenance
    label ``tile_source``; ``max_tile`` caps the tile shape handed to
    :func:`repro.core.vectorize.choose_tile`.  A length mismatch —
    e.g. a stale cached config after the partition changed — falls
    back to the analytic sweep with a diagnostic instead of failing.

    >>> from repro.core.graph import DataflowGraph
    >>> g = DataflowGraph("doc")
    >>> x = g.input("img", (64, 256))
    >>> _ = g.output(g.point(x, lambda v: v + 1.0), "out")
    >>> sched = build_schedule(g)
    >>> len(sched.groups), sched.groups[0].tile_source
    (1, 'model')
    >>> tuned = build_schedule(g, group_vector_factors=[1])
    >>> tuned.groups[0].tile[1], tuned.groups[0].tile_source
    (128, 'measured')
    """
    diagnostics: list[str] = []
    if canonicalize and not strict:
        pipeline = passes if isinstance(passes, PassPipeline) else (
            PassPipeline(tuple(passes)) if passes is not None
            else default_pipeline())
        graph, diagnostics = pipeline.run(graph, tracer=trace)
    graph.validate()
    order = graph.toposort()
    with maybe_span(trace, "compile.partition", cat="compile",
                    graph=graph.name, stages=len(order)) as sp:
        groups, fusion_diags = _partition_groups(graph, order, spec,
                                                 vector_factor,
                                                 backend=backend)
        sp.set(groups=len(groups))
    app = re.sub(r"\W", "_", graph.name)
    for k, g in enumerate(groups):
        g.name = f"{app}_g{k}"
    diagnostics.extend(fusion_diags)
    diagnostics.extend(_select_tiles(groups, spec, vector_factor,
                                     group_vf=group_vector_factors,
                                     max_tile=max_tile, source=tile_source,
                                     trace=trace, backend=backend))
    bundles = _assign_bundles(graph, n_bundles)
    return Schedule(graph, order, groups, bundles, n_bundles, diagnostics)


def _select_tiles(groups: list[FusionGroup], spec,
                  vector_factor: int | None,
                  group_vf: Sequence[int | None] | None = None,
                  max_tile: tuple[int, int] | None = None,
                  source: str = "measured", trace=None,
                  backend=None) -> list[str]:
    """Per-group tile/vector-factor selection (post-partition).

    Three modes, in precedence order: ``group_vf`` pins each group
    individually (the autotuner applying a measured/cached config,
    labeled ``source``), ``vector_factor`` pins every group to one
    factor (the paper's explicit knob), and ``None``/``None`` sweeps
    per group through the cost model — different plane widths in one
    graph can land on different datapath widths.
    """
    from repro.core.vectorize import select_tile
    diags: list[str] = []
    if group_vf is not None and len(group_vf) != len(groups):
        diags.append(f"[vectorize] tuned config has {len(group_vf)} "
                     f"group factors but the partition produced "
                     f"{len(groups)} groups; falling back to the "
                     f"analytic sweep")
        group_vf = None
    for gi, g in enumerate(groups):
        if not g.is_kernel:
            continue
        forced = vector_factor
        g.tile_source = "forced" if vector_factor is not None else "model"
        if group_vf is not None and group_vf[gi] is not None:
            forced = group_vf[gi]
            g.tile_source = source
        try:
            tile, sweep = select_tile(g, spec, forced, max_tile,
                                      trace=trace, backend=backend)
        except ValueError:
            # a persistent tuned config can outlive the partitioner or
            # the spec it was measured under (same group count, changed
            # plane/budget); an explicit vector_factor= stays a hard
            # error, but a stale CACHED factor degrades to the sweep
            if group_vf is None or group_vf[gi] is None:
                raise
            names = ",".join(s.name for s in g.stages)
            diags.append(f"[vectorize] {{{names}}}: tuned "
                         f"vector_factor={forced} no longer feasible; "
                         f"falling back to the analytic sweep")
            g.tile_source = "model"
            tile, sweep = select_tile(g, spec, vector_factor,
                                      max_tile, trace=trace,
                                      backend=backend)
        names = ",".join(s.name for s in g.stages)
        if sweep is not None:
            tried = ",".join(
                f"vf{r['vector_factor']}="
                + (f"{r['modeled_s'] * 1e6:.1f}us" if r["feasible"]
                   else "infeasible")
                for r in sweep)
            diags.append(f"[vectorize] {{{names}}}: swept {tried} -> "
                         f"vector_factor={g.vector_factor} tile={tile}")
        else:
            diags.append(f"[vectorize] {{{names}}}: {g.tile_source} "
                         f"vector_factor={g.vector_factor} tile={tile}")
    return diags


# ----------------------------------------------------------------------
# convex-subgraph DAG fusion
# ----------------------------------------------------------------------
def split_root(ch: Channel, within: set | None = None) -> Channel:
    """The buffer ``ch`` reads: through any chain of splits (only those
    in ``within``, when given), the channel they copy."""
    while (ch.producer is not None and ch.producer.kind == "split"
           and (within is None or ch.producer in within)):
        ch = ch.producer.inputs[0]
    return ch


def _is_fusible(st: Stage) -> bool:
    return (st.kind in FUSIBLE_KINDS
            and all(len(c.shape) == 2 for c in st.inputs + st.outputs))


def _partition_groups(graph: DataflowGraph, order: list[Stage],
                      spec=None, vector_factor: int | None = None,
                      backend=None
                      ) -> tuple[list[FusionGroup], list[str]]:
    """Grow maximal convex fusion groups over the stage DAG.

    Seeds one group per stage, then repeatedly merges the pair of
    edge-adjacent groups with the largest modeled latency win
    (``analytic_latency``: a merge removes one HBM write+read
    round-trip and lets both halves drain at the slower rate instead
    of sequentially).  A merge is legal iff both groups are fusible on
    the same plane shape, the union is *convex* in the DAG — no path
    between two member stages passes through an outside stage — and
    :func:`~repro.core.vectorize.choose_tile` can still fit the
    double-buffered union in VMEM.
    """
    n = len(order)
    pos = {st: i for i, st in enumerate(order)}

    succ: list[set[int]] = [set() for _ in range(n)]
    for i, st in enumerate(order):
        for ch in st.outputs:
            for c in ch.consumers:
                succ[i].add(pos[c])

    # reach[i]: bitmask of stages strictly reachable from i
    reach = [0] * n
    for i in reversed(range(n)):
        m = 0
        for j in succ[i]:
            m |= (1 << j) | reach[j]
        reach[i] = m

    owner = list(range(n))                      # stage idx -> group id
    members: dict[int, int] = {i: 1 << i for i in range(n)}
    fusible = [_is_fusible(st) for st in order]
    shape: dict[int, tuple[int, ...]] = {
        i: order[i].outputs[0].shape if order[i].outputs else ()
        for i in range(n)}

    def is_convex(union: int) -> bool:
        above = 0
        for i in _bits(union):
            above |= reach[i]
        for x in _bits(above & ~union):
            if reach[x] & union:
                return False
        return True

    def make_group(mask: int) -> FusionGroup:
        g = FusionGroup([order[i] for i in _bits(mask)], [], [], [], {})
        _classify_channels(g, graph)
        g.halo = _halo_analysis(g)
        return g

    # masks are immutable ints: memoize the per-candidate work so each
    # merge round only evaluates unions it has not seen before
    _fits_cache: dict[int, bool] = {}
    _lat_cache: dict[int, float] = {}

    def fits_vmem(mask: int) -> bool:
        # feasibility floor: a forced factor must fit every merged
        # group; in auto-sweep mode the narrowest datapath (vf=1) is
        # the existence check — select_tile widens afterwards.
        if mask not in _fits_cache:
            from repro.core.vectorize import choose_tile
            g = make_group(mask)
            try:
                choose_tile(g, spec, vector_factor or 1, backend=backend)
                _fits_cache[mask] = True
            except ValueError:
                _fits_cache[mask] = False
        return _fits_cache[mask]

    def latency(mask: int) -> float:
        if mask not in _lat_cache:
            tasks = ([TaskTiming("read", ii=1.0, fill=32.0)]
                     + [TaskTiming(order[i].name, ii=order[i].ii,
                                   fill=order[i].fill) for i in _bits(mask)]
                     + [TaskTiming("write", ii=1.0, fill=32.0)])
            _lat_cache[mask] = analytic_latency(tasks,
                                                _COST_ITEMS)["dataflow"]
        return _lat_cache[mask]

    n_merges = 0
    while True:
        pairs: set[tuple[int, int]] = set()
        for i in range(n):
            for j in succ[i]:
                ga, gb = owner[i], owner[j]
                if ga != gb:
                    pairs.add((min(ga, gb), max(ga, gb)))
        best: tuple[float, int, int, int] | None = None
        for ga, gb in sorted(pairs):
            if not (fusible[ga] and fusible[gb]):
                continue
            if shape[ga] != shape[gb]:
                continue
            union = members[ga] | members[gb]
            if not is_convex(union):
                continue
            if not fits_vmem(union):
                continue
            gain = latency(members[ga]) + latency(members[gb]) \
                - latency(union)
            if best is None or gain > best[0]:
                best = (gain, ga, gb, union)
        if best is None:
            break
        _, ga, gb, union = best
        members[ga] = union
        del members[gb]
        for i in _bits(union):
            owner[i] = ga
        n_merges += 1

    groups = [make_group(members[g]) for g in _order_groups(members, succ)]
    diags = [f"[convex-fusion] {n} stages -> {len(groups)} groups "
             f"({n_merges} merges)"]
    for g in groups:
        if len(g.stages) > 1:
            diags.append(
                f"[convex-fusion] fused {{{','.join(s.name for s in g.stages)}}}"
                f" into one streaming kernel")
    return groups, diags


def _order_groups(members: dict[int, int], succ: list[set[int]]
                  ) -> list[int]:
    """Topological order of the (convex => acyclic) group DAG.

    Deterministic: ready groups are taken lowest-member-index first,
    so the result is stable across runs.
    """
    owner = {i: g for g, mask in members.items() for i in _bits(mask)}
    gsucc: dict[int, set[int]] = {g: set() for g in members}
    indeg: dict[int, int] = {g: 0 for g in members}
    for i, js in enumerate(succ):
        for j in js:
            a, b = owner[i], owner[j]
            if a != b and b not in gsucc[a]:
                gsucc[a].add(b)
                indeg[b] += 1
    ready = sorted(g for g in members if indeg[g] == 0)
    out: list[int] = []
    while ready:
        g = ready.pop(0)
        out.append(g)
        for nb in sorted(gsucc[g]):
            indeg[nb] -= 1
            if indeg[nb] == 0:
                ready.append(nb)
        ready.sort()
    return out


def _bits(mask: int):
    while mask:
        low = mask & -mask
        yield low.bit_length() - 1
        mask ^= low


def _classify_channels(g: FusionGroup, graph: DataflowGraph) -> None:
    inside = set(g.stages)
    seen: set[Channel] = set()
    for st in g.stages:
        for ch in st.inputs:
            if ch in seen:
                continue
            seen.add(ch)
            if ch.producer not in inside:
                g.inputs.append(ch)
        for ch in st.outputs:
            if ch in seen:
                continue
            seen.add(ch)
            consumers_inside = ch.consumers and all(
                c in inside for c in ch.consumers)
            if ch.is_graph_output or not consumers_inside:
                g.outputs.append(ch)
            else:
                g.internal.append(ch)


# ----------------------------------------------------------------------
# halo (line-buffer) analysis
# ----------------------------------------------------------------------
def _halo_analysis(g: FusionGroup) -> dict[Channel, tuple[int, int]]:
    """Cumulative halo per channel, by backward DP over the group.

    ``halo(ch) = max over consumers st of halo(st.output) + st.halo``;
    group outputs carry halo (0, 0).  This is exactly the line-buffer
    depth a chained FPGA stencil pipeline needs, expressed in tiles.
    """
    halo: dict[Channel, tuple[int, int]] = {}
    inside = set(g.stages)
    for ch in g.outputs:
        halo[ch] = (0, 0)
    for st in reversed(g.stages):  # reverse topo order within the group
        out_halos = [halo.get(ch, (0, 0)) for ch in st.outputs]
        oh = (max(h[0] for h in out_halos), max(h[1] for h in out_halos))
        ih = (oh[0] + st.halo[0], oh[1] + st.halo[1])
        for ch in st.inputs:
            prev = halo.get(ch, (0, 0))
            cand = ih if ch.producer in inside or ch in g.inputs else (0, 0)
            halo[ch] = (max(prev[0], cand[0]), max(prev[1], cand[1]))
    return halo


# ----------------------------------------------------------------------
# memory bundles (paper Fig. 4)
# ----------------------------------------------------------------------
def _assign_bundles(graph: DataflowGraph, n_bundles: int) -> dict[Channel, int]:
    """Assign distinct HBM "bundles" to parallel I/O paths.

    Heuristic matching the paper: I/O channels on *different* branches
    of the DAG should land on different bundles so their transfers do
    not serialize on one interface.  We walk graph I/O in order and
    round-robin, but force siblings (channels touching the same stage)
    apart when possible.
    """
    io = graph.graph_inputs + graph.graph_outputs
    bundles: dict[Channel, int] = {}
    nxt = 0
    for ch in io:
        taken = set()
        peers = ch.consumers + ([ch.producer] if ch.producer else [])
        for st in peers:
            for other in st.inputs + st.outputs:
                if other in bundles:
                    taken.add(bundles[other])
        b = nxt % n_bundles
        for _ in range(n_bundles):
            if b not in taken:
                break
            b = (b + 1) % n_bundles
        bundles[ch] = b
        ch.bundle = b
        nxt += 1
    return bundles
