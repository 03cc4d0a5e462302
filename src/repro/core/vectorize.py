"""Tile / vector-factor selection (FLOWER contribution C3b).

On the FPGA, FLOWER widens the datapath (``int4`` channels for vector
factor 4) to match the 512-bit memory bus.  The TPU analogue: pick the
streamed tile so its minor dimension is a multiple of the 128-lane VPU
(and MXU) width, its second-minor a multiple of the 8-row sublane, and
the double-buffered working set of the whole fused group fits in VMEM.

The *vector factor* maps to how many 128-lane vectors a tile row
carries (``tw == 128 * vector_factor``, twice that for an EXPAND
kernel, see :meth:`FusionGroup.tile_unit`); the *burst length* maps to
the tile byte count per DMA (bigger tiles == longer HBM bursts ==
better DMA efficiency, up to the VMEM budget).

Two entry points:

- :func:`choose_tile` — the paper's *explicit* knob: the caller fixes
  the vector factor, we fit the tallest tile that holds the VMEM
  budget, or raise when the factor cannot fit the plane / ``max_tile``.
- :func:`select_tile` — the *automatic* mode used by the compiler
  driver: sweep every feasible vector factor through a DMA-efficiency
  cost model (:func:`modeled_plane_time`) and keep the fastest.  The
  sweep is what replaces a hardcoded default: wide tiles amortize the
  per-burst overhead, but over-wide tiles pay for padded columns when
  the plane width is not a multiple, and the model prices both.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.schedule import FusionGroup

__all__ = ["TPUSpec", "choose_tile", "select_tile", "sweep_vector_factor",
           "modeled_plane_time", "modeled_schedule_time", "scale_spec",
           "plane_features", "schedule_features",
           "vmem_report", "DEFAULT_MAX_TILE"]

LANE = 128     # VPU/MXU lane width (registry default; see _constants)
SUBLANE = 8    # float32 sublane rows

#: default (th, tw) cap for choose_tile/select_tile; the autotuner
#: (:mod:`repro.tune`) searches over alternative caps (the tile-height
#: axis of the schedule space)
DEFAULT_MAX_TILE = (256, 1024)


def _constants(backend, spec, max_tile) -> tuple:
    """Resolve (spec, max_tile, lane, sublane) for a tile decision.

    With ``backend`` (a name or :class:`~repro.backends.Backend`), the
    lane width, sublane rows, VMEM budgets and tile cap come from the
    resolved record — the single source of per-target constants;
    explicit ``spec``/``max_tile`` arguments still win.  Without one,
    the module-level defaults apply (identical values for the seed
    backends, so legacy call sites are bit-compatible).
    """
    if backend is None:
        return (spec or V5E,
                tuple(max_tile) if max_tile is not None else DEFAULT_MAX_TILE,
                LANE, SUBLANE)
    from repro.backends import resolve
    be = resolve(backend)
    return (spec or be.spec,
            tuple(max_tile) if max_tile is not None
            else tuple(be.default_max_tile),
            be.lane, be.sublane)


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Per-chip hardware constants (TPU v5e by default)."""

    vmem_bytes: int = 96 * 2**20        # budget (of 128 MiB physical)
    hbm_bytes: int = 16 * 2**30
    peak_flops_bf16: float = 197e12
    hbm_bw: float = 819e9
    ici_bw_per_link: float = 50e9
    clock_hz: float = 940e6
    #: fixed per-grid-step cost (DMA issue / burst setup) the sweep
    #: amortizes by widening tiles
    step_overhead_s: float = 1e-6


V5E = TPUSpec()


def choose_tile(group: FusionGroup, spec: TPUSpec | None = None,
                vector_factor: int = 1,
                max_tile: tuple[int, int] | None = None,
                backend=None) -> tuple[int, int]:
    """Pick (th, tw) for a fusion group at a fixed vector factor.

    ``tw`` is exactly ``lane * vector_factor`` — the paper's explicit
    vectorization knob sets the datapath width.  ``th`` starts at the
    largest hardware-aligned height ``<= max_tile[0]`` bounded by the
    plane, then shrinks until the double-buffered VMEM budget holds.
    The lane width, sublane rows, tile cap and VMEM budget come from
    the resolved ``backend`` (explicit ``spec``/``max_tile`` override).

    Raises :class:`ValueError` when the requested factor cannot fit —
    either because ``lane * vector_factor`` exceeds ``max_tile[1]`` or
    the lane-rounded plane width, or because even the minimal
    ``(sublane, tw)`` tile blows the VMEM budget.
    """
    spec, max_tile, lane, sublane = _constants(backend, spec, max_tile)
    if vector_factor < 1:
        raise ValueError(f"vector_factor must be >= 1, got {vector_factor}")
    shape = group.stages[0].outputs[0].shape
    if len(shape) != 2:
        raise ValueError(f"generic fusion tiles 2-D planes, got {shape}")
    H, W = shape
    sublane, lane = group.tile_unit(sublane, lane)
    tw = lane * vector_factor
    # clamp BEFORE committing to the factor: a tile wider than the
    # lane-rounded plane only streams padding, and max_tile is a hard
    # cap — the old code applied the factor after clamping and silently
    # exceeded both.
    cap_tw = min(_round_up(W, lane), max(lane, (max_tile[1] // lane) * lane))
    if tw > cap_tw:
        raise ValueError(
            f"vector_factor={vector_factor} needs a {tw}-lane-wide tile, "
            f"but the widest feasible tile is {cap_tw} "
            f"(plane width {W} -> {_round_up(W, lane)} lane-rounded, "
            f"max_tile[1]={max_tile[1]})")
    th = min(_round_up(H, sublane),
             max(sublane, (max_tile[0] // sublane) * sublane))

    while group.vmem_bytes((th, tw)) > spec.vmem_bytes:
        if th > sublane:
            th = max(sublane, th // 2 // sublane * sublane)
        else:
            raise ValueError(
                f"group {[s.name for s in group.stages]} cannot fit VMEM "
                f"budget {spec.vmem_bytes} even at minimal tile "
                f"({sublane}, {tw}) for vector_factor={vector_factor}: "
                f"{group.vmem_bytes((th, tw))} bytes")
    group.tile = (th, tw)
    group.vector_factor = vector_factor
    return group.tile


def modeled_plane_time(group: FusionGroup, tile: tuple[int, int],
                       spec: TPUSpec = V5E) -> float:
    """Modeled seconds to stream the whole plane through the kernel.

    Per grid step the kernel bursts every (halo-expanded) input tile
    HBM->VMEM, computes, and bursts the output tiles back; DMA and
    compute overlap (double buffering), and each step pays a fixed
    issue overhead.  Padded rows/columns are priced: the grid covers
    the tile-rounded plane, so an over-wide tile on a narrow plane
    streams dead columns.

    A calibrated spec (:class:`repro.tune.calibrate.CalibratedSpec`)
    may carry an ``ii_scale`` mapping stage kinds to fitted multipliers
    of their issue intervals; any spec without one prices every stage
    at its declared ``ii`` exactly as before.
    """
    th, tw = tile
    H, W = group.stages[0].outputs[0].shape
    grid = (_round_up(H, th) // th) * (_round_up(W, tw) // tw)
    bytes_step = _bytes_step(group, tile)
    dma_s = bytes_step / spec.hbm_bw
    scale = dict(getattr(spec, "ii_scale", ()) or ())
    if scale:
        steps = sum(st.ii * scale.get(st.kind, 1.0) for st in group.stages)
    else:
        steps = sum(st.ii for st in group.stages)
    compute_s = steps * th * tw / spec.clock_hz
    return grid * (spec.step_overhead_s + max(dma_s, compute_s))


def _bytes_step(group: FusionGroup, tile: tuple[int, int]) -> int:
    """HBM bytes one grid step moves: each input's window
    (:meth:`FusionGroup.in_window`) in, each output tile out."""
    th, tw = tile
    total = 0
    for ch in group.inputs:
        rows, cols = group.in_window(ch, tile)
        total += rows * cols * np.dtype(ch.dtype).itemsize
    for ch in group.outputs:
        total += th * tw * np.dtype(ch.dtype).itemsize
    return total


def plane_features(group: FusionGroup, tile: tuple[int, int]) -> dict:
    """Spec-independent features behind :func:`modeled_plane_time`.

    The model is, per fusion group,

    ``t = grid * (step_overhead_s + max(bytes_step / hbm_bw,
    sum_kind(steps[kind] * ii_scale[kind]) / clock_hz))``

    so recording ``grid`` (DMA issue count), ``bytes_step`` (HBM bytes
    per step) and ``steps`` (per-stage-kind issue-interval cycles per
    step, already multiplied by the tile area) into every drift row
    makes the modeled time *linear in the constants' reciprocals* —
    exactly what the calibration fit
    (:func:`repro.tune.calibrate.calibrate`) regresses from measured
    times.  :func:`repro.obs.drift.predict_features` is the inverse:
    it reconstitutes the modeled seconds from these features under any
    spec, bit-identically to :func:`modeled_plane_time`.
    """
    th, tw = tile
    H, W = group.stages[0].outputs[0].shape
    grid = (_round_up(H, th) // th) * (_round_up(W, tw) // tw)
    bytes_step = _bytes_step(group, tile)
    steps: dict[str, float] = {}
    for st in group.stages:
        steps[st.kind] = steps.get(st.kind, 0.0) + float(st.ii)
    return {"grid": grid, "bytes_step": bytes_step,
            "steps": {k: v * th * tw for k, v in sorted(steps.items())}}


def schedule_features(schedule, items: int = 1) -> dict:
    """Whole-app drift-row features: one entry per modeled group.

    Trivial (custom/reduce) groups carry no tile and score zero in
    :func:`modeled_schedule_time`, so they contribute no features
    either.  ``items`` scales the prediction (a width-``n`` batched
    launch does the plane ``n`` times); it rides in the feature dict so
    a drift row stays self-describing.
    """
    groups = [plane_features(g, g.tile) for g in schedule.groups
              if g.is_kernel and g.tile is not None]
    feats = {"groups": groups}
    if items != 1:
        feats["items"] = int(items)
    return feats


def sweep_vector_factor(group: FusionGroup, spec: TPUSpec | None = None,
                        max_tile: tuple[int, int] | None = None,
                        candidates: tuple[int, ...] | None = None,
                        trace=None, backend=None) -> list[dict]:
    """Cost-model sweep over vector factors; one record per candidate.

    Default candidates run 1..cap (every factor the plane/max_tile can
    hold, plus one infeasible sentinel so callers can check that
    feasibility is monotone).  Each record carries ``vector_factor``,
    ``feasible``, the chosen ``tile``, ``modeled_s`` and the
    :func:`plane_features` behind the modeled time (``features`` — what
    benchmark drift rows persist for the calibration fit).  ``trace``
    (a :class:`~repro.obs.tracer.Tracer`) wraps the sweep in a
    ``compile.vectorize.sweep`` span recording how many candidates
    were scored and how many were feasible.
    """
    spec, max_tile, lane, _ = _constants(backend, spec, max_tile)
    if trace is not None:
        with trace.span("compile.vectorize.sweep", cat="compile",
                        group=",".join(s.name for s in group.stages)) as sp:
            records = sweep_vector_factor(group, spec, max_tile, candidates,
                                          backend=backend)
            sp.set(candidates=len(records),
                   feasible=sum(1 for r in records if r["feasible"]))
            return records
    shape = group.stages[0].outputs[0].shape
    H, W = shape
    lane = group.tile_unit(1, lane)[1]
    cap_tw = min(_round_up(W, lane), max(lane, (max_tile[1] // lane) * lane))
    if candidates is None:
        candidates = tuple(range(1, cap_tw // lane + 2))
    records: list[dict] = []
    prev = (group.tile, group.vector_factor)
    try:
        for vf in candidates:
            try:
                tile = choose_tile(group, spec, vf, max_tile,
                                   backend=backend)
            except ValueError as e:
                records.append({"vector_factor": vf, "feasible": False,
                                "tile": None, "modeled_s": float("inf"),
                                "reason": str(e)})
                continue
            records.append({"vector_factor": vf, "feasible": True,
                            "tile": tile,
                            "modeled_s": modeled_plane_time(group, tile,
                                                            spec),
                            "features": plane_features(group, tile)})
    finally:
        # the sweep only *scores*; choose_tile/select_tile commit.
        # Without the restore, a standalone sweep would pin the group
        # to the last candidate tried, not the chosen tile.
        group.tile, group.vector_factor = prev
    return records


def select_tile(group: FusionGroup, spec: TPUSpec | None = None,
                vector_factor: int | None = None,
                max_tile: tuple[int, int] | None = None,
                trace=None, backend=None,
                ) -> tuple[tuple[int, int], list[dict] | None]:
    """Pick the group's tile; sweep the vector factor when not forced.

    ``vector_factor=None`` runs :func:`sweep_vector_factor` and keeps
    the fastest feasible candidate (ties break toward the wider tile —
    longer bursts).  An explicit factor forwards to
    :func:`choose_tile`.  Returns ``(tile, sweep_records)`` with
    ``sweep_records=None`` in forced mode; the group's ``tile`` and
    ``vector_factor`` fields are set either way.  ``trace`` threads a
    flight recorder into the sweep.
    """
    if vector_factor is not None:
        return choose_tile(group, spec, vector_factor, max_tile,
                           backend=backend), None
    records = sweep_vector_factor(group, spec, max_tile, trace=trace,
                                  backend=backend)
    feasible = [r for r in records if r["feasible"]]
    if not feasible:
        raise ValueError(
            f"no feasible vector factor for group "
            f"{[s.name for s in group.stages]}: "
            f"{records[0].get('reason', 'no candidates')}")
    best = min(feasible, key=lambda r: (r["modeled_s"], -r["vector_factor"]))
    group.tile = best["tile"]
    group.vector_factor = best["vector_factor"]
    return group.tile, records


def scale_spec(spec: TPUSpec, vmem_fraction: float) -> TPUSpec:
    """Shrink a spec's VMEM budget — the *fusion budget* knob.

    The partitioner only merges groups whose double-buffered working
    set fits ``spec.vmem_bytes``, so scaling the budget changes which
    stages fuse, not just how they tile.  The autotuner searches over
    fractions because the model's VMEM budget is a proxy (real kernels
    pay scratch and compiler overheads the closed form cannot see).
    """
    if not 0.0 < vmem_fraction <= 1.0:
        raise ValueError(f"vmem_fraction must be in (0, 1], got "
                         f"{vmem_fraction}")
    if vmem_fraction == 1.0:
        return spec
    return dataclasses.replace(spec,
                               vmem_bytes=int(spec.vmem_bytes * vmem_fraction))


def modeled_schedule_time(schedule, spec: TPUSpec = V5E) -> float:
    """Whole-app modeled seconds: sum of per-group plane times.

    Groups execute back-to-back at app granularity (each drains to HBM
    before the next starts), so the app-level model is additive over
    :func:`modeled_plane_time`; trivial (custom/reduce) groups carry no
    tile and score zero.  This is the ranking prior the autotuner uses
    to order joint candidates before measuring them.
    """
    total = 0.0
    for g in schedule.groups:
        if not g.is_kernel or g.tile is None:
            continue
        total += modeled_plane_time(g, g.tile, spec)
    return total


def vmem_report(group: FusionGroup) -> dict:
    th, tw = group.tile
    return {
        "tile": group.tile,
        "vector_factor": tw // group.tile_unit(SUBLANE, LANE)[1],
        "vmem_bytes": group.vmem_bytes(),
        "n_channels": len(group.inputs) + len(group.outputs)
        + len(group.internal),
        "burst_bytes": max(
            rows * cols * np.dtype(ch.dtype).itemsize
            for ch in group.inputs
            for rows, cols in [group.in_window(ch, group.tile)]
        ) if group.inputs else 0,
    }


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
