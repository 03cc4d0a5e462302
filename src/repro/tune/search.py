"""Profile-guided schedule search: model ranks, measurements pick.

PR 3 selected tiles purely analytically
(:func:`repro.core.vectorize.modeled_plane_time`).  The HLS literature
is unambiguous that this is only half the loop: de Fine Licht et al.
and the FLOWER evaluation both validate transformation parameters
against the target before committing.  This module closes the loop:

1. **prior** — the analytic sweep ranks candidates per fusion group
   (top-k by modeled time) so the measured search starts at the
   model's pick and never wastes a trial on a config the model can
   already rule out;
2. **measure** — each surviving candidate is *lowered and timed on
   the live backend* (:func:`default_measure`), the only judge that
   knows about padding pathologies, DMA issue limits and everything
   else the closed form misses;
3. **pick** — greedy coordinate descent over the per-group vector
   factors (plus the ``max_tile`` and fusion-budget axes), capped at
   ``max_trials`` measurements.  The analytic pick is always measured
   first, so the winner is **never slower than the analytic
   schedule** by construction;
4. **persist** — the winner goes into the on-disk
   :class:`~repro.tune.store.TuningCache`; the next
   ``compile_graph(..., tune="auto")`` of the same app on the same
   device kind does **zero** measurements.

Doctest (fake measurements, so it runs anywhere — real use omits
``measure``):

    >>> import tempfile
    >>> from repro.core.graph import DataflowGraph
    >>> from repro.tune.store import TuningCache
    >>> g = DataflowGraph("doc")
    >>> x = g.input("img", (64, 256))
    >>> _ = g.output(g.point(x, lambda v: v * 2.0), "out")
    >>> cache = TuningCache(tempfile.mkdtemp())
    >>> res = tune_graph(g, "xla", cache=cache,
    ...                  measure=lambda cfg: 1.0 / cfg.group_vf[0])
    >>> res.source, res.config.group_vf         # widest factor is fastest
    ('measured', (2,))
    >>> again = tune_graph(g, "xla", cache=cache,
    ...                    measure=lambda cfg: 1.0 / cfg.group_vf[0])
    >>> again.source, again.n_measurements      # served from disk
    ('cache', 0)
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Sequence

import numpy as np

from repro.core.schedule import Schedule, build_schedule
from repro.core.vectorize import (DEFAULT_MAX_TILE, TPUSpec, V5E,
                                  modeled_schedule_time, scale_spec,
                                  schedule_features, sweep_vector_factor)
from repro.obs.drift import DriftLog, resolve_drift
from repro.obs.tracer import maybe_span, resolve_tracer
from repro.tune.store import (ScheduleConfig, TuningCache, TuningKey,
                              TuningRecord, detect_device_kind)

__all__ = ["Trial", "TuningResult", "tune_graph", "resolve_tuning",
           "default_measure", "tuned_schedule_kwargs"]


def tuned_schedule_kwargs(config: ScheduleConfig, source: str,
                          spec: TPUSpec = V5E) -> dict:
    """:func:`~repro.core.schedule.build_schedule` kwargs for a config.

    The one mapping from a tuned :class:`ScheduleConfig` onto the
    scheduler's knobs, shared by ``compile_graph`` and
    ``replicate_app`` so the two can never drift apart.
    """
    return dict(spec=scale_spec(spec, config.vmem_fraction),
                group_vector_factors=config.group_vf,
                max_tile=config.max_tile, tile_source=source)


@dataclasses.dataclass
class Trial:
    """One measured candidate of the search."""

    label: str
    config: ScheduleConfig
    modeled_s: float
    measured_s: float


@dataclasses.dataclass
class TuningResult:
    """Outcome of :func:`tune_graph` for one ``(graph, backend, device)``."""

    key: TuningKey
    config: ScheduleConfig
    #: "measured" (fresh search) or "cache" (loaded, zero measurements)
    source: str
    trials: list[Trial]
    n_measurements: int
    record: TuningRecord
    #: candidates skipped on the calibrated prior without measuring
    n_pruned: int = 0

    def notes(self) -> list[str]:
        """Provenance lines for ``Schedule.diagnostics``."""
        lines = [f"[tune] source={self.source} backend={self.key.backend} "
                 f"device={self.key.device_kind} {self.config.describe()}"]
        if self.source == "cache":
            lines.append(f"[tune] loaded from TuningCache "
                         f"({self.n_measurements} measurements)")
        else:
            best = self.record.best_measured_s
            base = self.record.analytic_measured_s
            if best is not None and base is not None:
                lines.append(
                    f"[tune] measured {self.n_measurements} candidates: "
                    f"best={best * 1e6:.1f}us analytic={base * 1e6:.1f}us "
                    f"({base / best:.2f}x)" if best else
                    f"[tune] measured {self.n_measurements} candidates")
            if self.n_pruned:
                lines.append(f"[tune] calibrated prior pruned "
                             f"{self.n_pruned} candidates unmeasured")
        return lines


def _tuning_context(spec: TPUSpec, strict: bool, canonicalize: bool,
                    passes) -> str:
    """Digest of everything besides graph/backend/device that changes
    what a measurement means: the spec's hardware constants and the
    canonicalization regime (strict/point-fusion change the partition
    a config's ``group_vf`` refers to)."""
    import hashlib
    import json
    blob = json.dumps([sorted((f, repr(getattr(spec, f)))
                              for f in spec.__dataclass_fields__),
                       bool(strict), bool(canonicalize),
                       [type(p).__name__ for p in passes]
                       if passes is not None else None])
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def default_measure(graph, backend, config: ScheduleConfig, *,
                    spec: TPUSpec | None = None, reps: int = 3,
                    interpret: bool | None = None,
                    seed: int = 0, strict: bool = False,
                    canonicalize: bool = True, passes=None) -> float:
    """Lower ``graph`` under ``config`` and time it on the live backend.

    Compiles through :func:`repro.core.compiler.compile_graph` with the
    explicit config (no recursion into the tuner), synthesizes random
    inputs of the declared shapes, does one warmup call (JIT compile)
    and returns the best-of-``reps`` seconds per call.  Best-of is the
    standard autotuning estimator: min is robust to scheduler noise
    where mean is not.
    """
    from repro.backends import resolve
    from repro.core.compiler import compile_graph
    be = resolve(backend)
    interpret = be.resolve_interpret(interpret)
    app = compile_graph(graph, be, tune=config, spec=spec or be.spec,
                        interpret=interpret, strict=strict,
                        canonicalize=canonicalize, passes=passes)
    rng = np.random.default_rng(seed)
    inputs = {c.name: rng.normal(size=c.shape).astype(np.dtype(c.dtype))
              for c in app.graph.graph_inputs}
    names = app.output_names

    def call() -> None:
        out = app(**inputs)
        for n in names:
            np.asarray(out[n])          # force to host: include D2H

    call()                              # warmup (compiles)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best


def _model_config(graph, spec: TPUSpec, max_tile: tuple[int, int],
                  vmem_fraction: float,
                  build_kwargs: dict) -> tuple[ScheduleConfig, Schedule]:
    """The analytic pick under one (max_tile, budget) point, as a config."""
    sched = build_schedule(graph, spec=scale_spec(spec, vmem_fraction),
                           max_tile=max_tile, **build_kwargs)
    vfs = tuple(g.vector_factor if g.is_kernel else None
                for g in sched.groups)
    return (ScheduleConfig(group_vf=vfs, max_tile=max_tile,
                           vmem_fraction=vmem_fraction), sched)


def _modeled_for(graph, cfg: ScheduleConfig, spec: TPUSpec,
                 build_kwargs: dict) -> tuple[float, dict]:
    """Whole-app modeled seconds + drift features for one candidate."""
    sched = build_schedule(graph, spec=scale_spec(spec, cfg.vmem_fraction),
                           group_vector_factors=cfg.group_vf,
                           max_tile=cfg.max_tile, **build_kwargs)
    return modeled_schedule_time(sched, spec), schedule_features(sched)


def tune_graph(graph, backend="pallas", *,
               spec: TPUSpec | None = None,
               cache: TuningCache | None = None,
               device_kind: str | None = None, top_k: int = 3,
               max_trials: int = 12, reps: int = 3,
               measure: Callable[[ScheduleConfig], float] | None = None,
               interpret: bool | None = None, seed: int = 0,
               strict: bool = False, canonicalize: bool = True,
               passes=None,
               max_tile_candidates: Sequence[tuple[int, int]] = (
                   DEFAULT_MAX_TILE, (128, 1024)),
               vmem_fractions: Sequence[float] = (1.0,),
               force: bool = False, trace: Any = None,
               drift: Any = None, calibrate: Any = None,
               prior_ratio: float = 1.3) -> TuningResult:
    """Search the schedule space for ``graph`` by measuring candidates.

    The search space is the per-group vector factor (top-``top_k`` by
    the analytic model), the ``max_tile`` height cap and the fusion
    budget (``vmem_fractions`` of the spec's VMEM).  ``measure`` maps a
    :class:`ScheduleConfig` to seconds per call; the default lowers and
    times on the live backend — tests inject deterministic fakes.  At
    most ``max_trials`` measurements run; the analytic pick is always
    one of them, so the returned winner is never slower than it (as
    measured).  Results persist in ``cache`` keyed by graph signature,
    backend, device kind and input shapes; a hit returns immediately
    with ``n_measurements == 0``.

    Observability: ``trace`` wraps every measurement in a
    ``tune.trial`` span (label, modeled and measured seconds) for the
    flight recorder; each trial also appends a ``kind="trial"``
    (modeled, measured) row to the drift log living beside the tuning
    cache (``drift.jsonl`` under ``cache.root``), the data ROADMAP
    item 3's calibration pass consumes.  ``drift=False`` disables the
    rows, ``drift=`` a :class:`~repro.obs.drift.DriftLog`/path
    redirects them.  Every trial row carries the candidate schedule's
    cost-model **features** so it can feed the calibration fit.

    ``calibrate`` (same protocol as ``compile_graph``) swaps in the
    fitted :class:`~repro.tune.calibrate.CalibratedSpec` for this
    backend + device kind before the search starts.  Under a
    calibrated spec the model is trusted further: a candidate whose
    modeled time exceeds ``prior_ratio`` times the best modeled time
    seen so far is **pruned without measuring** (counted in
    ``n_pruned``), so a calibrated search reaches the same winner in
    strictly fewer measurements than an uncalibrated one whenever the
    fitted model ranks the pruned candidates correctly.  An
    *uncalibrated* spec never prunes — the seed model has not earned
    that trust (ROADMAP item 3).
    """
    from repro.backends import resolve_calibrated
    be = resolve_calibrated(backend, calibrate)
    be.require("tuning")
    interpret = be.resolve_interpret(interpret)
    spec = spec or be.spec
    # pruning is gated on evidence: only a spec that went through the
    # calibration fit (carries fitted per-kind ii multipliers) may veto
    # measurements on modeled time alone
    prune = bool(getattr(spec, "ii_scale", ())) and prior_ratio is not None
    # NOT `cache or ...`: an empty TuningCache is falsy (__len__ == 0)
    # and must still be used, not silently swapped for the default root
    cache = cache if cache is not None else TuningCache()
    device_kind = device_kind or detect_device_kind()
    tracer = resolve_tracer(trace)
    # trial rows land beside the tuning cache by default: one directory
    # holds everything learned about this machine
    drift_log = (DriftLog(os.path.join(cache.root, "drift.jsonl"))
                 if drift is None else resolve_drift(drift))
    # the measured program must BE the compiled program: the compile
    # flags ride in both the search (below) and the cache key, so a
    # config tuned under one regime never serves another — and the
    # backend rides along so the scheduler budgets with ITS constants
    build_kwargs = dict(strict=strict, canonicalize=canonicalize,
                        passes=passes, backend=be)
    context = _tuning_context(spec, strict, canonicalize, passes)
    key_pre = TuningKey.for_graph(graph, be, device_kind,
                                  interpret=interpret, context=context)
    if not force:
        rec = cache.get(key_pre)
        if rec is not None:
            return TuningResult(key_pre, rec.config, "cache", [], 0, rec)

    counter = {"n": 0, "pruned": 0}
    if measure is None:
        # the backend's measurement hook is the harness; the seeds all
        # point it at default_measure (lower + time on the live device)
        hook = be.measure if be.measure is not None else default_measure

        def measure(cfg: ScheduleConfig, _g=graph) -> float:
            return hook(_g, be, cfg, spec=spec, reps=reps,
                        interpret=interpret, seed=seed,
                        strict=strict, canonicalize=canonicalize,
                        passes=passes)
    user_measure = measure

    def timed(cfg: ScheduleConfig) -> float:
        counter["n"] += 1
        return user_measure(cfg)

    trials: list[Trial] = []
    seen: set[ScheduleConfig] = set()
    best_modeled = [float("inf")]

    def try_config(label: str, cfg: ScheduleConfig, modeled_s: float,
                   features: dict | None = None) -> Trial | None:
        if cfg in seen or counter["n"] >= max_trials:
            return None
        seen.add(cfg)
        if modeled_s > 0:
            best_modeled[0] = min(best_modeled[0], modeled_s)
        if prune and modeled_s > prior_ratio * best_modeled[0]:
            counter["pruned"] += 1
            return None
        with maybe_span(tracer, "tune.trial", cat="tune",
                        graph=graph.name, label=label) as sp:
            measured_s = timed(cfg)
            sp.set(modeled_s=modeled_s, measured_s=measured_s)
        t = Trial(label, cfg, modeled_s, measured_s)
        trials.append(t)
        if drift_log is not None:
            # sig/shapes bind late: set post-canonicalization, below
            attrs = dict(label=label, device=device_kind)
            if features is not None:
                attrs["features"] = features
            drift_log.record("trial", drift_sig, drift_shapes, be.name,
                             modeled_s, measured_s, **attrs)
        return t

    # ---- analytic baseline: the model's pick, measured first --------
    baseline_cfg, baseline_sched = _model_config(
        graph, spec, tuple(max_tile_candidates[0]), 1.0, build_kwargs)
    # canonicalization may have rewritten the graph in place: alias the
    # post-canonicalization signature so either form hits later
    key_post = TuningKey.for_graph(baseline_sched.graph, be,
                                   device_kind, interpret=interpret,
                                   context=context)
    tunable = [i for i, g in enumerate(baseline_sched.groups)
               if g.is_kernel]
    drift_sig = baseline_sched.graph.signature()
    drift_shapes = [list(c.shape)
                    for c in baseline_sched.graph.graph_inputs]

    if not tunable:                      # nothing to search: model wins
        rec = TuningRecord(config=baseline_cfg, source="measured",
                           modeled_s=0.0, n_trials=0)
        cache.put(key_post, rec, aliases=(key_pre,))
        return TuningResult(key_pre, baseline_cfg, "measured", [], 0, rec)

    analytic = try_config("analytic", baseline_cfg,
                          modeled_schedule_time(baseline_sched, spec),
                          schedule_features(baseline_sched))
    assert analytic is not None
    best = analytic

    # ---- axis 1: per-group vector factor (coordinate descent) ------
    for gi in tunable:
        group = baseline_sched.groups[gi]
        records = sweep_vector_factor(group, spec,
                                      max_tile=baseline_cfg.max_tile,
                                      backend=be)
        feasible = sorted((r for r in records if r["feasible"]),
                          key=lambda r: r["modeled_s"])
        for r in feasible[:top_k]:
            vfs = list(best.config.group_vf)
            vfs[gi] = r["vector_factor"]
            cand = dataclasses.replace(best.config, group_vf=tuple(vfs))
            mod_s, feats = _modeled_for(graph, cand, spec, build_kwargs)
            t = try_config(f"g{gi}:vf{r['vector_factor']}", cand,
                           mod_s, feats)
            if t is not None and t.measured_s < best.measured_s:
                best = t

    # ---- axis 2: tile-height cap ------------------------------------
    for mt in max_tile_candidates[1:]:
        cand = dataclasses.replace(best.config, max_tile=tuple(mt))
        mod_s, feats = _modeled_for(graph, cand, spec, build_kwargs)
        t = try_config(f"max_tile{tuple(mt)}", cand, mod_s, feats)
        if t is not None and t.measured_s < best.measured_s:
            best = t

    # ---- axis 3: fusion budget (changes the partition itself) -------
    for frac in vmem_fractions:
        if frac == 1.0:
            continue
        cfg_f, sched_f = _model_config(graph, spec, best.config.max_tile,
                                       frac, build_kwargs)
        t = try_config(f"vmem{frac:g}", cfg_f,
                       modeled_schedule_time(sched_f, spec),
                       schedule_features(sched_f))
        if t is not None and t.measured_s < best.measured_s:
            best = t

    rec = TuningRecord(config=best.config, source="measured",
                       best_measured_s=best.measured_s,
                       analytic_measured_s=analytic.measured_s,
                       modeled_s=best.modeled_s, n_trials=counter["n"],
                       n_pruned=counter["pruned"])
    cache.put(key_post, rec, aliases=(key_pre,))
    if drift_log is not None:
        drift_log.flush()       # trial rows persist with the record
    return TuningResult(key_pre, best.config, "measured", trials,
                        counter["n"], rec, n_pruned=counter["pruned"])


def resolve_tuning(graph, backend, *, tune: Any,
                   spec: TPUSpec | None = None,
                   cache: TuningCache | None = None,
                   interpret: bool | None = None,
                   **tune_kwargs: Any) -> tuple[ScheduleConfig, str,
                                                list[str]] | None:
    """Normalize a ``tune=`` argument into ``(config, source, notes)``.

    Shared by :func:`repro.core.compiler.compile_graph` and
    :func:`repro.parallel.replicate.replicate_app`:

    - ``None`` / ``"model"`` — no tuning (analytic sweep); returns None,
    - a :class:`ScheduleConfig` — apply verbatim (source ``"config"``),
    - ``"auto"`` — consult the :class:`TuningCache`, searching with
      :func:`tune_graph` on a miss (source ``"measured"`` or
      ``"cache"``).
    """
    if tune is None or tune == "model":
        return None
    if isinstance(tune, ScheduleConfig):
        return (tune, "config",
                [f"[tune] source=config {tune.describe()}"])
    if tune == "auto":
        result = tune_graph(graph, backend, spec=spec, cache=cache,
                            interpret=interpret, **tune_kwargs)
        return result.config, result.source, result.notes()
    raise ValueError(
        f"tune must be None, 'model', 'auto' or a ScheduleConfig; "
        f"got {tune!r}")
