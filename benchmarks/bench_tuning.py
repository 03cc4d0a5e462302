"""Profile-guided tuning sweep: tuned vs analytic vs default schedules.

Three schedule regimes, measured end-to-end on bench_parallel's app set
(gaussian_blur, filter_chain):

- **default** — the paper's explicit knob at its most conservative
  setting (``vector_factor=1``): what a user gets with no model and no
  measurements;
- **analytic** — PR 3's cost-model sweep (``compile_graph`` default):
  the model picks per-group tiles with zero measurements;
- **tuned** — ``tune="auto"``: the analytic sweep demoted to a prior,
  candidates *measured* on the live backend, winner persisted in the
  on-disk :class:`~repro.tune.store.TuningCache`.

Two invariants ride along (asserted in ``--smoke`` for CI):

1. the tuned schedule is never slower than the analytic pick — the
   analytic config is always one of the measured candidates, so the
   search winner bounds it by construction;
2. a second ``tune="auto"`` compile performs ZERO measurements — it is
   served entirely from the persistent cache (the bitstream-reuse
   property).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import compile_graph
from repro.core.apps import build_app
from repro.tune import TuningCache, tune_graph
import repro.tune.search as _search

_ROOT = os.path.join(os.path.dirname(__file__), "..")
_APPS = ("gaussian_blur", "filter_chain")      # bench_parallel's app set
_BACKEND = "pallas"


def _measured_us(app, h: int, w: int, reps: int) -> float:
    rng = np.random.default_rng(0)
    x = rng.normal(size=(h, w)).astype(np.float32)
    np.asarray(app(img=x)["out"])                  # warmup
    import time
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(app(img=x)["out"])
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def app_rows(name: str, h: int, w: int, reps: int,
             cache: TuningCache) -> list[dict]:
    rows = []

    default_app = compile_graph(build_app(name, h, w), _BACKEND,
                                vector_factor=1)
    rows.append({"name": f"tuning_{name}_default", "app": name,
                 "us": _measured_us(default_app, h, w, reps),
                 "vector_factors": [g.vector_factor
                                    for g in default_app.schedule.groups],
                 "source": "forced(vf=1)", "h": h, "w": w})

    analytic_app = compile_graph(build_app(name, h, w), _BACKEND)
    rows.append({"name": f"tuning_{name}_analytic", "app": name,
                 "us": _measured_us(analytic_app, h, w, reps),
                 "vector_factors": [g.vector_factor
                                    for g in analytic_app.schedule.groups],
                 "source": "model", "h": h, "w": w})

    result = tune_graph(build_app(name, h, w), _BACKEND, cache=cache,
                        reps=reps)
    assert result.source == "measured", result.source
    assert result.record.best_measured_s <= result.record.analytic_measured_s
    tuned_app = compile_graph(build_app(name, h, w), _BACKEND, tune="auto",
                              tune_cache=cache)
    rows.append({"name": f"tuning_{name}_tuned", "app": name,
                 "us": _measured_us(tuned_app, h, w, reps),
                 "vector_factors": [g.vector_factor
                                    for g in tuned_app.schedule.groups],
                 "source": "measured", "h": h, "w": w,
                 "config": result.config.to_json(),
                 "n_measurements": result.n_measurements,
                 "search_best_us": result.record.best_measured_s * 1e6,
                 "search_analytic_us":
                     result.record.analytic_measured_s * 1e6,
                 "trials": [{"label": t.label,
                             "modeled_us": t.modeled_s * 1e6,
                             "measured_us": t.measured_s * 1e6}
                            for t in result.trials]})

    # bitstream-reuse property: the second auto-tune measures NOTHING
    calls = {"n": 0}
    real = _search.default_measure

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    _search.default_measure = counting
    try:
        again = tune_graph(build_app(name, h, w), _BACKEND, cache=cache)
    finally:
        _search.default_measure = real
    assert again.source == "cache" and again.n_measurements == 0
    assert calls["n"] == 0, "cache hit must not re-measure"
    rows.append({"name": f"tuning_{name}_cached", "app": name, "us": 0.0,
                 "source": "cache", "n_measurements": 0,
                 "config": again.config.to_json(), "h": h, "w": w})

    # correctness: tuning picks tiles, never semantics
    rng = np.random.default_rng(1)
    x = rng.normal(size=(h, w)).astype(np.float32)
    a = np.asarray(analytic_app(img=x)["out"])
    b = np.asarray(tuned_app(img=x)["out"])
    assert np.array_equal(a, b), f"{name}: tuned changed bits"

    rows.append(calibrated_row(name, h, w, reps, result))
    return rows


def calibrated_row(name: str, h: int, w: int, reps: int,
                   uncal) -> dict:
    """Re-run the search under a calibrated prior and report the pruning.

    The prior comes from the checked-in golden drift fixture (the same
    rows ``tests/test_calibration.py`` pins), so this bench demonstrates
    the full loop: drift log -> fitted constants -> fewer measurements.
    The search must never measure *more* than the uncalibrated one; the
    hard strictly-fewer/same-winner property is asserted with an
    injected measure fn in the test suite, not here, because live
    timings can legitimately reorder near-tied candidates.
    """
    from repro.obs.drift import DriftRow
    from repro.tune.calibrate import calibrate

    fix = os.path.join(_ROOT, "tests", "fixtures",
                       "drift_bench_parallel.jsonl")
    with open(fix) as f:
        drift = [DriftRow.from_dict(json.loads(line)) for line in f]
    spec = calibrate(drift).spec
    with tempfile.TemporaryDirectory() as root:
        res = tune_graph(build_app(name, h, w), _BACKEND,
                         cache=TuningCache(root), reps=reps,
                         calibrate=spec)
    assert res.source == "measured", res.source
    assert res.n_measurements <= uncal.n_measurements, \
        (res.n_measurements, uncal.n_measurements)
    return {"name": f"tuning_{name}_calibrated", "app": name, "us": 0.0,
            "source": "measured+prior", "h": h, "w": w,
            "config": res.config.to_json(),
            "n_measurements": res.n_measurements,
            "n_pruned": res.n_pruned,
            "uncalibrated_n_measurements": uncal.n_measurements,
            "same_winner": res.config == uncal.config,
            "search_best_us": res.record.best_measured_s * 1e6}


def run(smoke: bool = False) -> list[dict]:
    h, w = (96, 256) if smoke else (256, 640)
    reps = 2 if smoke else 5
    apps = _APPS[:1] if smoke else _APPS
    rows = []
    with tempfile.TemporaryDirectory() as root:
        for name in apps:
            rows += app_rows(name, h, w, reps, TuningCache(root))
    if smoke:
        tuned = next(r for r in rows if r["name"].endswith("_tuned"))
        # tuned >= analytic, on the search's own measurements (the
        # analytic config is trial 0, so this holds by construction)
        assert tuned["search_best_us"] <= tuned["search_analytic_us"], tuned
    return rows


def main() -> None:
    smoke = "--smoke" in sys.argv
    rows = run(smoke=smoke)
    for r in rows:
        extra = {k: v for k, v in r.items()
                 if k not in ("name", "us", "trials")}
        print(f"{r['name']}: {r['us']:.1f} us/call {extra}")
    payload = {"rows": rows, "smoke": smoke}
    os.makedirs(os.path.join(_ROOT, "experiments"), exist_ok=True)
    with open(os.path.join(_ROOT, "experiments", "bench_tuning.json"),
              "w") as f:
        json.dump(payload, f, indent=1)
    if not smoke:
        with open(os.path.join(_ROOT, "BENCH_tuning.json"), "w") as f:
            json.dump(payload, f, indent=1)
    if smoke:
        print("smoke ok: tuned <= analytic on the measured search, and "
              "the second tune was a zero-measurement cache hit")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
