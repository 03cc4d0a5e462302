"""One run of one cell: set-up, the measured window, the check.

Set-up compiles the app through ``compile_graph(backend="pallas")``
(from the persistent compile cache after a checkout's first run), makes
the frame pool on the device from the seed, and starts the mix's entry
(``entries/<entry>.py``), which warms every shape the window uses.
``setup_s`` runs from process start to the window's first frame.  The
mix's loop (``loops/<loop>.py``) then drives the entry for the window.
Only after the window, the peak device memory read and the entry
closed, the sampled outputs are compared with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Any

import numpy as np

from harness import check, drive, profile, roofline, spec
from harness.frames import frame_sets

#: the traced run's window: a few seconds keep the trace small
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: spec.Cell
    window: drive.Window
    setup_s: float
    compile_graph_s: float
    #: the entry's ``report()`` after the window (``StreamEngine.report()``
    #: for the engine entry, None for device frames)
    engine_report: dict | None
    #: the reduced profiler trace (``--trace 1`` runs)
    trace: dict | None
    #: least device seconds per frame (``harness.roofline``)
    min_frame_s: float


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed & (2 ** 64 - 1))


def compile_app(cell: spec.Cell, ref) -> tuple[Any, float]:
    """The app through ``compile_graph(backend="pallas")``, and the
    seconds that took."""
    from repro.core import compile_graph
    from repro.core.apps import build_app

    cfg = cell.config
    t = time.perf_counter()
    app = compile_graph(build_app(cfg["app"], int(cfg["height"]),
                                  int(cfg["width"])), backend="pallas")
    seconds = time.perf_counter() - t
    if tuple(app.input_names) != ref.INPUTS or \
            tuple(app.output_names) != ref.OUTPUTS:
        raise RuntimeError(f"{cfg['app']}: program I/O {app.input_names} -> "
                           f"{app.output_names}, reference {ref.INPUTS} -> "
                           f"{ref.OUTPUTS}")
    return app, seconds


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        devices: list, t_start: float) -> dict:
    """One run on ``devices``; returns the result line's object."""
    import jax

    cfg, mix = cell.config, cell.traffic
    kind = devices[0].device_kind
    peak = roofline.peaks(kind) if devices[0].platform == "tpu" else None
    rng = rng_for(seed)
    ref = spec.reference(cfg["app"])
    shape = (int(cfg["height"]), int(cfg["width"]))
    app, compile_s = compile_app(cell, ref)
    pool_dev = frame_sets(seed, drive.POOL, shape, ref.INPUTS,
                          device=devices[0])
    jax.block_until_ready(pool_dev)
    entry = spec.entry(mix["entry"]).start(cell, app, pool_dev, devices)
    loop = spec.loop(mix["loop"])

    window_s = min(seconds, TRACE_SECONDS) if traced else seconds
    capture = profile.Capture() if traced else None
    setup_s = time.perf_counter() - t_start
    if capture is not None:
        capture.start()
    try:
        window = loop.run(mix, entry, window_s, rng,
                          drive.annotate(traced))
    finally:
        events = capture.stop() if capture is not None else None
    report = entry.report()
    entry.close()
    memory_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                      for d in devices) if peak is not None else 0
    del entry, app
    gc.collect()          # the engine's device buffers go before the check

    min_s = (roofline.min_seconds(ref, *shape, peak)
             if peak is not None else math.nan)
    reduced = (profile.reduce(events, [d.id for d in devices])
               if events is not None else None)
    record = Run(cell, window, setup_s, compile_s, report, reduced, min_s)

    gap = check.compare(window.outputs,
                        {f.index: f.pool for f in window.frames},
                        pool_dev, ref)
    sampled = min(drive.SAMPLE, len(window.frames))
    checks = {
        "gap": {"value": gap, "limit": float(cfg["check"]["gap"])},
        "missing": {"value": len(window.failed()), "limit": 0},
        "uncompared": {"value": sampled - len(window.outputs), "limit": 0},
    }
    correct = check.verdict(checks)
    names = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in names:
        value = spec.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out: dict[str, Any] = {"correct": correct,
                           "attempted": len(window.frames),
                           "failed": len(window.failed()),
                           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    lateness = [f.issued - f.due for f in window.frames
                if f.issued is not None]
    print(f"[chipbench] {cell.name} seed {seed}: setup {setup_s:.3f} s, "
          f"compile_graph {compile_s:.3f} s, {len(window.frames)} frames "
          f"sent, generator late by at most "
          f"{max(lateness, default=0.0) * 1e3:.3f} ms", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out["checks"] = checks
    return out
