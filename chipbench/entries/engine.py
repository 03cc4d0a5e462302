"""Host frames through ``StreamEngine.submit`` and
``StreamRequest.result``, the way a camera deployment serves them.

The configuration's ``engine`` group sets ``max_batch`` and
``replicas``; everything else is the engine's default.  Set-up warms the
engine through the same public calls the window makes: it serves bursts
of 1 and of one more than each power of two up to ``max_batch`` frames
(an idle engine sends a burst's first frame alone and batches the rest,
or batches them all), and repeats them until every batch width the
engine launched (``report()["buckets"]``) has been launched
``WARM_LAUNCHES`` times and a whole round of bursts brought no new one.
So every width is compiled, every staging buffer it rotates through
(the engine's ``inflight + 1``, 3 by default) has been written once, and
the readback path has run, before the window.  The bursts are few
because the engine keeps every served frame's outputs on the device.
"""
from __future__ import annotations

import numpy as np

from harness import drive

WARM_LAUNCHES = 3
WARM_ROUNDS = 20


def start(cell, app, pool_dev: list[dict], devices: list) -> drive.Entry:
    from repro.runtime import StreamEngine
    pool = [{k: np.asarray(v) for k, v in s.items()} for s in pool_dev]
    eng = cell.config.get("engine", {})
    max_batch = int(eng.get("max_batch", 8))
    engine = StreamEngine(backend="pallas", max_batch=max_batch,
                          replicas=int(eng.get("replicas", 1)))
    warm(engine, app, pool, max_batch)
    engine.telemetry.reset()
    return drive.Entry(
        pool=pool,
        issue=lambda inputs: engine.submit(app, inputs, block=False),
        wait=lambda req, timeout: req.result(timeout),
        report=engine.report, close=engine.close)


def warm(engine, app, pool: list[dict], max_batch: int) -> dict[int, int]:
    """Serve rounds of bursts until the batch widths settle; returns the
    launches per width."""
    bursts = [1] + [2 ** k + 1 for k in range(max_batch.bit_length())
                    if 2 ** k <= max_batch]
    sent = 0
    widths: dict[int, int] = {}
    for rnd in range(WARM_ROUNDS):
        before = set(widths)
        for n in bursts:
            reqs = [engine.submit(app, pool[(sent + j) % len(pool)])
                    for j in range(n)]
            sent += n
            for req in reqs:
                req.result(timeout=600)
        widths = engine.report()["buckets"]
        if rnd and set(widths) == before \
                and min(widths.values()) >= WARM_LAUNCHES:
            break
    return widths


def programs(cell, app, plane, devices: list) -> list:
    """``(label, compile)`` for every batch width the engine can launch,
    sharded over the cell's chips, with ``app`` compiled unjitted."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.runtime import MicroBatcher
    eng = cell.config.get("engine", {})
    mb = MicroBatcher(max_batch=int(eng.get("max_batch", 8)),
                      replicas=int(eng.get("replicas", 1)),
                      devices=devices[:cell.chips], backend="pallas")
    out = []
    for width in sorted({mb.bucket(n) for n in range(1, mb.max_batch + 1)}):
        shaped = [jax.ShapeDtypeStruct((width,) + plane.shape, plane.dtype)
                  for _ in app.input_names]
        if cell.chips == 1:
            shaped = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=SingleDeviceSharding(
                                               devices[0]))
                      for s in shaped]
        out.append((f"engine batch {width}",
                    lambda width=width, shaped=shaped: mb.batched_fn(
                        app, width).lower(*shaped).compile()))
    return out
