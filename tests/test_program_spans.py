"""Program spans on the profiler's clock, and an engine that keeps no
served batch on the device.

The serving path's spans (``obs.tracer.program_span``) land in a JAX
profiler trace and, when a flight recorder is given, in its ring too.
These tests read both sinks back from a ``StreamEngine`` serving bursts
that form batches of every width up to ``max_batch``, and check the
engine's device memory stays flat while it serves.
"""
import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.core import DataflowGraph, compile_graph
from repro.obs import Tracer, install, uninstall
from repro.obs import tracer as tracer_mod
from repro.obs.tracer import program_span
from repro.runtime import StreamEngine

WIDTHS = (1, 2, 4, 8)
BATCH_SPANS = ("batch.stack", "batch.launch", "engine.wait", "engine.copy")


def _double(h=8, w=128):
    g = DataflowGraph("spans_dbl")
    x = g.input("x", (h, w))
    g.output(g.point(x, lambda v: v * 2.0, name="dbl"), "y")
    return g


def _profiled(tmp_path, body):
    """Run ``body()`` under a JAX profiler session; return the host
    events ``(name, start_ns, end_ns, {stat: value})`` it recorded."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("app.", "batch.", "engine.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _serve_bursts(eng, app, rng):
    """One burst per width, each queued whole before the worker can
    form a batch (the engine's condition is re-entrant), so burst ``w``
    is exactly one batch of width ``w``."""
    served = 0
    for w in WIDTHS:
        frames = [rng.normal(size=(8, 128)).astype(np.float32)
                  for _ in range(w)]
        with eng._cond:
            reqs = [eng.submit(app, {"x": f}) for f in frames]
        for f, r in zip(frames, reqs):
            np.testing.assert_array_equal(r.result(timeout=120)["y"],
                                          f * 2.0)
            served += 1
    return served


def _check_batches(spans):
    """``spans``: ``(name, t0, t1, args)``.  Every batch has one span of
    each phase, in order, all with its id and width."""
    by_batch = {}
    for name, t0, t1, args in spans:
        if name in BATCH_SPANS:
            by_batch.setdefault(int(args["batch"]), []).append(
                (name, t0, t1, args))
    widths = []
    for seq, got in by_batch.items():
        got.sort(key=lambda s: s[1])
        assert [s[0] for s in got] == list(BATCH_SPANS), (seq, got)
        for (_, _, end, _), (_, start, _, _) in zip(got, got[1:]):
            assert start >= end
        assert len({int(s[3]["width"]) for s in got}) == 1
        widths.append(int(got[0][3]["width"]))
        copy = got[-1][3]
        assert {"batch", "width", "bytes", "shards"} <= set(copy)
        assert int(copy["bytes"]) == int(got[0][3]["width"]) * 8 * 128 * 4
        assert int(copy["shards"]) == 1         # one device, one shard
    return sorted(widths)


@pytest.mark.parametrize("sink", ["profiler", "flight_recorder"])
def test_every_batch_width_has_one_wait_then_one_copy(sink, tmp_path, rng):
    app = compile_graph(_double(), backend="xla")
    tr = Tracer() if sink == "flight_recorder" else None
    eng = StreamEngine(backend="xla", max_batch=8, trace=tr or False)
    served = []

    def body():
        served.append(_serve_bursts(eng, app, rng))

    try:
        if tr is None:
            events = _profiled(tmp_path, body)
        else:
            body()
            events = [(e.name, e.ts, e.ts + e.dur, e.args or {})
                      for e in tr.events() if e.ph == "X"]
        rep = eng.report()
    finally:
        eng.close()
    assert served == [sum(WIDTHS)]
    assert rep["measured"]["completed"] == sum(WIDTHS)
    assert _check_batches(events) == list(WIDTHS)
    names = {e[0] for e in events}
    assert {"engine.submit", "engine.park"} <= names
    # readback is wait + copy, batch by batch
    phases = rep["measured"]["phases"]
    assert phases["wait"]["count"] == phases["copy"]["count"] == len(WIDTHS)
    assert (phases["wait"]["mean_ms"] + phases["copy"]["mean_ms"]
            == pytest.approx(phases["readback"]["mean_ms"], rel=1e-6))
    assert rep["readback_shards"] == {1: len(WIDTHS)}


def test_app_launch_and_wait_spans_on_the_device_path(tmp_path):
    app = compile_graph(_double(), backend="xla")
    x = np.ones((8, 128), np.float32)

    def body():
        for _ in range(3):
            app.launch(x=x).result()

    events = _profiled(tmp_path, body)
    launches = [e for e in events if e[0] == "app.launch"]
    waits = [e for e in events if e[0] == "app.wait"]
    assert len(launches) == len(waits) == 3
    for (_, _, l1, largs), (_, w0, _, _) in zip(sorted(launches),
                                                 sorted(waits)):
        assert w0 >= l1 and not largs      # no args on the per-frame path
    tr = install(Tracer())
    try:
        app.launch(x=x).result()
    finally:
        uninstall()
    assert [(e.name, e.cat) for e in tr.events()] == [
        ("app.launch", "app"), ("app.wait", "app")]


def test_program_span_without_a_tracer_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("program_span read the clock")

    monkeypatch.setattr(tracer_mod.time, "perf_counter", no_clock)
    with program_span("engine.wait", None, batch=3, width=2):
        pass
    with program_span("app.launch"):
        pass


def test_program_span_records_the_flight_recorder_span():
    tr = Tracer()
    with program_span("engine.copy", tr, batch=7, width=4, bytes=16):
        time.sleep(0.001)
    (ev,) = tr.events()
    assert (ev.ph, ev.name, ev.cat) == ("X", "engine.copy", "engine")
    assert ev.args == {"batch": 7, "width": 4, "bytes": 16}
    assert ev.dur >= 0.001
    with pytest.raises(KeyError):           # the body's error propagates
        with program_span("engine.wait", tr, batch=8, width=4):
            raise KeyError("boom")
    assert [e.name for e in tr.events()] == ["engine.copy", "engine.wait"]


def test_engine_keeps_no_served_batch_on_the_device(rng):
    """Device memory stays flat while the engine serves: before, every
    retired batch's outputs stayed referenced (two arrays a frame)."""
    app = compile_graph(_double(64, 256), backend="xla")
    frames = [rng.normal(size=(64, 256)).astype(np.float32)
              for _ in range(4)]

    def serve(eng, n):
        for i in range(n):
            eng.submit(app, {"x": frames[i % 4]}).result(timeout=120)

    with StreamEngine(backend="xla", max_batch=4, trace=False) as eng:
        serve(eng, 10)
        gc.collect()
        after_10 = len(jax.live_arrays())
        serve(eng, 390)
        gc.collect()
        after_400 = len(jax.live_arrays())
    assert after_400 - after_10 <= 4, (after_10, after_400)
