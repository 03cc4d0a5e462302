"""Flight-recorder tests: tracer, export, metrics, drift, engine wiring.

Covers the observability contracts the rest of the repo leans on:

- concurrent tracing — interleaved spans from many threads nest and
  attribute correctly, per-thread timelines stay monotonic;
- the ring buffer drops oldest and never blocks, and the Chrome
  exporter sanitizes the eviction damage into a valid trace;
- a disabled tracer is a cheap ``None`` guard on the hot path
  (overhead bound asserted);
- one traced engine request yields a single trace id whose phase
  spans tile submit→complete with no gaps;
- telemetry reservoirs (not first-N buffers): late-run latency shifts
  move p99;
- drift capture persists modeled-vs-measured rows on disk and
  ``drift_report`` reproduces a misordering as negative rank
  correlation.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from repro.obs import (DriftLog, Histogram, MetricsRegistry, Tracer,
                       drift_report, export_chrome_trace, load_chrome_trace,
                       resolve_drift, resolve_tracer, spearman,
                       validate_chrome_trace)


# ----------------------------------------------------------------------
# tracer core
# ----------------------------------------------------------------------
def test_span_nesting_and_exit_attrs():
    tr = Tracer()
    with tr.span("outer", cat="t", a=1) as sp:
        with tr.span("inner", cat="t"):
            pass
        sp.set(b=2)
    evs = tr.events()
    assert [(e.ph, e.name) for e in evs] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
    assert evs[0].args == {"a": 1}
    assert evs[-1].args == {"b": 2}          # exit attrs ride on the E


def test_cross_thread_begin_end():
    tr = Tracer()
    tok = tr.begin("xfer", cat="t")
    out: list = []
    th = threading.Thread(target=lambda: out.append(tr.end(tok)))
    th.start()
    th.join()
    evs = tr.events()
    assert len(evs) == 1 and evs[0].ph == "X" and evs[0].name == "xfer"
    assert evs[0].dur >= 0.0
    # the X is attributed to the *beginning* thread's timeline
    assert evs[0].tid == threading.main_thread().ident


def test_concurrent_interleaved_spans_validate(tmp_path):
    tr = Tracer()
    barrier = threading.Barrier(4)

    def work(i: int):
        barrier.wait()
        for j in range(50):
            with tr.span(f"req{i}", cat="load", j=j):
                with tr.span("step", cat="load"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    payload = export_chrome_trace(tr, str(tmp_path / "t.json"))
    stats = validate_chrome_trace(payload)      # raises on any violation
    assert stats["spans"] == 4 * 50 * 2
    assert stats["threads"] == 4
    # every thread's B events name only its own requests
    by_tid: dict = {}
    for e in tr.events():
        if e.ph == "B" and e.name.startswith("req"):
            by_tid.setdefault(e.tid, set()).add(e.name)
    assert all(len(names) == 1 for names in by_tid.values())


def test_ring_drops_oldest_never_blocks(tmp_path):
    tr = Tracer(capacity=64)
    for i in range(500):
        with tr.span(f"s{i}", cat="t"):
            pass
    assert len(tr) == 64
    assert tr.dropped == 2 * 500 - 64
    names = [e.name for e in tr.events()]
    assert "s0" not in names and "s499" in names      # oldest evicted
    # eviction orphans E events / leaves dangling Bs; export sanitizes
    payload = export_chrome_trace(tr, str(tmp_path / "ring.json"))
    validate_chrome_trace(payload)


def test_disabled_tracer_is_none_and_cheap():
    assert resolve_tracer(False) is None
    assert resolve_tracer(Tracer(enabled=False)) is None
    # the hot-path pattern is a None guard; bound its per-iteration
    # cost (generous: CI boxes are noisy, the guard is ~10ns)
    tracer = resolve_tracer(False)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        if tracer is not None:
            tracer.instant("never")
    dt = time.perf_counter() - t0
    assert dt / n < 2e-6, f"disabled-tracer guard cost {dt / n * 1e9:.0f}ns"


def test_tracer_is_always_truthy():
    # __len__ would make an empty tracer falsy and `tracer or x`
    # silently discard a live recorder (the engine->batcher bug)
    assert bool(Tracer())
    assert len(Tracer()) == 0


def test_resolve_tracer_semantics():
    tr = Tracer()
    assert resolve_tracer(tr) is tr
    assert isinstance(resolve_tracer(True), Tracer)
    assert resolve_tracer(False) is None
    with pytest.raises(TypeError):
        resolve_tracer("out.json")


def test_counter_and_instant_export(tmp_path):
    tr = Tracer()
    tr.instant("mark", cat="t")
    tr.counter("depth", 3)
    payload = export_chrome_trace(tr, str(tmp_path / "c.json"))
    phs = {e["ph"] for e in payload["traceEvents"]}
    assert "i" in phs and "C" in phs
    validate_chrome_trace(payload)


# ----------------------------------------------------------------------
# chrome export
# ----------------------------------------------------------------------
def test_export_roundtrip_and_schema(tmp_path):
    tr = Tracer()
    with tr.span("a", cat="t"):
        pass
    aid = tr.new_id()
    now = time.perf_counter()
    tr.async_span("phase", aid, now, now + 1e-3, cat="req")
    path = str(tmp_path / "out.json")
    export_chrome_trace(tr, path)
    payload = load_chrome_trace(path)
    assert payload["displayTimeUnit"] == "ms"
    stats = validate_chrome_trace(payload)
    assert stats["spans"] == 1 and stats["async_spans"] == 1
    # raw file is plain JSON (Perfetto/chrome://tracing loadable)
    with open(path) as f:
        assert isinstance(json.load(f)["traceEvents"], list)


def test_validate_rejects_unbalanced():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 0},
            {"ph": "E", "name": "b", "pid": 1, "tid": 1, "ts": 1},
        ]})


# ----------------------------------------------------------------------
# metrics: reservoir histograms
# ----------------------------------------------------------------------
def test_histogram_reservoir_sees_late_run():
    # first-N truncation would freeze the percentile on the early era;
    # a uniform reservoir keeps sampling the whole run
    h = Histogram("lat", capacity=500, seed=0)
    h.extend([1.0] * 5000)
    assert h.percentile(99) == 1.0
    h.extend([100.0] * 5000)
    assert h.count == 10_000
    assert h.percentile(99) == 100.0          # late shift visible
    assert 0.3 < np.mean(h.samples() == np.float64(100.0)) < 0.7


def test_histogram_deterministic_seed():
    a, b = Histogram("x", capacity=64, seed=7), Histogram("x", capacity=64,
                                                          seed=7)
    xs = list(range(10_000))
    a.extend(xs)
    b.extend(xs)
    assert a.samples() == b.samples()


def test_registry_type_conflict():
    reg = MetricsRegistry()
    reg.counter("n")
    with pytest.raises(ValueError):
        reg.histogram("n")
    assert sorted(reg.names()) == ["n"]


def test_telemetry_p99_tracks_late_latency_shift():
    from repro.runtime.telemetry import Telemetry
    tel = Telemetry(max_samples=1000, seed=0)
    now = time.perf_counter()
    tel.observe_batches([(now, 8, None, [0.001] * 100, None)
                         for _ in range(50)])
    assert tel.snapshot()["latency_p99_ms"] == pytest.approx(1.0)
    tel.observe_batches([(now, 8, None, [0.5] * 100, None)
                         for _ in range(50)])
    snap = tel.snapshot()
    assert snap["completed"] == 10_000
    # with first-5000 truncation this would still read 1.0ms
    assert snap["latency_p99_ms"] > 100.0


# ----------------------------------------------------------------------
# drift capture
# ----------------------------------------------------------------------
def test_drift_log_persists_and_reloads(tmp_path):
    path = str(tmp_path / "drift.jsonl")
    log = DriftLog(path)
    log.record("trial", "sigA", [[8, 128]], "xla", 1e-5, 2e-4, label="vf1")
    log.record("trial", "sigA", [[8, 128]], "xla", 2e-5, 1e-4, label="vf2")
    log.flush()
    rows = DriftLog(path).rows()               # fresh handle, from disk
    assert [r.attrs["label"] for r in rows] == ["vf1", "vf2"]
    assert rows[0].modeled_s == 1e-5 and rows[0].measured_s == 2e-4


def test_drift_report_reproduces_misordering(tmp_path):
    # the model ranks candidates one way, the hardware the other —
    # exactly the bench_parallel misordering; spearman must go negative
    log = DriftLog(str(tmp_path / "d.jsonl"))
    modeled = [1.0, 2.0, 3.0, 4.0]
    measured = [4.0, 3.0, 2.0, 1.0]
    for m, s in zip(modeled, measured):
        log.record("vf_sweep", "sig", [[96, 256]], "pallas", m * 1e-5,
                   s * 1e-5)
    log.flush()
    rep = drift_report(DriftLog(log.path))
    assert rep["n"] == 4
    assert rep["spearman"] == pytest.approx(-1.0)
    assert rep["groups"]["sig"]["spearman"] == pytest.approx(-1.0)
    assert os.path.exists(log.path)


def test_spearman_ties_and_degenerate():
    assert spearman([1, 2, 3], [1, 2, 3]) == pytest.approx(1.0)
    assert np.isnan(spearman([1.0], [2.0]))
    assert np.isnan(spearman([1, 1, 1], [1, 2, 3]))
    # partial ties average ranks instead of breaking arbitrarily
    assert spearman([1, 2, 2, 3], [1, 2, 2, 3]) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        spearman([1, 2], [1, 2, 3])


def _row(modeled, measured, kind="launch", sig="sig", attrs=None):
    from repro.obs.drift import DriftRow
    return DriftRow(kind, sig, [[8, 128]], "xla", modeled, measured, attrs)


def test_drift_report_skips_and_counts_sick_rows():
    # NaN/inf/nonpositive on either side must be dropped AND counted —
    # not poison every statistic, not vanish silently
    clean = [_row(1e-5, 2e-5), _row(2e-5, 3e-5), _row(3e-5, 5e-5)]
    sick = [_row(float("nan"), 1e-5), _row(1e-5, float("inf")),
            _row(0.0, 1e-5), _row(1e-5, -1e-5)]
    rep = drift_report(clean + sick)
    assert rep["n"] == 3 and rep["skipped"] == 4
    assert rep["spearman"] == pytest.approx(1.0)
    assert np.isfinite(rep["bias"]) and np.isfinite(rep["log10_spread"])


def test_drift_report_all_sick_rows():
    rep = drift_report([_row(float("nan"), 1e-5), _row(1e-5, 0.0)])
    assert rep["n"] == 0 and rep["skipped"] == 2
    assert np.isnan(rep["spearman"]) and np.isnan(rep["bias"])
    assert rep["groups"] == {} and rep["by_kind"] == {}


def test_drift_report_all_tied_and_single_row():
    # all-tied modeled: rank correlation is undefined (nan), but the
    # bias is still a perfectly good constant to report
    tied = drift_report([_row(1e-5, 1e-4), _row(1e-5, 2e-4),
                         _row(1e-5, 3e-4)])
    assert np.isnan(tied["spearman"])
    assert tied["bias"] == pytest.approx(20.0)
    single = drift_report([_row(1e-5, 2e-5)])
    assert single["n"] == 1 and np.isnan(single["spearman"])
    assert single["bias"] == pytest.approx(2.0)


def test_drift_report_with_spec_rescoring():
    # rows carrying features are re-scored under the given spec; rows
    # without features are counted, not guessed at
    class Spec:
        clock_hz, hbm_bw, step_overhead_s = 1e9, 1e9, 1e-3

    feats = {"groups": [{"grid": 2, "bytes_step": 10.0,
                         "steps": {"point": 100.0}}]}
    with_f = [_row(1e-5, 2.1e-3, attrs={"features": dict(feats)}),
              _row(2e-5, 2.0e-3, attrs={"features": dict(feats)})]
    without = [_row(3e-5, 4e-5)]
    rep = drift_report(with_f + without, spec=Spec())
    ws = rep["with_spec"]
    assert ws["n"] == 2 and ws["without_features"] == 1
    # predicted 2*(1ms + 100ns) for both rows: bias ~1, spearman nan
    assert ws["bias"] == pytest.approx(1.0, rel=0.1)
    assert np.isnan(ws["spearman"])
    # without spec= the key is absent entirely
    assert "with_spec" not in drift_report(with_f)


def test_drift_row_features_roundtrip_disk(tmp_path):
    # features ride attrs through the JSONL file bit-for-bit, and the
    # accessor is None (not a crash) for rows that predate them
    from repro.obs.drift import DriftRow, predict_features
    log = DriftLog(str(tmp_path / "f.jsonl"))
    feats = {"groups": [{"grid": 4, "bytes_step": 1000.0,
                         "steps": {"stencil": 2000.0}}], "items": 2}
    log.record("launch", "sig", [[8, 128]], "xla", 1e-5, 2e-5,
               features=feats)
    log.record("launch", "sig", [[8, 128]], "xla", 1e-5, 2e-5)
    log.flush()
    rows = DriftLog(log.path).rows()
    assert rows[0].features == feats
    assert rows[1].features is None
    class Spec:
        clock_hz, hbm_bw, step_overhead_s = 1e9, 1e9, 1e-6
    # items multiplies through the reconstituted prediction
    assert predict_features(rows[0].features, Spec()) == pytest.approx(
        2 * 4 * (1e-6 + 2e-6), rel=1e-12)
    # malformed features (wrong type) read back as None, not a crash
    assert DriftRow("launch", "s", None, "xla", 1e-5, 2e-5,
                    {"features": "oops"}).features is None


def test_resolve_drift_semantics(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_DRIFT_LOG", raising=False)
    assert resolve_drift(None) is None         # off by default
    assert resolve_drift(False) is None
    path = str(tmp_path / "d.jsonl")
    monkeypatch.setenv("REPRO_DRIFT_LOG", path)
    log = resolve_drift(None)                  # env switches it on
    assert isinstance(log, DriftLog) and log.path == path
    assert resolve_drift(path).path == path
    with pytest.raises(TypeError):
        resolve_drift(3.14)


# ----------------------------------------------------------------------
# engine + compile integration
# ----------------------------------------------------------------------
def _pointwise():
    from repro.core import DataflowGraph
    g = DataflowGraph("obs_pw")
    x = g.input("x", (8, 128))
    g.output(g.point(x, lambda v: v * 2.0, name="dbl"), "y")
    return g


def test_engine_trace_single_id_contiguous_phases(tmp_path):
    from repro.runtime import StreamEngine
    tr = Tracer()
    with StreamEngine(backend="xla", max_batch=4, trace=tr) as eng:
        h = eng.submit(_pointwise(), {"x": np.ones((8, 128), np.float32)})
        np.asarray(h.result(timeout=60)["y"])
    aids = {e.aid for e in tr.events() if e.cat == "request"
            if e.aid is not None}
    assert len(aids) == 1                      # one request, one trace id
    aid = aids.pop()
    phases = [e for e in tr.events()
              if e.cat == "request" and e.aid == aid and e.ph == "b"
              and e.name != "request"]
    phases.sort(key=lambda e: e.ts)
    assert [e.name for e in phases] == ["queue_wait", "form", "stack",
                                       "launch", "inflight", "wait",
                                       "copy"]
    # phase spans tile submit→complete with no gaps: each 'b' at the
    # previous phase's 'e'
    evs = [e for e in tr.events() if e.cat == "request" and e.aid == aid]
    b_ts = {e.name: e.ts for e in evs if e.ph == "b"}
    e_ts = {e.name: e.ts for e in evs if e.ph == "e"}
    chain = ["queue_wait", "form", "stack", "launch", "inflight", "wait",
             "copy"]
    assert b_ts["queue_wait"] == pytest.approx(b_ts["request"], abs=1e-9)
    for prev, nxt in zip(chain, chain[1:]):
        assert e_ts[prev] == pytest.approx(b_ts[nxt], abs=1e-9)
    assert e_ts["copy"] == pytest.approx(e_ts["request"], abs=1e-9)
    # the batcher's stack/launch X spans rode the same tracer
    assert {e.name for e in tr.events() if e.cat == "batch"} == {
        "batch.stack", "batch.launch"}
    validate_chrome_trace(export_chrome_trace(tr, str(tmp_path / "e.json")))


def test_engine_drift_rows_compile_then_launch(tmp_path):
    from repro.runtime import StreamEngine
    path = str(tmp_path / "drift.jsonl")
    with StreamEngine(backend="xla", max_batch=2, drift=path) as eng:
        g = _pointwise()
        for i in range(3):
            eng.submit(g, {"x": np.full((8, 128), i, np.float32)}
                       ).result(timeout=60)
    rows = DriftLog(path).rows()
    assert len(rows) >= 3
    kinds = [r.kind for r in rows]
    assert kinds[0] == "compile"               # first launch includes jit
    assert "launch" in kinds[1:]
    rep = drift_report(DriftLog(path))
    assert rep["n"] == len(rows) and rep["bias"] > 0


def test_compile_trace_spans():
    from repro.core import compile_graph
    tr = Tracer()
    compile_graph(_pointwise(), backend="xla", trace=tr)
    names = {e.name for e in tr.events() if e.ph == "B"}
    assert {"compile", "compile.lower", "compile.host",
            "compile.partition", "compile.pass.auto-split",
            "compile.pass.dead-channel", "compile.pass.point-fusion",
            "compile.vectorize.sweep"} <= names


def test_untraced_engine_has_no_recorder_state():
    from repro.runtime import StreamEngine
    with StreamEngine(backend="xla", max_batch=2) as eng:
        assert eng.tracer is None and eng.drift is None
        assert eng._batcher.tracer is None
