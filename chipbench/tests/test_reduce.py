"""The trace-to-metrics reduction on a small recorded trace.

``fixtures/blur1080.device.trace.json`` holds the extracted events of a
10 ms traced window of ``blur1080.device`` on one TPU v5 lite: 27 calls
of the app, each a pad, the Mosaic kernel and a crop.  The expectations
below are worked out from the raw events independently of the
reduction's own bookkeeping.
"""
import json
import os

import pytest

from harness import profile, spec

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "blur1080.device.trace.json")


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        return json.load(f)


def _window(events):
    w = next(e for e in events if e["name"] == "harness.window")
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def test_busy_is_the_union_of_device_ops_in_the_window(events):
    w0, w1 = _window(events)
    ops = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in events if e["line"] == "XLA Ops")
    assert all(w0 <= a and b <= w1 for a, b in ops)
    busy, end = 0.0, -1.0
    for a, b in ops:                  # pad, kernel, crop never overlap
        assert a >= end
        busy += b - a
        end = b
    r = profile.reduce(events, [0])
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]


def test_kernel_and_other_time_per_call(events):
    r = profile.reduce(events, [0])
    assert r["calls"] == 27
    kernels = [e["dur_ns"] for e in events if e["line"] == "XLA Ops"
               and "tpu_custom_call" in e["name"]]
    others = [e["dur_ns"] for e in events if e["line"] == "XLA Ops"
              and "tpu_custom_call" not in e["name"]]
    assert len(kernels) == 27 and len(others) == 54
    assert r["kernel_s"] == pytest.approx(sum(kernels) * 1e-9)
    assert r["other_s"] == pytest.approx(sum(others) * 1e-9)
    # the kernel is most of a call: about 127 of 154 us
    assert 100e-6 < r["kernel_s"] / r["calls"] < 160e-6


def test_breakdown_names_ops_and_labels_gaps(events):
    r = profile.reduce(events, [0])
    ops = r["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["step.1 custom-call", "pad.0 pad",
                                   "slice.0 slice"]
    assert sum(s for _, s in ops) == pytest.approx(r["busy_s"])
    gaps = r["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert all(label.startswith("harness.") for label, _ in gaps)


def test_metric_readers_on_the_reduced_trace(events):
    from harness import cells, roofline
    r = profile.reduce(events, [0])
    ref = spec.reference("gaussian_blur")
    min_s = roofline.min_seconds(ref, 1080, 1920,
                                 roofline.peaks("TPU v5 lite"))
    run = cells.Run(cell=None, window=None, setup_s=1.0,
                    compile_graph_s=0.1, engine_report=None, trace=r,
                    min_frame_s=min_s)
    kernel = spec.metric_reader("kernel_ms")(run)
    wrapper = spec.metric_reader("wrapper_ms")(run)
    share = spec.metric_reader("app_roofline")(run)
    idle = spec.metric_reader("device_idle_pct")(run)
    assert kernel == pytest.approx(r["kernel_s"] / 27 * 1e3)
    assert wrapper == pytest.approx(r["other_s"] / 27 * 1e3)
    assert share == pytest.approx(min_s / ((kernel + wrapper) / 1e3) * 100)
    assert 0 < share < 100
    assert idle == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))


def test_a_trace_without_the_window_or_the_device_is_refused(events):
    with pytest.raises(ValueError):
        profile.reduce([e for e in events
                        if e["name"] != "harness.window"], [0])
    with pytest.raises(ValueError):
        profile.reduce(events, [3])
