"""The harness's pure parts: spec, schedules, statistics, bytes, peaks,
frames and the references."""
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from harness import drive, roofline, spec, stats
from harness.frames import frame_sets

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(spec.BENCHMARK_JSON) as f:
        return json.load(f)


def test_every_cell_loads_and_every_metric_has_a_reader():
    bench = _bench()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["chips"] == cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"fps", "setup_s"}
        assert cell.per_layer, w["name"]
        moved = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in moved, (w["name"], m["name"])
        spec.reference(cell.config["app"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert callable(spec.metric_reader(m["name"]))


def test_reduced_and_names_follow_the_contract():
    bench = _bench()
    files = set()
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        path = os.path.join(ROOT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        files.add(c["file"])
    assert len(files) == len(bench["configs"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_mix_names_a_loop_and_an_entry_found_by_name():
    for path in os.listdir(os.path.join(spec.BENCH_DIR, "traffic")):
        with open(os.path.join(spec.BENCH_DIR, "traffic", path)) as f:
            mix = json.load(f)
        assert callable(spec.loop(mix["loop"]).run), path
        entry = spec.entry(mix["entry"])
        assert callable(entry.start) and callable(entry.programs), path
    with pytest.raises(FileNotFoundError):
        spec.loop("no_such_loop")


def test_a_suffixed_metric_falls_back_to_the_reader_of_its_name():
    assert spec.metric_reader("stage_ms.x4") is spec.metric_reader("stage_ms")
    assert spec.metric_reader("readback_ms.cameras") is \
        spec.metric_reader("readback_ms")
    assert spec.metric_reader("fps") is not spec.metric_reader("setup_s")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric.cameras")


def test_the_compile_cache_is_in_the_checkout_though_jax_came_first():
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` once, when it is imported;
    the harness imports it before the start, which still has to point
    the cache into the checkout."""
    code = ("import sys; sys.path.insert(0, 'chipbench'); import jax; "
            "from harness import boot; boot.start(1, 'test'); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, "elsewhere"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == os.path.join(ROOT, ".jax_cache")


def test_camera_schedule_is_seeded_and_the_same_set_of_arrivals():
    schedule = spec.loop("open").camera_schedule
    a = schedule(4, 30, 2.0, np.random.default_rng(1))
    b = schedule(4, 30, 2.0, np.random.default_rng(1))
    c = schedule(4, 30, 2.0, np.random.default_rng(2 ** 40))
    assert a == b
    assert len(a) == 4 * 60
    assert [t for t, _ in a] == pytest.approx([t for t, _ in c])
    gaps = np.diff([t for t, _ in a])
    assert gaps == pytest.approx(np.full(len(gaps), 1 / 120))
    for cam in range(4):
        times = [t for t, k in a if k == cam]
        assert np.diff(times) == pytest.approx(np.full(59, 1 / 30))


def test_reservoir_keeps_k_and_is_seeded():
    def sample(seed):
        r = drive.Reservoir(5, np.random.default_rng(seed))
        for i in range(1000):
            r.offer(i, i)
        return sorted(r.kept)
    assert len(sample(3)) == 5 and sample(3) == sample(3)
    assert sample(3) != sample(4)


def test_percentile_over_all_frames_counts_missing_as_slowest():
    xs = [float(i) for i in range(1, 101)]
    assert stats.percentile(xs, 95) == 95.0
    assert stats.percentile(xs[:95] + [math.inf] * 5, 95) == 95.0
    assert stats.percentile(xs[:94] + [math.inf] * 6, 95) == math.inf
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0


def test_min_bytes_and_seconds_per_app():
    lk = spec.reference("optical_flow_lk")
    blur = spec.reference("gaussian_blur")
    assert roofline.min_bytes(lk, 1080, 1920) == 4 * 1080 * 1920 * 4
    assert roofline.min_bytes(blur, 2160, 3840) == 2 * 2160 * 3840 * 4
    peak = roofline.peaks("TPU v5 lite")
    t = roofline.min_seconds(lk, 1080, 1920, peak)
    assert t == pytest.approx(33177600 / 819e9)
    assert lk.min_ops(1080, 1920) / peak["flops_per_s"] < t / 10


def test_peaks_lookup_and_unknown_device_is_an_error():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")


def test_frames_are_seeded_by_every_bit_of_the_seed():
    names = ("f1", "f2")
    a = frame_sets(2 ** 40 + 1, 2, (16, 128), names)
    b = frame_sets(2 ** 40 + 1, 2, (16, 128), names)
    c = frame_sets(1, 2, (16, 128), names)
    assert all(np.array_equal(a[i][k], b[i][k]) for i in (0, 1) for k in names)
    assert not np.array_equal(a[0]["f1"], c[0]["f1"])
    x = np.asarray(a[0]["f1"])
    assert x.min() >= 0 and x.max() <= 255 and np.array_equal(x, np.round(x))
    assert not np.array_equal(a[0]["f1"], a[0]["f2"])    # the scene moved


@pytest.mark.parametrize("app", ["optical_flow_lk", "gaussian_blur"])
def test_reference_agrees_with_the_program_reference_eval(app):
    from repro.core.apps import build_app
    ref = spec.reference(app)
    g = build_app(app, 32, 256)
    for f in frame_sets(11, 2, (32, 256), ref.INPUTS):
        want = g.reference_eval(f)
        got = ref.reference(f)
        for k in ref.OUTPUTS:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=1e-5, atol=1e-5)


def test_cli_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "blur1080.device", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
