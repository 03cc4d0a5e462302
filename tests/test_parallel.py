"""Hardware parallelism end-to-end: vector factor + replication.

Covers the vectorization knob (tile minor-dim widening through the
cost-model sweep), spatial replication (shard_map row partitioning
with halo exchange), the batch-parallel serving farm, and the
correctness fixes in the tile/sim/batching hot paths.

Bit-exactness note: the replication/vectorization equivalence tests
use apps whose stencil taps are powers of two (``filter_chain``,
``gaussian_blur``), so every product is exact and no backend's FMA
contraction can change a single bit — the same convention as
tests/test_compiler.py.
"""
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (DataflowGraph, TaskTiming, analytic_latency,
                        build_schedule, choose_tile, compile_graph,
                        simulate_pipeline, sweep_vector_factor)
from repro.core.apps import build_app
from repro.core.graph import GraphError
from repro.parallel.replicate import (graph_input_halo, replicate_app)
from repro.runtime import MicroBatcher

H, W = 96, 256


def _single_group(name="gaussian_blur", h=H, w=W):
    sched = build_schedule(build_app(name, h, w))
    assert len(sched.groups) == 1
    return sched.groups[0]


# ----------------------------------------------------------------------
# choose_tile clamping (satellite bugfix)
# ----------------------------------------------------------------------
def test_choose_tile_exact_minor_dim():
    g = _single_group()
    th, tw = choose_tile(g, vector_factor=2)
    assert tw == 2 * 128
    assert th % 8 == 0


def test_choose_tile_rejects_factor_beyond_plane():
    """The old code silently returned a tile wider than the plane."""
    g = _single_group(h=96, w=256)          # lane-rounded width: 256
    with pytest.raises(ValueError, match="widest feasible"):
        choose_tile(g, vector_factor=3)     # 384 lanes > 256


def test_choose_tile_rejects_factor_beyond_max_tile():
    g = _single_group(h=96, w=4096)
    with pytest.raises(ValueError, match="max_tile"):
        choose_tile(g, vector_factor=4, max_tile=(256, 256))


def test_choose_tile_never_exceeds_max_tile():
    g = _single_group(h=2048, w=4096)
    th, tw = choose_tile(g, vector_factor=2, max_tile=(64, 512))
    assert th <= 64 and tw == 256


# ----------------------------------------------------------------------
# cost-model sweep
# ----------------------------------------------------------------------
def test_sweep_feasibility_is_monotone():
    g = _single_group(h=96, w=640)
    records = sweep_vector_factor(g)
    feas = [r["feasible"] for r in records]
    # once infeasible, never feasible again (wider tiles only get worse)
    assert feas == sorted(feas, reverse=True)
    assert feas[0] is True and feas[-1] is False
    for r in records:
        if r["feasible"]:
            assert r["tile"][1] == 128 * r["vector_factor"]


def test_sweep_does_not_mutate_selected_tile():
    """The sweep only scores; a standalone sweep over a scheduled
    group must not replace the schedule's chosen tile."""
    sched = build_schedule(build_app("gaussian_blur", 96, 640))
    g = sched.groups[0]
    chosen = (g.tile, g.vector_factor)
    sweep_vector_factor(g)
    assert (g.tile, g.vector_factor) == chosen


def test_schedule_selects_tile_and_reports_it():
    sched = build_schedule(build_app("gaussian_blur", 96, 640))
    g = sched.groups[0]
    assert g.tile is not None and g.vector_factor is not None
    assert g.tile[1] == 128 * g.vector_factor
    # the sweep avoids padding waste: 640 = 5 * 128 divides exactly
    assert g.vector_factor == 5
    text = sched.describe()
    assert "[vectorize]" in text and "vector_factor=5" in text


def test_forced_vector_factor_in_diagnostics():
    sched = build_schedule(build_app("gaussian_blur", 96, 640),
                           vector_factor=2)
    assert sched.groups[0].tile[1] == 256
    assert any("forced vector_factor=2" in d for d in sched.diagnostics)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_vectorized_bit_exact_vs_default(backend, rng):
    """vector_factor>1 tiles change the schedule, never the bits."""
    x = rng.normal(size=(H, W)).astype(np.float32)
    base = compile_graph(build_app("gaussian_blur", H, W), backend=backend)
    vec = compile_graph(build_app("gaussian_blur", H, W), backend=backend,
                        vector_factor=2)
    assert vec.schedule.groups[0].tile[1] == 256
    np.testing.assert_array_equal(np.asarray(base(img=x)["out"]),
                                  np.asarray(vec(img=x)["out"]))


# ----------------------------------------------------------------------
# simulate_pipeline steady_rate (satellite bugfix)
# ----------------------------------------------------------------------
def test_steady_rate_equals_max_ii_exactly():
    """Constant-ii pipeline completes one item every max(ii) cycles in
    steady state; the old fencepost error under-reported it by
    ~ii/(n/2)."""
    for iis in ([1.0, 2.0, 1.0], [3.0, 1.0], [2.5]):
        tasks = [TaskTiming(f"t{i}", ii=v, fill=8.0)
                 for i, v in enumerate(iis)]
        sim = simulate_pipeline(tasks, 64, depth=2)
        assert sim["steady_rate"] == pytest.approx(max(iis), abs=1e-9)


def test_analytic_latency_zero_items():
    tasks = [TaskTiming("a", ii=1.0, fill=4.0)]
    r = analytic_latency(tasks, 0)
    assert r["sequential"] == r["dataflow"] == 4.0
    assert r["speedup"] == 1.0
    assert analytic_latency([TaskTiming("z", ii=1.0, fill=0.0)],
                            0)["speedup"] == 1.0  # 0/0 guarded
    with pytest.raises(ValueError):
        simulate_pipeline(tasks, 0)


# ----------------------------------------------------------------------
# MicroBatcher validation (satellite bugfix)
# ----------------------------------------------------------------------
class _Req:
    def __init__(self, inputs):
        self.inputs = inputs


def test_microbatcher_rejects_empty_batch(rng):
    app = compile_graph(build_app("square", 16, 128), backend="xla")
    mb = MicroBatcher(max_batch=4)
    with pytest.raises(ValueError, match="empty request batch"):
        mb.stack(app, [])
    with pytest.raises(ValueError, match="empty request batch"):
        mb.launch(app, [])


def test_microbatcher_stacks_scalar_channels(rng):
    """0-d channel inputs stack to a (B,) staging buffer."""
    g = DataflowGraph("scalar_mix")
    x = g.input("x", (16, 128))
    s = g.input("s", ())
    y = g.custom([x, s], lambda xv, sv: xv * sv, [(16, 128)],
                 name="scale")[0]
    g.output(y, "y")
    app = compile_graph(g, backend="xla")
    mb = MicroBatcher(max_batch=4)
    reqs = [_Req({"x": rng.normal(size=(16, 128)).astype(np.float32),
                  "s": np.float32(i + 1)}) for i in range(3)]
    args = mb.stack(app, reqs, pad_to=4)
    assert args[0].shape == (4, 16, 128) and args[1].shape == (4,)
    out = mb.launch(app, reqs, pad_to=4)["y"]
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            np.asarray(out[i]), r.inputs["x"] * r.inputs["s"])


def test_microbatcher_names_bad_shape(rng):
    app = compile_graph(build_app("square", 16, 128), backend="xla")
    mb = MicroBatcher(max_batch=4)
    good = _Req({"img": rng.normal(size=(16, 128)).astype(np.float32)})
    bad = _Req({"img": rng.normal(size=(16, 64)).astype(np.float32)})
    with pytest.raises(ValueError, match=r"request\[1\] input 'img'"):
        mb.stack(app, [good, bad])


def test_microbatcher_replicas_must_divide():
    with pytest.raises(ValueError, match="divide evenly"):
        MicroBatcher(max_batch=6, replicas=4)


# ----------------------------------------------------------------------
# replication: halo analysis + single-device fallback (bit-exact)
# ----------------------------------------------------------------------
def test_graph_input_halo_accumulates_across_groups():
    g = build_app("filter_chain", H, W)      # three 3x3 stencils
    halos = graph_input_halo(g)
    assert list(halos.values()) == [(3, 3)]


def test_replicate_rejects_mixed_shapes():
    g = DataflowGraph("mixed")
    x = g.input("x", (32, 128))
    g.output(g.reduce(x, lambda v: v.sum(), out_shape=()), "total")
    with pytest.raises(GraphError, match="2-D plane"):
        replicate_app(g, 1, backend="xla")


def test_replicate_rejects_opaque_stages():
    """custom/reduce stages could read across the row cut; no halo
    provision or masking makes that correct, so reject loudly."""
    g = DataflowGraph("opaque")
    x = g.input("x", (32, 128))
    y = g.custom([x], lambda v: v + 1.0, [(32, 128)], name="addone")[0]
    g.output(g.stencil(y, (3, 3), lambda p: p.mean(0)), "out")
    with pytest.raises(GraphError, match="opaque"):
        replicate_app(g, 1, backend="xla")


def test_replicate_rejects_nondividing_height():
    with pytest.raises(GraphError, match="divide"):
        replicate_app(build_app("square", 30, 128), 4, backend="xla")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("name", ["filter_chain", "gaussian_blur"])
def test_replicated_single_device_bit_exact(backend, name, rng):
    """1 replica == the CI fallback: same shard_map + halo-exchange
    code path, must reproduce the plain app bit-for-bit."""
    app = compile_graph(build_app(name, H, W), backend=backend)
    rep = replicate_app(app)
    assert rep.n_replicas == 1 and rep.halo_rows > 0
    x = rng.normal(size=(H, W)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(app(img=x)["out"]),
                                  np.asarray(rep(img=x)["out"]))


def test_replicated_app_launch_and_describe(rng):
    rep = replicate_app(build_app("filter_chain", H, W), backend="xla")
    x = rng.normal(size=(H, W)).astype(np.float32)
    h = rep.launch(img=x)
    out = h.result()["out"]
    assert out.shape == (H, W)
    text = rep.describe()
    assert "1 replicas" in text and "halo rows" in text


# ----------------------------------------------------------------------
# replication: true multi-device (subprocess, forced host devices)
# ----------------------------------------------------------------------
PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.core import compile_graph
from repro.core.apps import build_app
"""


def run_sub(code: str, timeout: int = 560):
    r = subprocess.run([sys.executable, "-c", PREAMBLE + code],
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_replicated_multi_device_bit_exact():
    run_sub("""
from repro.parallel.replicate import replicate_app
for backend in ("xla", "pallas"):
    app = compile_graph(build_app("filter_chain", 96, 256), backend=backend)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 256)).astype(np.float32)
    ref = np.asarray(app(img=x)["out"])
    for k in (2, 4):
        rep = replicate_app(app, k)
        assert rep.n_replicas == k
        out = np.asarray(rep(img=x)["out"])
        assert np.array_equal(out, ref), (backend, k,
                                          float(np.abs(out - ref).max()))
print("ok")
""")


def test_engine_replicas_multi_device_bit_exact():
    run_sub("""
from repro.runtime import StreamEngine
g = build_app("filter_chain", 32, 128)
app = compile_graph(build_app("filter_chain", 32, 128), backend="xla")
rng = np.random.default_rng(0)
xs = [rng.normal(size=(32, 128)).astype(np.float32) for _ in range(12)]
ref = [np.asarray(app(img=x)["out"]) for x in xs]
with StreamEngine(backend="xla", max_batch=8, replicas=4) as eng:
    handles = [eng.submit(g, {"img": x}) for x in xs]
    outs = [h.result()["out"] for h in handles]
    rep = eng.report()
assert all(np.array_equal(a, b) for a, b in zip(outs, ref))
m = rep["measured"]
assert m["replicas"] == 4
assert m["throughput_per_replica_rps"] * 4 == m["throughput_rps"]
mod = next(iter(rep["modeled"].values()))
assert mod["replica_scaling_modeled"] > 1.0
print("ok")
""")


@pytest.mark.parametrize("replicas", [4, 1])
def test_engine_readback_hands_each_request_its_shard(replicas):
    """Each request's result is a row of its own device's host copy:
    with 4 replicas a 2-row shard of the 8-wide batch, never a
    batch-wide array; with 1 the single shard is the whole batch."""
    run_sub(f"""
import gc
from repro.runtime import StreamEngine
replicas = {replicas}
g = build_app("filter_chain", 32, 128)
app = compile_graph(build_app("filter_chain", 32, 128), backend="xla")
rng = np.random.default_rng(1)
xs = [rng.normal(size=(32, 128)).astype(np.float32) for _ in range(13)]
ref = [np.asarray(app(img=x)["out"]) for x in xs]
eng = StreamEngine(backend="xla", max_batch=8, replicas=replicas)
got = []
for order in (list(range(8)), list(rng.permutation(13))):
    # queued whole under the engine's re-entrant condition: 8 frames
    # form one batch of 8, 13 form batches of 8 and 5 (width 8)
    with eng._cond:
        reqs = [(i, eng.submit(g, {{"img": xs[i]}})) for i in order]
    got += [(i, r.result(timeout=300)["out"]) for i, r in reqs]
rep = eng.report()
eng.close()
del eng
gc.collect()
assert rep["buckets"] == {{8: 3}}, rep["buckets"]
assert rep["readback_shards"] == {{replicas: 3}}, rep["readback_shards"]
for i, out in got:
    assert np.array_equal(out, ref[i]), i
    rows = None if out.base is None else out.base.shape[0]
    assert rows == 8 // replicas, (i, rows)
print("ok")
""")
