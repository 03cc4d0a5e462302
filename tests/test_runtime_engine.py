"""Streaming serving runtime: engine, cache, micro-batcher, telemetry.

The acceptance path: >=32 concurrent requests against a compiled
diamond graph are bit-exact vs ``reference_eval``, with the compile
cache reporting exactly 1 miss + N-1 hits for same-signature traffic.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CycleError, DataflowGraph, compile_graph
from repro.core.apps import JACOBI3, LAPLACE3, _conv
from repro.runtime import (CompileCache, MicroBatcher, QueueFullError,
                           SlotPool, StreamEngine, Telemetry, modeled_latency)


def _diamond(h=48, w=256, name="diamond"):
    g = DataflowGraph(name)
    x = g.input("x", (h, w))
    s1 = g.stencil(x, (3, 3), _conv(LAPLACE3), name="lap")
    s2 = g.stencil(x, (3, 3), _conv(JACOBI3), name="jac")
    g.output(g.point2(s1, s2, lambda u, v: u - v, name="merge"), "y")
    return g


# ----------------------------------------------------------------------
# acceptance: the full engine path on the pallas backend
# ----------------------------------------------------------------------
def test_engine_e2e_32_requests_bit_exact_and_cached(rng):
    n = 32
    g = _diamond()
    frames = [rng.normal(size=(48, 256)).astype(np.float32)
              for _ in range(n)]
    with StreamEngine(backend="pallas", max_batch=8, max_queue=64) as eng:
        handles = []
        lock = threading.Lock()

        def submit(chunk):
            for f in chunk:
                h = eng.submit(g, {"x": f})
                with lock:
                    handles.append((f, h))

        threads = [threading.Thread(target=submit, args=(frames[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = [(f, h.result(timeout=600)) for f, h in handles]
        report = eng.report()

    # bit-exact against the reference oracle (atol=0)
    ref_graph = eng.cache.get(g, backend="pallas").schedule.graph
    for f, r in results:
        ref = np.asarray(ref_graph.reference_eval({"x": f})["y"])
        np.testing.assert_array_equal(r["y"], ref)

    # same-signature traffic: exactly 1 compile event for N requests.
    # hits/misses are per COMPILE, not per request (resubmitting the
    # same graph object is a `requests` tick, not a phantom hit)
    assert report["cache"]["misses"] == 1
    assert report["cache"]["hits"] == 0
    assert report["cache"]["requests"] == n

    m = report["measured"]
    assert m["completed"] == n and m["submitted"] == n
    assert m["latency_p50_ms"] <= m["latency_p99_ms"]
    # the Fig. 1 model rides along with the live metrics
    mod = report["modeled"]["diamond"]
    assert mod["sequential"] > mod["dataflow"] > 0


# ----------------------------------------------------------------------
# compile cache
# ----------------------------------------------------------------------
def test_cache_structural_hit_across_fresh_graphs():
    """Two structurally identical graphs (different names) share one
    compile; a different topology misses."""
    cache = CompileCache()
    a1 = cache.get(_diamond(8, 128, name="g1"), backend="xla")
    a2 = cache.get(_diamond(8, 128, name="g2"), backend="xla")
    assert a1 is a2
    assert cache.stats.misses == 1 and cache.stats.hits == 1
    g3 = _diamond(16, 128, name="g3")        # different shape
    a3 = cache.get(g3, backend="xla")
    assert a3 is not a1 and cache.stats.misses == 2
    # backend is part of the identity
    cache.get(_diamond(8, 128), backend="xla_staged")
    assert cache.stats.misses == 3


def test_cache_alias_survives_in_place_canonicalization():
    """Passes rewrite graphs in place (auto-split inserts a stage), so
    the same OBJECT resubmitted after compiling must still hit."""
    cache = CompileCache()
    g = _diamond(8, 128)
    pre = g.signature()
    cache.get(g, backend="xla")
    assert g.signature() != pre              # canonicalized in place
    cache.get(g, backend="xla")              # same object: no new event
    assert cache.stats.misses == 1 and cache.stats.hits == 0
    assert cache.stats.requests == 2
    # and a fresh non-canonical twin hits through the structural key
    cache.get(_diamond(8, 128), backend="xla")
    assert cache.stats.misses == 1 and cache.stats.hits == 1


def test_cache_lru_eviction():
    cache = CompileCache(maxsize=2)
    cache.get(_diamond(8, 128), backend="xla")
    cache.get(_diamond(16, 128), backend="xla")
    cache.get(_diamond(24, 128), backend="xla")
    assert cache.stats.evictions > 0
    # maxsize bounds the entry table
    assert len(cache) <= 2


def test_signature_ignores_labels_but_not_bodies():
    s1 = _diamond(8, 128, name="a").signature()
    s2 = _diamond(8, 128, name="b").signature()
    assert s1 == s2
    g = _diamond(8, 128)
    g.stages[-1].fn = lambda u, v: u + v     # different merge body
    assert g.signature() != s1


def test_signature_sees_globals_defaults_and_io_names():
    """Stage bodies differing only in the global they call or a default
    value must not collide (they compute different things); graph I/O
    names are the app's calling convention so they count too."""
    def build(fn, inn="x", outn="y"):
        g = DataflowGraph("g")
        x = g.input(inn, (8, 128))
        g.output(g.point(x, fn), outn)
        return g

    assert build(lambda v: jnp.abs(v)).signature() \
        != build(lambda v: jnp.exp(v)).signature()
    assert build(lambda v, k=2.0: v * k).signature() \
        != build(lambda v, k=3.0: v * k).signature()
    assert build(jnp.abs).signature() == build(jnp.abs).signature()
    assert build(jnp.abs).signature() \
        != build(jnp.abs, inn="img", outn="z").signature()


# ----------------------------------------------------------------------
# backpressure (the simulator's finite FIFO, live)
# ----------------------------------------------------------------------
def test_bounded_queue_backpressure(rng):
    g = _diamond(8, 128)
    x = rng.normal(size=(8, 128)).astype(np.float32)
    eng = StreamEngine(backend="xla", max_queue=2, max_batch=2,
                       autostart=False)
    try:
        eng.submit(g, {"x": x}, block=False)
        eng.submit(g, {"x": x}, block=False)
        with pytest.raises(QueueFullError):
            eng.submit(g, {"x": x}, block=False)
        # draining the queue releases the backpressure
        eng.start()
        h = eng.submit(g, {"x": x}, timeout=60)
        assert h.result(timeout=60)["y"].shape == (8, 128)
    finally:
        eng.close()


def test_engine_rejects_after_close(rng):
    eng = StreamEngine(backend="xla", autostart=False)
    eng.close()
    with pytest.raises(RuntimeError):
        eng.submit(_diamond(8, 128), {"x": np.zeros((8, 128), np.float32)})


def test_engine_rejects_bad_input_at_submit(rng):
    """A malformed request fails its own submit instead of poisoning
    the micro-batch it would have joined."""
    g = _diamond(8, 128)
    with StreamEngine(backend="xla", max_batch=2) as eng:
        ok = eng.submit(g, {"x": rng.normal(size=(8, 128))
                            .astype(np.float32)})
        with pytest.raises(ValueError, match="expected shape"):
            eng.submit(g, {"x": np.zeros((4, 4), np.float32)})
        with pytest.raises(ValueError, match="missing graph input"):
            eng.submit(g, {"img": np.zeros((8, 128), np.float32)})
        assert ok.result(timeout=120)["y"].shape == (8, 128)


# ----------------------------------------------------------------------
# async launch handles and the micro-batcher
# ----------------------------------------------------------------------
def test_compiled_app_async_launch(rng):
    app = compile_graph(_diamond(8, 128), backend="xla")
    x = rng.normal(size=(8, 128)).astype(np.float32)
    h = app.launch(x=x)
    out = h.result()
    assert h.done()
    np.testing.assert_array_equal(np.asarray(out["y"]),
                                  np.asarray(app(x=x)["y"]))


def test_micro_batcher_pad_and_slice_bit_exact(rng):
    app = compile_graph(_diamond(8, 128), backend="xla")
    mb = MicroBatcher(max_batch=8)

    class R:
        def __init__(self, x):
            self.inputs = {"x": x}

    reqs = [R(rng.normal(size=(8, 128)).astype(np.float32))
            for _ in range(5)]
    outs = mb.launch(app, reqs, pad_to=8)    # ragged batch, padded
    y = np.asarray(outs["y"])
    assert y.shape == (8, 8, 128)            # padded width
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            y[i], np.asarray(app(x=r.inputs["x"])["y"]))
    with pytest.raises(ValueError):
        mb.launch(app, [R(np.zeros((8, 128), np.float32))] * 9)


# ----------------------------------------------------------------------
# shared slot machinery
# ----------------------------------------------------------------------
def test_slot_pool_fifo_admission_and_retirement():
    pool = SlotPool(2)
    for item in "abcd":
        pool.submit(item)
    assert [i for _, i in pool.admit()] == ["a", "b"]
    assert pool.active == 2 and not pool.free_slots()
    oldest = pool.oldest()
    assert pool.retire(oldest) == "a"
    assert pool.admit() == [(oldest, "c")]
    # retirement follows admission order, not slot index order
    assert pool.slots[pool.oldest()] == "b"
    retired = [pool.retire(pool.oldest()), pool.retire(pool.oldest())]
    assert retired == ["b", "c"]
    # the pool hands items back and keeps none of them
    assert not hasattr(pool, "finished")
    with pytest.raises(ValueError):
        pool.retire(0)                       # empty slot
    assert pool.busy                         # "d" still queued


def test_telemetry_report_shapes():
    t = Telemetry()
    t.observe_submit(0)
    t.observe_batch(4)
    for ms in (1.0, 2.0, 3.0):
        t.observe_completion(ms * 1e-3)
    snap = t.snapshot()
    assert snap["completed"] == 3
    assert snap["latency_p50_ms"] == pytest.approx(2.0)
    assert snap["latency_p50_ms"] <= snap["latency_p99_ms"]
    app = compile_graph(_diamond(8, 128), backend="xla")
    rep = t.report(modeled={"diamond": modeled_latency(app, 16)})
    assert set(rep) == {"measured", "modeled"}
    mod = rep["modeled"]["diamond"]
    assert mod["speedup"] > 1.0 and "dataflow_sim" in mod
