"""The readers of the engine's readback split (``device_wait_ms``,
``d2h_ms``), on synthetic runs and on an engine that served frames on
the CPU."""
import json

import numpy as np
import pytest

from harness import cells, spec

READERS = {"device_wait_ms": "wait", "d2h_ms": "copy"}


def _run(engine_report):
    return cells.Run(cell=None, window=None, setup_s=1.0,
                     compile_graph_s=0.1, engine_report=engine_report,
                     trace=None, min_frame_s=1e-4)


def _phases(**mean_ms):
    return {"measured": {"phases": {p: {"mean_ms": v, "count": 3}
                                    for p, v in mean_ms.items()}}}


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("suffix", ["cameras", "x4"])
def test_reader_is_the_mean_of_its_phase(metric, suffix):
    read = spec.metric_reader(f"{metric}.{suffix}")
    assert read is spec.metric_reader(metric)
    rep = _phases(wait=9.0, copy=2.5, readback=11.5)
    want = {"wait": 9.0, "copy": 2.5}[READERS[metric]]
    assert read(_run(rep)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_none_without_its_phase_or_an_engine(metric):
    read = spec.metric_reader(metric)
    # a program from before the split reports readback alone
    assert read(_run(_phases(stack=1.0, launch=1.0, readback=11.5))) is None
    # the device entry has no engine
    assert read(_run(None)) is None


def test_wait_and_copy_add_up_to_readback_on_a_served_engine():
    from repro.core import DataflowGraph, compile_graph
    from repro.runtime import StreamEngine
    g = DataflowGraph("readers_dbl")
    x = g.input("x", (8, 128))
    g.output(g.point(x, lambda v: v * 2.0, name="dbl"), "y")
    app = compile_graph(g, backend="xla")
    with StreamEngine(backend="xla", max_batch=4) as eng:
        for i in range(6):
            eng.submit(app, {"x": np.full((8, 128), i, np.float32)}
                       ).result(timeout=60)
        rep = eng.report()
    run = _run(rep)
    wait = spec.metric_reader("device_wait_ms.cameras")(run)
    copy = spec.metric_reader("d2h_ms.cameras")(run)
    back = spec.metric_reader("readback_ms.cameras")(run)
    assert wait >= 0 and copy >= 0
    assert wait + copy == pytest.approx(back, rel=1e-6)


def test_the_new_entries_read_the_readback_layer():
    with open(spec.BENCHMARK_JSON) as f:
        bench = json.load(f)
    got = {m["name"]: m for m in bench["per_layer"]
           if m["name"].split(".")[0] in READERS}
    assert sorted(got) == ["d2h_ms.cameras", "d2h_ms.x4",
                           "device_wait_ms.cameras", "device_wait_ms.x4"]
    for name, m in got.items():
        assert m["layer"] == "readback" and m["source"] == "program_span"
        cell = "lk1080.cameras" if name.endswith("cameras") else "blur4k.x4"
        assert m["workloads"] == [cell]
        assert m["moves"] == ("latency_p95_ms" if cell == "lk1080.cameras"
                              else "fps")
