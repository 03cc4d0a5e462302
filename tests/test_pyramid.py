"""Multi-rate stages: REDUCE and EXPAND, and the ``pyramid_blend`` app.

- ``fe.pyr_down`` / ``fe.pyr_up`` alone and the whole app agree with
  the benchmark's plain reference (``chipbench/references/
  pyramid_blend.py``, loaded by path; it imports nothing of the
  system), on every seed backend, with Pallas in interpret mode;
- the backends agree with each other bit-exactly;
- each resampling stage is a kernel of its own whose only HBM output is
  its result at the new size, and the schedule's inter-group byte count
  matches a count by hand;
- bad shapes are a ``GraphError`` naming the user's line.
"""
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

import repro.frontend as fe
from repro.core.apps import build_app
from repro.core.compiler import compile_graph
from repro.core.graph import DataflowGraph, GraphError
from repro.core.schedule import build_schedule
from repro.frontend import lib

H, W = 48, 256
ROOT = pathlib.Path(__file__).resolve().parent.parent
BACKENDS = ("xla", "xla_staged", "pallas")


def _load_reference():
    path = ROOT / "chipbench" / "references" / "pyramid_blend.py"
    spec = importlib.util.spec_from_file_location("pyramid_blend_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
#: the cell's limit on the check's gap (largest difference over the
#: reference plane's mean magnitude).  The program is not bit-exact
#: with the reference here: XLA on the CPU contracts a multiply and the
#: add after it into one fused multiply-add wherever they share a
#: fusion, and the reference, run op by op, rounds each product.
GAP = json.loads((ROOT / "chipbench" / "configs" / "pyramid_blend-2048.json")
                 .read_text())["check"]["gap"]


def _gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).mean())


def _levels(rng, shape):
    """A seeded random plane of whole 8-bit levels, as cameras give."""
    return np.round(rng.uniform(0.0, 255.0, size=shape)).astype(np.float32)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("op", ["pyr_down", "pyr_up"])
def test_resample_matches_plain_reference(op, backend, rng):
    x = rng.normal(size=(H, W)).astype(np.float32)
    app = compile_graph(fe.trace(getattr(fe, op), (H, W), name=op),
                        backend=backend)
    got = np.asarray(app(x=x)["out"])
    want = np.asarray({"pyr_down": REF.reduce,
                       "pyr_up": REF.expand}[op](x))
    assert got.shape == want.shape == (
        (H // 2, W // 2) if op == "pyr_down" else (2 * H, 2 * W))
    assert _gap(got, want) <= GAP


def test_pyramid_blend_matches_plain_reference_on_every_backend(rng):
    names = REF.INPUTS
    inputs = {k: _levels(rng, (H, W)) for k in names}
    want = REF.reference(inputs)
    outs = {}
    for be in BACKENDS:
        app = compile_graph(build_app("pyramid_blend", H, W), backend=be)
        assert tuple(app.input_names) == REF.INPUTS
        assert tuple(app.output_names) == REF.OUTPUTS
        outs[be] = {k: np.asarray(v) for k, v in app(**inputs).items()}
        for k in REF.OUTPUTS:
            assert _gap(outs[be][k], want[k]) <= GAP, (be, k)
    for be in BACKENDS[1:]:                        # atol=0 among backends
        for k in REF.OUTPUTS:
            np.testing.assert_array_equal(outs[be][k], outs["xla"][k],
                                          err_msg=f"{be}/{k}")


def test_each_resampling_stage_is_its_own_kernel_and_writes_one_plane():
    sched = build_schedule(build_app("pyramid_blend", H, W),
                           backend="pallas")
    resample = [st for st in sched.graph.stages if st.kind == "resample"]
    assert len(resample) == 21 + 27           # REDUCE + EXPAND
    by_stage = {st: g for g in sched.groups for st in g.stages}
    for st in resample:
        g = by_stage[st]
        assert g.kind == "resample" and g.stages == [st]
        (src,), (dst,) = g.inputs, g.outputs
        assert g.inputs == st.inputs and g.outputs == st.outputs
        assert dst.shape in ((src.shape[0] // 2, src.shape[1] // 2),
                             (2 * src.shape[0], 2 * src.shape[1]))
    # no channel holds a prefiltered full-size or a zero-stuffed plane:
    # every channel a resampling stage writes is at the new size
    for ch in sched.graph.channels:
        if ch.producer is not None and ch.producer.kind == "resample":
            assert ch.shape != ch.producer.inputs[0].shape
    counts = sched.group_counts()
    assert counts["resample"] == 48
    assert counts["dataflow"] == 10            # 3 channels x 3 levels + 1
    assert counts["alias"] == 21               # fan-out of shared levels

    # by hand, in planes of each level (L0 48x256 .. L3 6x32, float32):
    # each plane counts its write and one read per group reading it.
    # Per channel and image: G1, G2 (written, read by REDUCE, EXPAND and
    # the level's blend: 4 each), G3 (written, read by EXPAND and the
    # level-3 group: 3), and EXPAND(G1..G3) (written, read once: 2);
    # per channel the collapse: S3, S2, S1 (2 each) and EXPAND(S3..S1)
    # (2 each); the mask: GM1, GM2 (written, read by REDUCE and three
    # blends: 5 each), GM3 (written, read by the level-3 group: 2).
    # Inputs and outputs are the app's own I/O.
    L = [(H >> lv) * (W >> lv) * 4 for lv in range(4)]
    image = 4 * L[1] + 4 * L[2] + 3 * L[3] + 2 * (L[0] + L[1] + L[2])
    collapse = 2 * (L[3] + L[2] + L[1]) + 2 * (L[2] + L[1] + L[0])
    mask = 5 * L[1] + 5 * L[2] + 2 * L[3]
    assert sched.interstage_bytes() == 3 * (2 * image + collapse) + mask
    assert "inter-group HBM" in sched.describe()


def test_resampling_kernels_write_only_their_result():
    """The lowered program's Pallas calls: each resampling kernel writes
    one plane of its output's size (tile-rounded), never its input's."""
    app = compile_graph(build_app("pyramid_blend", H, W), backend="pallas",
                        jit=False)
    args = [jax.ShapeDtypeStruct((H, W), np.float32)] * 7
    jaxpr = jax.make_jaxpr(app.fn)(*args)
    calls = {}
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = [v.aval.shape for v in eqn.outvars]
    kernels = [g for g in app.schedule.groups if g.is_kernel]
    assert sorted(calls) == sorted(g.name for g in kernels)
    for g in kernels:
        if g.kind != "resample":
            continue
        (shape,) = calls[g.name]
        th, tw = g.tile
        dst = g.outputs[0].shape
        assert shape == (-(-dst[0] // th) * th, -(-dst[1] // tw) * tw)


@pytest.mark.parametrize("op", ["pyr_down", "pyr_up"])
def test_bad_resample_input_names_the_user_line(op):
    with pytest.raises(GraphError) as ei:                     # not 2-D
        fe.trace(lambda x: getattr(fe, op)(x), (4, H, W))
    assert "2-D" in str(ei.value) and "test_pyramid.py" in str(ei.value)
    with pytest.raises(GraphError) as ei:                     # not float
        fe.trace(lambda x: getattr(fe, op)(x), fe.spec((H, W), np.int32))
    assert "floating" in str(ei.value)


def test_odd_size_is_a_graph_error_with_the_source_line():
    with pytest.raises(GraphError) as ei:
        fe.trace(lambda x: fe.pyr_down(x), (H - 1, W))
    msg = str(ei.value)
    assert "even" in msg and str((H - 1, W)) in msg
    assert "test_pyramid.py" in msg
    g = DataflowGraph("v")
    x = g.input("x", (H, W - 1))
    with pytest.raises(GraphError) as ei:
        g.pyr_down(x, lib.pyr_down_taps, name="half",
                   meta={"src": "user_prog.py:7"})
    assert "'half'" in str(ei.value) and "user_prog.py:7" in str(ei.value)


def test_validate_checks_the_resampling_shape_rule():
    g = DataflowGraph("v")
    x = g.input("x", (H, W))
    out = g.channel((H, W))                   # neither half nor twice
    g.task("bad", "resample", lib.pyr_down_taps, [x], [out], window=(5, 5))
    g.output(out, "out")
    with pytest.raises(GraphError, match="neither half nor twice"):
        g.validate()
