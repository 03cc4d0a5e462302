"""Backend abstraction layer: one registry drives lowering, tuning,
serving, and replication.

Acceptance tests for ``src/repro/backends/``:

- registry anatomy: the seed trio plus the gated ``pallas_gpu`` stub
  register, resolve (by name or spec), and report stable digests;
- capability matrix: every registered backend x every Table-I app
  either compiles and matches the ``xla`` oracle bit-exactly, or
  raises a single typed :class:`UnsupportedBackendError` naming the
  missing capability — never a crash;
- policy resolution: interpret-vs-compiled, donation and staging
  decisions come from the resolved record and reproduce the
  pre-registry behaviour on CPU;
- the serving/tuning caches key on the backend digest, so constants
  changes invalidate instead of aliasing;
- replication's kwarg filter is DERIVED from ``compile_graph``'s live
  signature — the regression test here fails when a new compile kwarg
  appears without being routed or declared unrouted;
- lint-as-test: zero backend string-literal comparisons anywhere in
  ``src/`` outside ``src/repro/backends/``.
"""
import dataclasses
import pathlib
import re

import numpy as np
import pytest

from repro.backends import (Backend, PALLAS, PALLAS_GPU, SEED_BACKENDS,
                            STAGE_KINDS, UnsupportedBackendError, XLA,
                            backends, current_platform, get, names,
                            register, resolve, unregister,
                            use_pallas_kernels)
from repro.core.apps import APPS, build_app
from repro.core.compiler import compile_graph
from repro.core.graph import GraphError

H, W = 48, 256

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"


# ----------------------------------------------------------------------
# registry anatomy
# ----------------------------------------------------------------------
def test_seed_backends_registered():
    assert set(SEED_BACKENDS) <= set(names())
    assert "pallas_gpu" in names()
    assert all(isinstance(b, Backend) for b in backends())


def test_resolve_name_and_spec_passthrough():
    assert resolve("pallas") is PALLAS
    assert resolve(PALLAS) is PALLAS
    adhoc = dataclasses.replace(XLA, name="adhoc")   # never registered
    assert resolve(adhoc) is adhoc                   # specs pass through
    assert get("does-not-exist") is None


def test_resolve_unknown_name_is_typed():
    with pytest.raises(UnsupportedBackendError) as ei:
        resolve("hexagon")
    assert ei.value.backend == "hexagon"
    assert "registered" in ei.value.missing
    assert isinstance(ei.value, GraphError)          # one error taxonomy
    with pytest.raises(UnsupportedBackendError):
        resolve(42)


def test_register_duplicate_name_rejected():
    clone = dataclasses.replace(XLA)
    with pytest.raises(ValueError, match="already registered"):
        register(clone)
    try:
        register(dataclasses.replace(XLA, name="scratch_backend"))
        assert resolve("scratch_backend").name == "scratch_backend"
    finally:
        unregister("scratch_backend")
    assert "scratch_backend" not in names()


def test_digest_is_stable_and_constants_sensitive():
    assert XLA.digest() == XLA.digest()
    assert XLA.cache_key() == f"xla@{XLA.digest()}"
    wider = dataclasses.replace(XLA, lane=256)
    assert wider.digest() != XLA.digest()
    fatter = dataclasses.replace(
        XLA, spec=dataclasses.replace(XLA.spec, vmem_bytes=1 << 20))
    assert fatter.digest() != XLA.digest()
    # capabilities are part of the identity too
    gated = dataclasses.replace(
        XLA, capabilities=frozenset({"point"}))
    assert gated.digest() != XLA.digest()


def test_capability_api():
    assert XLA.supports("stencil") and XLA.supports("tuning")
    assert not PALLAS_GPU.supports("stencil")
    assert PALLAS_GPU.missing("stencil", "point") == ("stencil",)
    XLA.require("point", "stencil")                  # no raise
    with pytest.raises(UnsupportedBackendError) as ei:
        PALLAS_GPU.require("stencil")
    assert ei.value.backend == "pallas_gpu"
    assert "stencil" in ei.value.missing


def test_backend_validates_capability_vocabulary():
    with pytest.raises(ValueError, match="unknown capabilit"):
        Backend(name="bogus", capabilities=frozenset({"telepathy"}))


# ----------------------------------------------------------------------
# policy resolution: interpret / donation / staging
# ----------------------------------------------------------------------
def test_interpret_resolution_matches_seed_defaults_on_cpu():
    plat = current_platform()
    for name in SEED_BACKENDS:
        be = resolve(name)
        # explicit values always win
        assert be.resolve_interpret(True) is True
        assert be.resolve_interpret(False) is False
        # None defers to nativeness; on CPU every seed interprets,
        # which is exactly the old compile_graph(interpret=True) default
        assert be.resolve_interpret(None) == (plat not in
                                              be.native_platforms)
    if plat != "tpu":
        assert PALLAS.resolve_interpret(None) is True


def test_interpreting_where_kernels_compile_natively_raises():
    here = dataclasses.replace(PALLAS, name="native_here",
                               native_platforms=(current_platform(),))
    assert here.resolve_interpret(None) is False
    assert here.resolve_interpret(False) is False
    with pytest.raises(UnsupportedBackendError) as ei:
        here.resolve_interpret(True)
    assert ei.value.missing == ("compiled",)


def test_donation_policy_matches_old_microbatcher_probe():
    for name in SEED_BACKENDS:
        be = resolve(name)
        assert be.resolve_donate(True, "cpu") is False
        assert be.resolve_donate(True, "tpu") is True
        assert be.resolve_donate(False, "tpu") is False
    never = dataclasses.replace(XLA, name="never", donation="never")
    assert never.resolve_donate(True, "tpu") is False


def test_staging_depth_keeps_historical_slack():
    for name in SEED_BACKENDS:
        assert resolve(name).staging_depth(2) == 3   # old inflight + 1


# ----------------------------------------------------------------------
# capability matrix: every backend x every Table-I app
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def oracle_outputs():
    """xla-compiled outputs per app, the bit-exactness oracle."""
    rng = np.random.default_rng(7)
    out = {}
    for name in sorted(APPS):
        g = build_app(name, H, W)
        inputs = {c.name: rng.normal(size=c.shape).astype(np.float32)
                  for c in g.graph_inputs}
        outs = compile_graph(build_app(name, H, W), backend="xla")(**inputs)
        out[name] = (inputs, {k: np.asarray(v) for k, v in outs.items()})
    return out


@pytest.mark.parametrize("backend", sorted(
    set().union(*[{n} for n in ("xla", "xla_staged", "pallas",
                                "pallas_gpu")])))
@pytest.mark.parametrize("app", sorted(APPS))
def test_capability_matrix(app, backend, oracle_outputs):
    inputs, expected = oracle_outputs[app]
    try:
        compiled = compile_graph(build_app(app, H, W), backend=backend)
    except UnsupportedBackendError as e:
        # the ONE typed rejection: it must name the backend and what is
        # missing (a capability, the platform gate, or the lower stub)
        assert e.backend == backend
        assert e.missing, f"{backend} rejection names nothing missing"
        return
    got = compiled(**inputs)
    assert sorted(got) == sorted(expected)
    for k in expected:                               # atol=0: bit-exact
        np.testing.assert_array_equal(np.asarray(got[k]), expected[k],
                                      err_msg=f"{app}/{backend}/{k}")


def test_seed_backends_share_graph_signature():
    sigs = set()
    for b in SEED_BACKENDS:
        app = compile_graph(build_app("sobel", H, W), backend=b)
        sigs.add(app.graph.signature())
        assert app.signature().endswith(resolve(b).cache_key())
    assert len(sigs) == 1, "lowering must not perturb the canonical graph"


def test_pallas_gpu_stub_is_gated_not_crashing():
    be = resolve("pallas_gpu")
    assert be.capabilities >= {"point", "pointN", "split"}
    assert be.requires_platform == "gpu"
    if current_platform() not in ("gpu", "cuda", "rocm"):
        assert not be.available()
    with pytest.raises(UnsupportedBackendError):
        compile_graph(build_app("sobel", H, W), backend="pallas_gpu")


# ----------------------------------------------------------------------
# cache keying on the backend digest
# ----------------------------------------------------------------------
def test_compile_cache_splits_on_backend_digest():
    from repro.runtime.cache import CompileCache
    cache = CompileCache()
    g = build_app("square", H, W)
    a1 = cache.get(g, backend="xla")
    # same name, different constants => different digest => a recompile
    variant = dataclasses.replace(XLA, default_max_tile=(128, 512))
    a2 = cache.get(g, backend=variant)
    assert a1 is not a2
    assert cache.stats.misses == 2
    assert cache.get(g, backend="xla") is a1         # still hot


def test_tuning_key_carries_backend_digest():
    from repro.tune.store import TuningKey
    g = build_app("square", H, W)
    key = TuningKey.for_graph(g, "xla", "cpu")
    assert key.backend == XLA.cache_key()
    variant = dataclasses.replace(XLA, lane=256)
    key2 = TuningKey.for_graph(g, variant, "cpu")
    assert key2.backend != key.backend
    assert key2.digest() != key.digest()


def test_dataflow_fn_memoizes_backend_structurally():
    from repro.frontend import dataflow_fn

    @dataflow_fn
    def double(img):
        return img * 2.0

    x = np.ones((8, 128), np.float32)
    a1 = double.compile(x, backend=resolve("xla"))
    a2 = double.compile(x, backend=dataclasses.replace(XLA))  # equal copy
    assert a1 is a2                    # keyed by cache_key, not id()


# ----------------------------------------------------------------------
# kernels' impl= knob rides the same registry probe
# ----------------------------------------------------------------------
def test_use_pallas_kernels_resolution():
    assert use_pallas_kernels("pallas") is True
    assert use_pallas_kernels("ref") is False
    assert use_pallas_kernels("auto") == resolve("pallas").is_native()
    assert use_pallas_kernels("auto", auto_native=False) is False
    assert use_pallas_kernels("pallas", auto_native=False) is True


# ----------------------------------------------------------------------
# replication kwarg routing is derived, and covers compile_graph
# ----------------------------------------------------------------------
def test_replication_routing_covers_every_compile_kwarg():
    """Fails when compile_graph grows a kwarg replication ignores.

    Every keyword of ``compile_graph`` (beyond graph/backend) must be
    either routed into the scheduler/lowering/tuner by
    ``replication_kwarg_routing`` or explicitly declared in
    ``UNROUTED_COMPILE_KWARGS``.  Add a new compile knob and this test
    names it until replication takes a position on it.
    """
    import inspect
    from repro.parallel.replicate import (UNROUTED_COMPILE_KWARGS,
                                          replication_kwarg_routing)
    all_kwargs = set(
        inspect.signature(compile_graph).parameters) - {"graph", "backend"}
    known, sched, lower = replication_kwarg_routing()
    unclassified = all_kwargs - known - UNROUTED_COMPILE_KWARGS
    assert not unclassified, (
        f"compile_graph kwargs {sorted(unclassified)} are neither routed "
        f"by replicate_app nor declared in UNROUTED_COMPILE_KWARGS — "
        f"decide how replication treats them")
    # the historical hand-maintained set stays supported
    assert known >= {"canonicalize", "strict", "passes", "spec",
                     "vector_factor", "interpret", "tune", "tune_cache",
                     "max_tile"}
    assert sched and lower


def test_replicate_app_rejects_unknown_kwargs():
    from repro.parallel.replicate import replicate_app
    with pytest.raises(TypeError, match="unsupported compile kwargs"):
        replicate_app(build_app("square", H, W), 1, bogus_option=1)


def test_replicate_requires_replication_capability():
    from repro.parallel.replicate import replicate_app
    gated = dataclasses.replace(
        XLA, name="no_repl",
        capabilities=frozenset(STAGE_KINDS) | {"tuning"})
    with pytest.raises(UnsupportedBackendError) as ei:
        replicate_app(build_app("square", H, W), 1, backend=gated)
    assert "replication" in ei.value.missing


# ----------------------------------------------------------------------
# lint-as-test: no backend string-literal dispatch outside backends/
# ----------------------------------------------------------------------
_BACKEND_LIT = r'["\'](?:xla|xla_staged|pallas|pallas_gpu)["\']'
_LITERAL_DISPATCH = re.compile(
    rf'(?:==|!=)\s*{_BACKEND_LIT}'
    rf'|{_BACKEND_LIT}\s*(?:==|!=)'
    rf'|\b(?:in|not\s+in)\s+[\(\[{{]\s*{_BACKEND_LIT}')


def test_no_backend_literal_comparisons_outside_registry():
    """grep src/ for `== "pallas"`-style dispatch; zero allowed.

    Backend behaviour differences must live on the Backend record
    (capabilities, constants, hooks) — an if/elif on the name anywhere
    else reintroduces exactly the drift the registry removed.
    """
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        if "backends" in path.relative_to(SRC).parts:
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if _LITERAL_DISPATCH.search(line):
                offenders.append(f"{path.relative_to(SRC)}:{i}: "
                                 f"{line.strip()}")
    assert not offenders, (
        "backend string-literal comparisons outside src/repro/backends/ "
        "(dispatch through the registry instead):\n" + "\n".join(offenders))
