"""Compile the main path for a described TPU v5e, without the chip.

Nothing runs here: each test lowers a program the serving path
launches, with ``interpret=False``, and compiles it with the TPU
compiler against a v5e described by ``jax.experimental.topologies``.
Mosaic refuses unaligned blocks, over-budget VMEM and kernels it cannot
partition at this point, so these tests catch on the CPU what interpret
mode cannot.  The topology is described only inside a fixture, after a
test of this file has started (only one process may load the TPU
library at a time).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import compile_graph
from repro.core.apps import build_app

#: the engine's batch-8 vmapped launch at 1920x1080
BATCHED_APPS = ("gaussian_blur", "mean_filter", "filter_chain", "harris",
                "optical_flow_lk")
PLANE = (1080, 1920)
BATCH = 8
#: the multi-rate app at its benchmark size (PolyMage's)
PLANE_PYR = (2048, 2048)
#: the four-chip replication paths at 3840x2160
REPLICATED_APPS = ("gaussian_blur", "filter_chain")
PLANE_4K = (2160, 3840)

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_app(name: str, plane: tuple[int, int]):
    # jit=False: the host launcher would compile for this process's CPU
    return compile_graph(build_app(name, *plane), backend="pallas",
                         interpret=False, jit=False)


@pytest.mark.parametrize("name", BATCHED_APPS)
def test_batched_fused_program_compiles_for_v5e(name, one_chip,
                                                no_persistent_cache):
    app = _compiled_app(name, PLANE)
    kernels = sum(g.is_kernel for g in app.schedule.groups)
    args = [jax.ShapeDtypeStruct((BATCH,) + PLANE, jnp.float32,
                                 sharding=one_chip)
            for _ in app.input_names]
    hlo = jax.jit(jax.vmap(app.fn)).lower(*args).compile().as_text()
    assert kernels > 0
    assert hlo.count(KERNEL) == kernels


def test_pyramid_blend_compiles_for_v5e(one_chip, no_persistent_cache):
    """The multi-rate app at its benchmark size: one device program
    holding one Mosaic kernel per kernel group of the schedule, the 48
    resampling kernels among them, each under its group's name."""
    app = _compiled_app("pyramid_blend", PLANE_PYR)
    kernels = [g for g in app.schedule.groups if g.is_kernel]
    args = [jax.ShapeDtypeStruct(PLANE_PYR, jnp.float32, sharding=one_chip)
            for _ in app.input_names]
    hlo = jax.jit(app.fn).lower(*args).compile().as_text()
    assert sum(g.kind == "resample" for g in kernels) == 48
    assert hlo.count(KERNEL) == len(kernels)
    for g in kernels:
        assert re.search(rf"%{g.name}(\.\d+)? = .*{KERNEL}", hlo), g.name


@pytest.mark.parametrize("name", REPLICATED_APPS)
def test_replicated_engine_batch_compiles_for_v5e_2x2(name, topo,
                                                      no_persistent_cache):
    from repro.runtime import MicroBatcher
    app = _compiled_app(name, PLANE_4K)
    mb = MicroBatcher(max_batch=BATCH, replicas=4, devices=list(topo.devices),
                      backend="pallas")
    args = [jax.ShapeDtypeStruct((BATCH,) + PLANE_4K, jnp.float32)
            for _ in app.input_names]
    hlo = mb.batched_fn(app, BATCH).lower(*args).compile().as_text()
    assert KERNEL in hlo


@pytest.mark.parametrize("name", REPLICATED_APPS)
def test_replicate_app_compiles_for_v5e_2x2(name, topo, no_persistent_cache):
    from repro.parallel.replicate import replicate_app
    app = _compiled_app(name, PLANE_4K)
    rapp = replicate_app(app, 4, devices=list(topo.devices), interpret=False)
    rows = NamedSharding(rapp.mesh, P(rapp.mesh.axis_names[0], None))
    args = [jax.ShapeDtypeStruct(PLANE_4K, jnp.float32, sharding=rows)
            for _ in rapp.input_names]
    hlo = rapp.fn.lower(*args).compile().as_text()
    assert KERNEL in hlo
    assert "collective-permute" in hlo        # the halo exchange
