#!/usr/bin/env python3
"""Compile every cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py

For each cell of ``BENCHMARK.json``, at its real size: the app's fused
program as the entry point launches it (one frame for device frames;
every engine batch width, sharded over the cell's chips of a described
v5e 2x2, for host frames), the frame maker, and the plain reference with
its comparison.  The TPU compiler refuses here what it would refuse on
the chip (unaligned blocks, VMEM over the limit, a kernel it cannot
partition, a program over the device's memory).  Nothing runs, so
nothing here is a time.  Prints one line per program and exits non-zero
if any failed.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KERNEL = 'custom_call_target="tpu_custom_call"'


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import check, drive, frames, spec
    from repro.core import compile_graph
    from repro.core.apps import build_app

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(spec.BENCHMARK_JSON) as f:
        bench = json.load(f)
    failed = 0

    def attempt(label, fn):
        nonlocal failed
        try:
            compiled = fn()
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            print(f"ok   {label}: {hlo.count(KERNEL)} Mosaic kernels, "
                  f"arguments {mem.argument_size_in_bytes} B, outputs "
                  f"{mem.output_size_in_bytes} B, temporaries "
                  f"{mem.temp_size_in_bytes} B", flush=True)
        except Exception as e:
            failed += 1
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)

    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        cfg, mix = cell.config, cell.traffic
        h, wd = int(cfg["height"]), int(cfg["width"])
        ref = spec.reference(cfg["app"])
        app = compile_graph(build_app(cfg["app"], h, wd), backend="pallas",
                            interpret=False, jit=False)
        plane = jax.ShapeDtypeStruct((h, wd), jnp.float32, sharding=one)
        for label, fn in spec.entry(mix["entry"]).programs(
                cell, app, plane, list(topo.devices)):
            attempt(f"{cell.name} {label}", fn)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=one)
        attempt(f"{cell.name} frame maker",
                lambda: frames._make.lower(
                    key, drive.POOL, h, wd,
                    len(ref.INPUTS)).compile())
        ins = {k: plane for k in ref.INPUTS}
        attempt(f"{cell.name} reference",
                lambda: jax.jit(ref.reference).lower(
                    ins).compile())
        attempt(f"{cell.name} comparison",
                lambda: check._gap.lower(plane, plane).compile())
    print(f"rehearsal: {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
