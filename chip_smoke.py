#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: compile_graph -> StreamEngine.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # replication across four chips only

One chip: ``optical_flow_lk``, ``harris`` and ``gaussian_blur`` at
1920x1080 float32 are compiled with ``compile_graph(backend="pallas")``
(every fused group must be a Mosaic kernel, none interpreted), then
``FRAMES`` frames of each are served through
``StreamEngine(backend="pallas", max_batch=8)`` and every result is
checked against ``DataflowGraph.reference_eval`` and the ``xla``
backend on the same chip.

``--chips 4``: ``gaussian_blur`` and ``filter_chain`` at 3840x2160 are
served through ``StreamEngine(replicas=4)`` and through
``replicate_app(app, 4)``; both must equal the one-chip output bit for
bit, with their shards on four distinct devices.

The whole run is one process: it is the only one that touches JAX.  It
fails unless JAX's first device is a TPU.  Times it prints come from a
single smoke run and are not benchmark results.  The last line of
standard output is the JSON verdict, printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ONE_CHIP_APPS = ("optical_flow_lk", "harris", "gaussian_blur")
ONE_CHIP_PLANE = (1080, 1920)
FOUR_CHIP_APPS = ("gaussian_blur", "filter_chain")
FOUR_CHIP_PLANE = (2160, 3840)
FRAMES = 32
FOUR_CHIP_FRAMES = 16
SEED = 0
#: the one stated tolerance where Mosaic and XLA round differently:
#: max |pallas - reference| <= TOL * max(1, max |reference|)
TOL = 1e-4


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def frames_for(graph, n: int, rng) -> list[dict[str, np.ndarray]]:
    """``n`` request input dicts of uniform [0, 1) planes (pixel-like)."""
    planes = {ch.name: rng.random((n,) + tuple(ch.shape), dtype=np.float32)
              for ch in graph.graph_inputs}
    return [{k: v[i] for k, v in planes.items()} for i in range(n)]


def normalized_err(got: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """(max abs error, that error over max(1, max |ref|))."""
    err = float(np.max(np.abs(got.astype(np.float64) - ref)))
    return err, err / max(1.0, float(np.max(np.abs(ref))))


def serve(engine, graph, requests) -> tuple[list[dict], float]:
    t0 = time.perf_counter()
    handles = [engine.submit(graph, r) for r in requests]
    outs = [h.result() for h in handles]
    return outs, time.perf_counter() - t0


def one_chip() -> None:
    import jax

    from repro.backends import resolve
    from repro.core import compile_graph
    from repro.core.apps import build_app
    from repro.runtime import StreamEngine

    check(resolve("pallas").resolve_interpret(None) is False,
          "the pallas backend resolves to interpret mode on this device")
    rng = np.random.default_rng(SEED)
    h, w = ONE_CHIP_PLANE
    total_compile = 0.0
    for name in ONE_CHIP_APPS:
        graph = build_app(name, h, w)
        t0 = time.perf_counter()
        app = compile_graph(graph, backend="pallas")
        dt = time.perf_counter() - t0
        total_compile += dt
        kernels = sum(1 for g in app.schedule.groups if g.is_kernel)
        calls = app.compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        log(f"{name}: compile_graph {dt:.2f} s (smoke run), "
            f"{kernels} fused groups -> {calls} tpu_custom_call")
        check(kernels > 0 and calls == kernels,
              f"{name}: {kernels} fused groups but {calls} Mosaic kernels "
              f"in the compiled HLO")

        requests = frames_for(graph, FRAMES, rng)
        with StreamEngine(backend="pallas", max_batch=8,
                          max_queue=FRAMES) as eng:
            outs, wall = serve(eng, graph, requests)
            buckets = eng.report()["buckets"]
        log(f"{name}: served {len(outs)} frames of {h}x{w} in {wall:.2f} s "
            f"wall incl. first-launch compiles (smoke run, not a "
            f"benchmark); batch widths {buckets}")
        check(len(outs) == FRAMES, f"{name}: {len(outs)} results")

        ref_fn = jax.jit(graph.reference_eval)
        xla = compile_graph(graph, backend="xla")
        worst = {"reference_eval": [0.0, 0.0, True], "xla": [0.0, 0.0, True]}
        for req, out in zip(requests, outs):
            refs = {"reference_eval": ref_fn(req), "xla": xla(**req)}
            for label, ref in refs.items():
                for oname in app.output_names:
                    got = np.asarray(out[oname])
                    want = np.asarray(ref[oname])
                    check(got.shape == want.shape and
                          bool(np.all(np.isfinite(got))),
                          f"{name}/{oname}: shape {got.shape} vs "
                          f"{want.shape} or non-finite values")
                    err, rel = normalized_err(got, want)
                    rec = worst[label]
                    rec[0], rec[1] = max(rec[0], err), max(rec[1], rel)
                    rec[2] = rec[2] and bool(np.array_equal(got, want))
        for label, (err, rel, exact) in worst.items():
            log(f"{name}: vs {label}: max abs err {err!r}, normalized "
                f"{rel!r}, bit-exact {exact} (tolerance {TOL})")
            check(rel <= TOL, f"{name}: {label} mismatch {rel} > {TOL}")
    log(f"total compile_graph seconds {total_compile:.2f} (smoke run)")


def four_chips() -> None:
    import jax

    from repro.core import compile_graph
    from repro.core.apps import build_app
    from repro.parallel.replicate import replicate_app
    from repro.runtime import MicroBatcher, StreamEngine

    devices = jax.devices()[:4]
    check(len({d.id for d in devices}) == 4,
          f"need 4 devices, JAX sees {len(jax.devices())}")
    rng = np.random.default_rng(SEED)
    h, w = FOUR_CHIP_PLANE

    def on_four(arr, what: str) -> None:
        devs = {s.device.id for s in arr.addressable_shards}
        check(len(devs) == 4, f"{what}: shards on devices {sorted(devs)}")

    for name in FOUR_CHIP_APPS:
        graph = build_app(name, h, w)
        app = compile_graph(graph, backend="pallas")
        requests = frames_for(graph, FOUR_CHIP_FRAMES, rng)
        single = [{k: np.asarray(v) for k, v in app(**r).items()}
                  for r in requests]

        with StreamEngine(backend="pallas", max_batch=8, replicas=4,
                          max_queue=FOUR_CHIP_FRAMES) as eng:
            outs, wall = serve(eng, graph, requests)
        for i, (got, want) in enumerate(zip(outs, single)):
            for oname in app.output_names:
                check(np.array_equal(np.asarray(got[oname]), want[oname]),
                      f"{name}: StreamEngine(replicas=4) frame {i} "
                      f"{oname} differs from one chip")
        batched = MicroBatcher(max_batch=8, replicas=4,
                               backend="pallas").launch(
            app, [types.SimpleNamespace(inputs=r) for r in requests[:8]])
        for oname, arr in batched.items():
            on_four(arr, f"{name}: replicas=4 batch {oname}")
        log(f"{name}: StreamEngine(replicas=4) served {len(outs)} frames "
            f"of {h}x{w} in {wall:.2f} s wall (smoke run), bit-exact vs "
            f"one chip, batch sharded over 4 devices")

        t0 = time.perf_counter()
        rapp = replicate_app(app, 4)
        for i, r in enumerate(requests):
            res = rapp(**r)
            for oname in app.output_names:
                if i == 0:
                    on_four(res[oname], f"{name}: replicate_app {oname}")
                check(np.array_equal(np.asarray(res[oname]),
                                     single[i][oname]),
                      f"{name}: replicate_app(app, 4) frame {i} {oname} "
                      f"differs from one chip")
        log(f"{name}: replicate_app(app, 4) {len(requests)} frames in "
            f"{time.perf_counter() - t0:.2f} s incl. compile (smoke run), "
            f"halo {rapp.halo_rows} rows, bit-exact vs one chip, rows "
            f"sharded over 4 devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: compile + serve on one chip; 4: only the "
                         "replication phase across four chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: FAILED: JAX runs on {platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    try:
        from repro.launch.compile_cache import use_compile_cache
        log(f"compile cache: {use_compile_cache()}")
        (four_chips if args.chips == 4 else one_chip)()
    except Exception as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        import traceback
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
