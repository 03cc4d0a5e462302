#!/usr/bin/env python3
"""On-chip benchmark of FLOWER's compiled image pipelines: one cell, one run.

    python3 chipbench/run.py --workload lk1080.cameras --seed 7 \\
        --seconds 10 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) is one configuration (an
app at a raster size, mapped onto 1 or 4 chips) under one traffic mix.
The run compiles the app with ``compile_graph(backend="pallas")``, makes
its frames from ``--seed``, warms every shape it will use, drives the
cell's entry point for ``--seconds`` (or a few seconds, traced by JAX's
profiler, with ``--trace 1``), then compares sampled outputs with the
app's plain reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``
that decided ``correct``, each with its limit.

It runs on the machine it is started on and needs a TPU with as many
chips as the cell asks for: anything else exits non-zero and prints no
result.  JAX's persistent compile cache is ``.jax_cache/`` at the root
of the checkout.
"""
import time

T_START = time.perf_counter()   # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def finite(x):
    """JSON has no infinity: a value that is not finite is printed as
    1e300 (a check that reads it fails)."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import boot, cells, spec
    cell = spec.load_cell(args.workload)
    devices = boot.start(cell.chips, f"chipbench {args.workload}")
    if devices is None:
        return 2
    result = cells.run(cell, args.seed, args.seconds, bool(args.trace),
                       devices, T_START)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
