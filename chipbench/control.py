#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 chipbench/control.py --workload lk1080.device \\
        --seeds 101 102 103 --seconds 2

One process.  For each seed it makes one run of the cell through the
timed path with a short window (``--seconds``) and reads the check's
``gap`` (the program's reading), then puts the app's plain reference,
computed in bfloat16, in the program's place on the same frame sets and
reads the same gap against the float32 reference (the control's
reading) on the first three seeds.  The limit in the configuration's
``check`` lies between the largest program reading and the smallest
control reading.  The benchmark's own runs do not run this.  Needs a
TPU, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: how many of the seeds the control is read on
CONTROL_SEEDS = 3


def control_gap(cell, seed: int, devices) -> float:
    """The gap of the plain reference in bfloat16 against float32, on
    the cell's sample count of frame sets made from ``seed``."""
    import functools

    import jax
    import jax.numpy as jnp

    from harness import check, drive, spec
    from harness.frames import frame_sets
    ref = spec.reference(cell.config["app"])
    shape = (int(cell.config["height"]), int(cell.config["width"]))
    n = drive.SAMPLE
    pool = frame_sets(seed, n, shape, ref.INPUTS, device=devices[0])
    low = jax.jit(functools.partial(ref.reference, dtype=jnp.bfloat16))
    outputs = {i: {k: v.astype(jnp.float32) for k, v in low(p).items()}
               for i, p in enumerate(pool)}
    return check.compare(outputs, {i: i for i in range(n)}, pool, ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    from harness import boot, cells, spec
    cell = spec.load_cell(args.workload)
    devices = boot.start(cell.chips, "control")
    if devices is None:
        return 2

    program, control = {}, {}
    for seed in args.seeds:
        out = cells.run(cell, seed, args.seconds, False, devices,
                        time.perf_counter())
        program[seed] = out["checks"]["gap"]["value"]
        print(json.dumps({"seed": seed, "program_gap": program[seed],
                          "correct": out["correct"],
                          "frames": out["attempted"]}), flush=True)
    for seed in args.seeds[:CONTROL_SEEDS]:
        control[seed] = control_gap(cell, seed, devices)
        print(json.dumps({"seed": seed, "control_gap": control[seed]}),
              flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_max": max(program.values()),
                      "control_min": min(control.values()),
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
