#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find its knee.

    python3 chipbench/sweep.py --workload lk1080.cameras --seed 5 \\
        --seconds 4 --rates 60 90 120

One process: the cell's traffic mix runs once per offered rate, for
``--seconds``, as ``--streams`` cameras at ``rate / streams`` frames/s
each, each rate with a set-up of its own (a fresh engine).  For each
rate it prints the offered and completed frame rates, the p50 and p95
latency over every frame due, and the backlog (frames due but not back)
at the window's end and at its middle: a backlog that grows from the
middle to the end means the rate is above what the system sustains.
The knee is the highest offered rate without a growing backlog; a
cell's camera count is then about four fifths of it over the cameras'
own rate.  Needs a TPU, like ``run.py``.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def backlog(window, at: float) -> int:
    t = window.t0 + at
    return sum(1 for f in window.frames
               if f.due <= t and (f.done is None or f.done > t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from harness import boot, cells, drive, spec, stats
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: the cell's traffic is not an open loop",
              file=sys.stderr)
        return 2
    devices = boot.start(cell.chips, "sweep")
    if devices is None:
        return 2
    import jax

    from harness.frames import frame_sets
    ref = spec.reference(cell.config["app"])
    shape = (int(cell.config["height"]), int(cell.config["width"]))
    rows = []
    for rate in args.rates:
        mix = dict(cell.traffic, streams=args.streams,
                   stream_fps=rate / args.streams)
        app, _ = cells.compile_app(cell, ref)
        pool_dev = frame_sets(args.seed, drive.POOL, shape,
                              ref.INPUTS, device=devices[0])
        jax.block_until_ready(pool_dev)
        entry = spec.entry(mix["entry"]).start(cell, app, pool_dev, devices)
        w = spec.loop("open").run(mix, entry, args.seconds,
                                  cells.rng_for(args.seed),
                                  drive.annotate(False))
        lat = [(f.done - f.due) if f.done is not None and f.error is None
               else float("inf") for f in w.frames]
        rep = entry.report()["measured"]
        entry.close()
        del entry, app, pool_dev
        gc.collect()
        row = {"offered_fps": rate,
               "fps": len(w.in_window()) / w.seconds,
               "p50_ms": stats.percentile(lat, 50) * 1e3,
               "p95_ms": stats.percentile(lat, 95) * 1e3,
               "failed": len(w.failed()),
               "backlog_mid": backlog(w, args.seconds / 2),
               "backlog_end": backlog(w, args.seconds),
               "batch_mean": rep["batch_size_mean"],
               "phases_ms": {k: v["mean_ms"]
                             for k, v in rep["phases"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
