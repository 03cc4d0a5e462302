#!/usr/bin/env python3
"""Look for the cause of a stall of the whole process in a cell's runs.

    python3 chipbench/stall.py --workload lk1080.cameras \\
        --seeds 11 12 13 --seconds 20

One process; one run of the cell per seed, as ``control.py`` makes them.
Around each run:

- a watchdog thread wakes every ``TICK_S``, takes every Python
  thread's innermost frames, and notes each wake-up late by more than
  ``STALL_S``: a stall of the whole process holds it up too.  For such
  a wake-up it keeps the frames taken before and after it: the thread
  that moved on is the one that held the interpreter through the stall,
  and where it stood is where the stall began;
- ``gc.callbacks`` note every garbage collection: its generation, when
  it started and how long it took.

For each run it prints one JSON line: its metrics, the late wake-ups
and the collections over ``STALL_S`` from the window's start on, each
with its start in seconds from the run's start (a wake-up with its
frames), and per generation the count, total and longest collection.
The benchmark's own runs do not run this.  Needs a TPU, like
``run.py``.
"""
import argparse
import gc
import json
import os
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

TICK_S = 0.010
STALL_S = 0.040
DEPTH = 6


def stacks() -> dict[str, list[str]]:
    """Each thread's innermost ``DEPTH`` frames, by thread name."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return {names.get(ident, str(ident)):
            [f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
             for f in traceback.extract_stack(frame, limit=DEPTH)]
            for ident, frame in sys._current_frames().items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    from harness import boot, cells, spec
    cell = spec.load_cell(args.workload)
    devices = boot.start(cell.chips, "stall")
    if devices is None:
        return 2

    collections: list[tuple[int, float, float]] = []
    started: list[float] = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            t = time.perf_counter()
            collections.append((info["generation"], started[0],
                                t - started[0]))

    gc.callbacks.append(on_gc)
    for seed in args.seeds:
        collections.clear()
        late: list[tuple[float, float, dict, dict]] = []
        stop = threading.Event()

        def watch() -> None:
            while not stop.is_set():
                before = stacks()
                t = time.perf_counter()
                time.sleep(TICK_S)
                over = time.perf_counter() - t - TICK_S
                if over > STALL_S:
                    late.append((t, over, before, stacks()))

        t0 = time.perf_counter()
        watcher = threading.Thread(target=watch, name="stall-watchdog",
                                   daemon=True)
        watcher.start()
        try:
            out = cells.run(cell, seed, args.seconds, False, devices, t0)
        finally:
            stop.set()
            watcher.join()
        window = t0 + out["metrics"]["setup_s"]["value"]
        gens = {}
        for g in (0, 1, 2):
            ms = [d * 1e3 for gen, _, d in collections if gen == g]
            gens[g] = {"count": len(ms), "total_ms": sum(ms),
                       "longest_ms": max(ms, default=0.0)}
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "late_wakeups": [{"at_s": t - t0, "late_ms": d * 1e3,
                              "before": b, "after": a}
                             for t, d, b, a in late if t >= window],
            "long_collections": [[g, t - t0, d * 1e3]
                                 for g, t, d in collections
                                 if d > STALL_S and t >= window],
            "gc": gens}), flush=True)
    gc.callbacks.remove(on_gc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
