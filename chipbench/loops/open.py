"""Open loop: independent cameras, each frame due on a fixed schedule
whatever the system does.

Mix keys: ``streams`` cameras at ``stream_fps`` frames/s each.  Their
phases split one frame period evenly and the seed assigns them, so every
seed offers the same arrivals in another order.  A frame is timed from
its due time to when its result is back with the client.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from harness import drive


def camera_schedule(streams: int, stream_fps: float, seconds: float,
                    rng: np.random.Generator) -> list[tuple[float, int]]:
    """``(offset from window start in s, camera)`` for every frame due
    in the window, in time order.  Camera ``c`` sits at phase slot
    ``perm[c]`` of ``streams`` even slots in one frame period."""
    period = 1.0 / stream_fps
    slot = rng.permutation(streams)
    out = []
    for c in range(streams):
        phase = slot[c] * period / streams
        t = phase
        while t < seconds:
            out.append((t, c))
            t += period
    out.sort()
    return out


def run(mix: dict, entry: drive.Entry, seconds: float,
        rng: np.random.Generator, ann) -> drive.Window:
    """This thread sends each frame at its due time; a collector thread
    takes the results in order."""
    pool = entry.pool
    streams = int(mix["streams"])
    schedule = camera_schedule(streams, float(mix["stream_fps"]), seconds,
                               rng)
    start = rng.integers(len(pool), size=streams)
    sample = set(rng.choice(len(schedule),
                            size=min(drive.SAMPLE, len(schedule)),
                            replace=False).tolist())
    outputs: dict[int, dict] = {}
    todo: queue.Queue = queue.Queue()
    seen = [0] * streams

    def collect(deadline: float) -> None:
        while True:
            item = todo.get()
            if item is None:
                return
            frame, handle = item
            try:
                with ann("harness.result"):
                    out = entry.wait(handle, max(0.0, deadline
                                                 - time.perf_counter()))
            except TimeoutError:
                frame.error = "never came"
                continue
            except Exception as e:          # a failed frame is counted
                frame.error = f"{type(e).__name__}: {e}"
                continue
            frame.done = time.perf_counter()
            if frame.index in sample:
                outputs[frame.index] = out

    t0 = time.perf_counter()
    collector = threading.Thread(target=collect,
                                 args=(t0 + seconds + drive.GRACE_S,),
                                 name="chipbench-collector", daemon=True)
    collector.start()
    frames = []
    try:
        with ann("harness.window"):
            for i, (offset, cam) in enumerate(schedule):
                p = int((start[cam] + seen[cam]) % len(pool))
                seen[cam] += 1
                frame = drive.Frame(i, p, t0 + offset)
                frames.append(frame)
                lag = frame.due - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                frame.issued = time.perf_counter()
                try:
                    with ann("harness.submit"):
                        handle = entry.issue(pool[p])
                except Exception as e:
                    frame.error = f"{type(e).__name__}: {e}"
                    continue
                todo.put((frame, handle))
            # the window ends with the schedule, not with the results
            rest = t0 + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
    finally:
        todo.put(None)
        collector.join()
    return drive.Window(t0, seconds, frames, outputs)
