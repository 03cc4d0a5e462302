"""Closed loop: a client that keeps ``outstanding`` frames in flight.

It waits for the oldest, then sends the next, until the window closes;
the frames still in flight then are drained and checked, not counted.
A frame is timed from when it was sent to when its result is back.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

from harness import drive


def run(mix: dict, entry: drive.Entry, seconds: float,
        rng: np.random.Generator, ann) -> drive.Window:
    pool = entry.pool
    outstanding = int(mix["outstanding"])
    order = rng.permutation(len(pool))
    keep = drive.Reservoir(drive.SAMPLE, rng)
    frames: list[drive.Frame] = []
    inflight: deque = deque()
    t0 = time.perf_counter()
    end = t0 + seconds

    def retire() -> None:
        frame, handle = inflight.popleft()
        try:
            with ann("harness.result"):
                out = entry.wait(handle, max(0.0, end + drive.GRACE_S
                                             - time.perf_counter()))
        except TimeoutError:
            frame.error = "never came"
            return
        except Exception as e:
            frame.error = f"{type(e).__name__}: {e}"
            return
        frame.done = time.perf_counter()
        keep.offer(frame.index, out)

    with ann("harness.window"):
        while time.perf_counter() < end:
            while len(inflight) < outstanding:
                i = len(frames)
                p = int(order[i % len(pool)])
                frame = drive.Frame(i, p, time.perf_counter())
                frame.issued = frame.due
                frames.append(frame)
                try:
                    with ann("harness.launch"):
                        inflight.append((frame, entry.issue(pool[p])))
                except Exception as e:
                    frame.error = f"{type(e).__name__}: {e}"
                    break
            if inflight:
                retire()
    while inflight:
        retire()
    return drive.Window(t0, seconds, frames, keep.kept)
