"""What every loop and entry shares: frames, the window, the sample.

A traffic mix (``chipbench/traffic/<name>.json``) is data.  It names its
``loop`` (``chipbench/loops/<loop>.py``, the generator that sends the
frames and times them) and its ``entry`` (``chipbench/entries/<entry>.py``,
how frames reach the system under test); every other key is a parameter
its loop reads.  Every mix shares two sizes: the seed makes ``POOL``
distinct frame sets, which the frames cycle through in a seeded order,
and ``SAMPLE`` of the window's frames, drawn from the seed, are compared
with the plain reference after the window.

Every frame is timed from its due time (open loop) or from when it was
sent (closed loop) to when its result is back with the client.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import numpy as np

#: how long after the window closes a frame may still come back before
#: it counts as never having come
GRACE_S = 60.0
POOL = 16
SAMPLE = 16


@dataclasses.dataclass
class Entry:
    """The system under test, set up and warm, as a loop drives it."""

    #: the frame sets as ``issue`` takes them
    pool: list[dict]
    #: ``issue(inputs) -> handle`` sends one frame set
    issue: Callable[[dict], Any]
    #: ``wait(handle, timeout) -> outputs`` waits for its outputs
    wait: Callable[[Any, float], dict]
    #: the program's own counters after the window, or None
    report: Callable[[], dict | None] = lambda: None
    #: frees what the entry holds, before the check
    close: Callable[[], None] = lambda: None


@dataclasses.dataclass
class Frame:
    index: int
    pool: int
    due: float
    issued: float | None = None
    done: float | None = None
    error: str | None = None


@dataclasses.dataclass
class Window:
    """What one measured window did, frame by frame."""

    t0: float
    seconds: float
    frames: list[Frame]
    #: outputs of the sampled frames, by frame index
    outputs: dict[int, dict[str, Any]]

    def in_window(self) -> list[Frame]:
        end = self.t0 + self.seconds
        return [f for f in self.frames if f.done is not None
                and f.error is None and f.done <= end]

    def failed(self) -> list[Frame]:
        return [f for f in self.frames if f.error is not None
                or f.done is None]


class Reservoir:
    """A uniform sample of ``k`` frames from a stream of unknown length,
    drawn from the seed (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.seen = 0
        self.kept: dict[int, Any] = {}
        self._slots: list[int] = []

    def offer(self, index: int, item: Any) -> None:
        self.seen += 1
        if len(self._slots) < self.k:
            self._slots.append(index)
            self.kept[index] = item
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            del self.kept[self._slots[j]]
            self._slots[j] = index
            self.kept[index] = item


def annotate(traced: bool) -> Callable[[str], Any]:
    """``annotate(name)``: a profiler span on the host's line of the
    trace when traced, else nothing."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)
