"""The profiler trace of a ``--trace 1`` run, and its reduction.

:class:`Capture` records the window with JAX's profiler (Python tracer
off) into a temporary directory, reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` into plain event records, and deletes the
directory.  :func:`reduce` turns those records into the numbers the
per-layer metrics read; it is pure, and its tests run it on a small
recorded trace kept beside them.

Conventions of the trace (one TPU v5e host):

- each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
  one event per HLO operation that ran, its line ``XLA Modules`` one
  per execution of a compiled program;
- an operation's event name is its HLO instruction
  (``%step.1 = f32[...] custom-call(...), custom_call_target=...``); a
  Mosaic kernel is a ``custom-call`` whose target is
  ``tpu_custom_call``, and everything else the app's program runs (pad,
  crop, copies) is "other";
- host threads are the lines of ``/host:CPU``; the harness marks its
  window and its own calls there with ``harness.*`` annotations, on the
  same clock as the device events.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Any

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "harness.window"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10
_OPCODE = re.compile(r"\s([a-z][a-z0-9_.-]*)\(")


class Capture:
    """Start and stop JAX's profiler around the window."""

    def __init__(self) -> None:
        self.dir: str | None = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> list[dict[str, Any]]:
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{self.dir}")
            return extract(jax.profiler.ProfileData.from_file(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def extract(data) -> list[dict[str, Any]]:
    """Plain records ``{plane, line, name, start_ns, dur_ns}`` of every
    event on the device planes and host threads."""
    out = []
    for plane in data.planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def is_kernel(op: dict[str, Any]) -> bool:
    return KERNEL_TARGET in op["name"]


def op_label(name: str) -> str:
    """``%step.1 = f32[..] custom-call(..), ..`` -> ``step.1 custom-call``."""
    lhs, _, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return f"{lhs.lstrip('%')} {m.group(1)}" if m else lhs.lstrip("%")


def reduce(events: list[dict[str, Any]],
           device_ids: list[int]) -> dict[str, Any]:
    """Device busy time, per-call kernel and other time, the top device
    operations and the longest idle gaps, all inside the window."""
    windows = [e for e in events if e["name"] == WINDOW
               and e["plane"] == HOST_PLANE]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    win = max(windows, key=lambda e: e["dur_ns"])
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    planes = [f"{DEVICE_PREFIX}{i}" for i in device_ids]

    ops: dict[str, list[dict]] = defaultdict(list)
    modules: dict[str, list[dict]] = defaultdict(list)
    for e in events:
        if e["plane"] in planes:
            if e["line"] == OPS_LINE:
                ops[e["plane"]].append(e)
            elif e["line"] == MODULES_LINE:
                modules[e["plane"]].append(e)
    if not any(ops.values()):
        raise ValueError(f"no device operations on {planes} in the trace")

    busy = {}
    merged = {}
    op_time: dict[str, float] = defaultdict(float)
    for p in planes:
        spans = []
        for op in ops[p]:
            c = _clip(op["start_ns"], op["start_ns"] + op["dur_ns"], w0, w1)
            if c is not None:
                spans.append(c)
                op_time[op_label(op["name"])] += (c[1] - c[0]) * 1e-9
        merged[p] = _union(spans)
        busy[p] = sum(b - a for a, b in merged[p]) * 1e-9

    # per call: programs that started in the window, each whole
    first = planes[0]
    calls = sorted((m for m in modules[first] if w0 <= m["start_ns"] < w1),
                   key=lambda m: m["start_ns"])
    first_ops = sorted(ops[first], key=lambda op: op["start_ns"])
    kernel_s = other_s = 0.0
    j = 0
    for m in calls:
        m0, m1 = m["start_ns"], m["start_ns"] + m["dur_ns"]
        while j < len(first_ops) and first_ops[j]["start_ns"] < m0:
            j += 1
        while j < len(first_ops) and first_ops[j]["start_ns"] < m1:
            op = first_ops[j]
            if is_kernel(op):
                kernel_s += op["dur_ns"] * 1e-9
            else:
                other_s += op["dur_ns"] * 1e-9
            j += 1

    gaps = []
    prev = w0
    for a, b in merged[first] + [[w1, w1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events if e["plane"] == HOST_PLANE
            and e["name"] != WINDOW]
    idle = [[_host_label(host, a, b), (b - a) * 1e-9] for a, b in gaps[:TOP]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy.values()) / len(planes),
        "calls": len(calls),
        "kernel_s": kernel_s,
        "other_s": other_s,
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": idle},
    }


def _host_label(host: list[dict], a: float, b: float) -> str:
    """What the host did in a device gap: the harness call that overlaps
    it most, then the other host event that does."""
    best: dict[bool, tuple[float, str]] = {}
    for e in host:
        c = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], a, b)
        if c is None:
            continue
        harness = e["name"].startswith("harness.")
        cover = c[1] - c[0]
        if cover > best.get(harness, (0.0, ""))[0]:
            best[harness] = (cover, e["name"])
    parts = [best[k][1] for k in (True, False) if k in best]
    return " / ".join(parts) if parts else "no host event"
