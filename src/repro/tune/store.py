"""Persistent tuning cache: measured schedule configs, on disk.

FLOWER amortizes its most expensive step by shipping the synthesized
bitstream: place-and-route runs once, every later execution loads the
artifact.  The software analogue for a *measured* autotuner is this
store — profiling lowered candidates on the live backend costs real
wall-clock, so the winning :class:`ScheduleConfig` is persisted under a
:class:`TuningKey` of ``(DataflowGraph.signature(), backend,
device_kind, input shapes)`` and every later
``compile_graph(..., tune="auto")`` of the same app on the same
hardware loads it with **zero** re-measurement.

Layout: one JSON file per key under the cache root (``root`` argument,
else ``$REPRO_TUNE_CACHE``, else ``~/.cache/repro/tune``).  Writes are
atomic (temp file + ``os.replace``) so concurrent tuners never expose
a torn record; records are versioned so a future format change
invalidates old entries instead of misreading them.

    >>> import tempfile
    >>> cache = TuningCache(tempfile.mkdtemp())
    >>> key = TuningKey("sig0123", "pallas", "cpu", (("img", (8, 128), "float32"),))
    >>> cfg = ScheduleConfig(group_vf=(2,))
    >>> cache.put(key, TuningRecord(config=cfg, source="measured"))
    >>> cache.get(key).config.group_vf
    (2,)
    >>> len(TuningCache(cache.root))      # a fresh handle re-reads disk
    1
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Iterator

__all__ = ["ScheduleConfig", "TuningKey", "TuningRecord", "TuningCache",
           "default_cache_root"]

#: bump when the record format changes; readers skip other versions
RECORD_VERSION = 1


@dataclasses.dataclass(frozen=True)
class ScheduleConfig:
    """One point of the schedule search space, ready to re-apply.

    The three knobs the tuner searches (see ``docs/tuning.md``):

    - ``group_vf`` — per-fusion-group vector factor, aligned with
      ``Schedule.groups`` order (``None`` for trivial custom/reduce
      groups, which have no tile),
    - ``max_tile`` — the tile-shape cap handed to
      :func:`repro.core.vectorize.choose_tile` (the height axis of the
      search; the width axis is ``group_vf``),
    - ``vmem_fraction`` — the fusion budget: the fraction of
      ``TPUSpec.vmem_bytes`` the partitioner may spend, which changes
      *which stages fuse*, not just how they tile.
    """

    group_vf: tuple[int | None, ...]
    max_tile: tuple[int, int] = (256, 1024)
    vmem_fraction: float = 1.0

    def to_json(self) -> dict[str, Any]:
        return {"group_vf": list(self.group_vf),
                "max_tile": list(self.max_tile),
                "vmem_fraction": self.vmem_fraction}

    @classmethod
    def from_json(cls, d: dict[str, Any]) -> "ScheduleConfig":
        return cls(group_vf=tuple(d["group_vf"]),
                   max_tile=tuple(d["max_tile"]),
                   vmem_fraction=float(d["vmem_fraction"]))

    def describe(self) -> str:
        vfs = ",".join("-" if v is None else str(v) for v in self.group_vf)
        return (f"vf=[{vfs}] max_tile={self.max_tile} "
                f"vmem_fraction={self.vmem_fraction:g}")


@dataclasses.dataclass(frozen=True)
class TuningKey:
    """Identity of a tuning result: graph x backend x hardware x shapes.

    ``signature`` is :meth:`repro.core.graph.DataflowGraph.signature`
    (structural: topology, shapes, dtypes, stage bodies); ``shapes``
    repeats the graph-input shapes explicitly so a record survives a
    signature-algorithm change detectably rather than silently.
    ``mode`` separates Pallas interpreter-mode timings from compiled
    ones — they have unrelated performance profiles, so a winner
    measured under one must never be served for the other.
    ``context`` digests everything else that changes what a
    measurement means (the TPUSpec's constants, strict/canonicalize
    compile flags): configs tuned under one context are invisible to
    compiles running under another.

    ``backend`` is the resolved record's
    :meth:`~repro.backends.Backend.cache_key` — ``name@digest`` over
    its capabilities and constants — so a re-registered backend with
    different lane/VMEM constants invalidates old winners instead of
    silently serving schedules measured under other budgets.
    """

    signature: str
    backend: str
    device_kind: str
    shapes: tuple[tuple[str, tuple[int, ...], str], ...]
    mode: str = "interpret"
    context: str = ""

    @classmethod
    def for_graph(cls, graph, backend,
                  device_kind: str | None = None, *,
                  interpret: bool = True,
                  context: str = "") -> "TuningKey":
        from repro.backends import resolve
        backend_key = resolve(backend).cache_key()
        if device_kind is None:
            device_kind = detect_device_kind()
        import numpy as np
        shapes = tuple((c.name, tuple(c.shape), np.dtype(c.dtype).name)
                       for c in graph.graph_inputs)
        return cls(graph.signature(), backend_key, device_kind, shapes,
                   "interpret" if interpret else "compiled", context)

    def digest(self) -> str:
        blob = json.dumps([self.signature, self.backend, self.device_kind,
                           [list(map(str, s)) for s in self.shapes],
                           self.mode, self.context])
        return hashlib.sha256(blob.encode()).hexdigest()[:24]


@dataclasses.dataclass
class TuningRecord:
    """A stored tuning result plus enough context to audit it."""

    config: ScheduleConfig
    #: how the config was obtained ("measured"); a *loaded* record is
    #: reported as source="cache" by the search layer
    source: str = "measured"
    best_measured_s: float | None = None
    analytic_measured_s: float | None = None
    modeled_s: float | None = None
    n_trials: int = 0
    #: candidates the calibrated prior skipped without measuring
    #: (0 for uncalibrated searches and pre-calibration records)
    n_pruned: int = 0
    created_at: float = 0.0
    version: int = RECORD_VERSION

    def to_json(self, key: TuningKey) -> dict[str, Any]:
        return {"version": self.version,
                "key": {"signature": key.signature, "backend": key.backend,
                        "device_kind": key.device_kind, "mode": key.mode,
                        "context": key.context,
                        "shapes": [[n, list(s), d] for n, s, d in key.shapes]},
                "config": self.config.to_json(), "source": self.source,
                "best_measured_s": self.best_measured_s,
                "analytic_measured_s": self.analytic_measured_s,
                "modeled_s": self.modeled_s, "n_trials": self.n_trials,
                "n_pruned": self.n_pruned,
                "created_at": self.created_at}

    @classmethod
    def from_json(cls, d: dict[str, Any]) -> "TuningRecord":
        return cls(config=ScheduleConfig.from_json(d["config"]),
                   source=d.get("source", "measured"),
                   best_measured_s=d.get("best_measured_s"),
                   analytic_measured_s=d.get("analytic_measured_s"),
                   modeled_s=d.get("modeled_s"),
                   n_trials=int(d.get("n_trials", 0)),
                   n_pruned=int(d.get("n_pruned", 0)),
                   created_at=float(d.get("created_at", 0.0)),
                   version=int(d.get("version", 0)))


def default_cache_root() -> str:
    """Resolve the on-disk root: ``$REPRO_TUNE_CACHE`` else XDG cache."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    xdg = os.environ.get("XDG_CACHE_HOME",
                         os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(xdg, "repro", "tune")


def detect_device_kind() -> str:
    """Hardware identity for the tuning key.

    A schedule measured on one device kind must not be served on
    another — the whole point of measuring — so the key carries
    ``jax.devices()[0].device_kind``.  A JAX that cannot reach its
    devices raises rather than keying winners on a made-up kind.
    """
    import jax
    return jax.devices()[0].device_kind


class TuningCache:
    """On-disk store of measured :class:`ScheduleConfig` winners.

    ``get``/``put`` are keyed by :class:`TuningKey`; a process-local
    memo sits in front of the filesystem so the serving engine's many
    per-request ``compile_graph(tune="auto")`` calls do not re-read
    JSON.  ``put`` accepts ``aliases`` — extra keys mapping to the same
    record — because canonicalization can legitimately change a graph's
    signature once (see :class:`repro.runtime.cache.CompileCache`):
    both the pre- and post-canonicalization forms must hit.
    """

    def __init__(self, root: str | None = None):
        self.root = root or default_cache_root()
        self._memo: dict[str, TuningRecord | None] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def _path(self, key: TuningKey) -> str:
        return os.path.join(self.root, key.digest() + ".json")

    def get(self, key: TuningKey) -> TuningRecord | None:
        """Load the record for ``key`` (memoized), or ``None`` on miss."""
        digest = key.digest()
        with self._lock:
            if digest in self._memo:
                return self._memo[digest]
        rec: TuningRecord | None = None
        try:
            with open(self._path(key)) as f:
                raw = json.load(f)
            if raw.get("version") == RECORD_VERSION:
                rec = TuningRecord.from_json(raw)
        except (OSError, ValueError, KeyError):
            rec = None
        with self._lock:
            self._memo[digest] = rec
        return rec

    def put(self, key: TuningKey, record: TuningRecord,
            aliases: tuple[TuningKey, ...] = ()) -> None:
        """Persist ``record`` under ``key`` (and ``aliases``) atomically."""
        if not record.created_at:
            record.created_at = time.time()
        os.makedirs(self.root, exist_ok=True)
        for k in (key, *aliases):
            payload = json.dumps(record.to_json(k), indent=1)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(payload)
                os.replace(tmp, self._path(k))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            with self._lock:
                self._memo[k.digest()] = record

    def invalidate(self, key: TuningKey) -> None:
        with self._lock:
            self._memo.pop(key.digest(), None)
        try:
            os.unlink(self._path(key))
        except OSError:
            pass

    def clear(self) -> None:
        with self._lock:
            self._memo.clear()
        try:
            names = os.listdir(self.root)
        except OSError:
            return
        for n in names:
            if n.endswith(".json"):
                try:
                    os.unlink(os.path.join(self.root, n))
                except OSError:
                    pass

    def entries(self) -> Iterator[TuningRecord]:
        """Yield every readable current-version record on disk.

        Alias files (the pre/post-canonicalization forms of one
        tuning result) are deduplicated — one tuned app counts once.
        """
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return
        seen: list[TuningRecord] = []
        for n in names:
            if not n.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, n)) as f:
                    raw = json.load(f)
                if raw.get("version") != RECORD_VERSION:
                    continue
                rec = TuningRecord.from_json(raw)
            except (OSError, ValueError, KeyError):
                continue
            if rec in seen:                 # an alias of a yielded record
                continue
            seen.append(rec)
            yield rec

    def __len__(self) -> int:
        return sum(1 for _ in self.entries())
