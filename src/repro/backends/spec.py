"""The :class:`Backend` spec: one declarative record per target.

FLOWER lowers one dataflow program to different targets (the Avnet
Ultra96 SoC vs. the Alveo U280 card) through a single canonical
pipeline; the per-target decisions — lowering strategy, datapath
constants, memory budgets, measurement harness — live in *flow*
descriptions, not sprinkled through the compiler.  This module is the
software analogue (after edalize's flow classes): a ``Backend`` is a
frozen dataclass naming

- **identity** — ``name`` and a stable :meth:`digest` over
  capabilities + constants, so caches keyed on a backend can never
  serve an incompatible target;
- **capabilities** — the set of stage kinds (``point``, ``stencil``,
  ``custom``, ...) and features the target can lower.  Asking for
  anything outside the set raises the single typed
  :class:`UnsupportedBackendError` naming what is missing — never a
  bare ``KeyError`` deep inside a lowering;
- **hardware constants** — lane width, sublane rows, default tile cap
  and the :class:`~repro.core.vectorize.TPUSpec` memory/compute
  budgets that the vectorizer's sweep and the scheduler's fusion
  budget read (subsuming the ad-hoc ``TPUSpec`` plumbing);
- **hooks** — ``lower`` (group -> callable kernel), ``measure`` (the
  autotuner's timing harness) and policies the serving runtime used
  to re-derive locally: donation (:class:`MicroBatcher
  <repro.runtime.batching.MicroBatcher>`), staging depth slack, and
  interpret-vs-compiled resolution.

Backends are registered once (:mod:`repro.backends.registry`) and
resolved everywhere else; no other module may compare backend names.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Callable

from repro.core.graph import GraphError

__all__ = ["Backend", "UnsupportedBackendError", "STAGE_KINDS"]

#: every stage kind a DataflowGraph can contain; a backend's
#: capability set is validated against this vocabulary
STAGE_KINDS = ("point", "pointN", "split", "stencil", "resample", "custom",
               "reduce")

#: non-stage capability flags a backend may declare
FEATURE_CAPS = ("fused_streaming", "staged_hbm", "replication", "tuning")


class UnsupportedBackendError(GraphError):
    """A backend cannot serve the request — and says exactly why.

    Raised for an unknown backend name, a stage kind outside the
    backend's capability set, or a registered-but-gated backend whose
    device requirement is not met.  ``missing`` carries the
    capability (or requirement) that was absent so tooling can react
    programmatically; the message names it for humans.
    """

    def __init__(self, message: str, *, backend: str = "",
                 missing: tuple[str, ...] = ()):
        super().__init__(message)
        self.backend = backend
        self.missing = tuple(missing)


def _default_platform() -> str:
    """The platform JAX runs on ("cpu" / "tpu" / "gpu" / ...).

    A JAX that cannot initialize its backend raises here: guessing
    "cpu" would quietly run the interpreter on a host whose chip failed.
    """
    import jax
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class Backend:
    """Declarative description of one lowering target.

    Instances are immutable; behavioural variation lives in the
    ``lower`` / ``measure`` hooks and the policy fields, never in
    call-site string comparisons.  Two backends with equal
    capabilities and constants share a :meth:`digest`, so compile and
    tuning caches keyed on :meth:`cache_key` transfer between them
    exactly when that is safe.
    """

    name: str
    #: one-line human description (docs/backends.md table)
    description: str = ""
    #: stage kinds + feature flags this backend can lower
    capabilities: frozenset = frozenset(STAGE_KINDS)
    #: platforms where this backend's kernels compile natively
    #: (outside them, pallas-style backends run interpreted)
    native_platforms: tuple = ()
    #: platform the backend *requires* to lower at all (``None`` =
    #: runs anywhere); a gated backend registers and reports its
    #: capabilities but refuses to lower off-target
    requires_platform: str | None = None

    # -- hardware constants (subsume the ad-hoc TPUSpec plumbing) ------
    #: VPU/MXU lane width: fused tiles are ``lane * vector_factor`` wide
    lane: int = 128
    #: sublane rows (float32): tile heights align to this
    sublane: int = 8
    #: default (th, tw) cap for tile selection
    default_max_tile: tuple = (256, 1024)
    #: memory-space / bandwidth / clock budgets (VMEM, HBM, ...)
    spec: Any = None

    # -- hooks ---------------------------------------------------------
    #: ``lower(group, *, backend, spec, vector_factor, interpret,
    #: valid_rows) -> Callable`` producing the group's kernel; ``None``
    #: marks a registered-but-gated stub
    lower: Callable | None = None
    #: ``measure(graph, backend, config, **kw) -> seconds`` for the
    #: autotuner; ``None`` falls back to
    #: :func:`repro.tune.search.default_measure`
    measure: Callable | None = None

    # -- runtime policies ---------------------------------------------
    #: buffer-donation policy for the MicroBatcher: ``"auto"`` donates
    #: except on platforms that ignore it (probing once per bucket
    #: elsewhere), ``"never"`` disables donation outright
    donation: str = "auto"
    #: extra staging-buffer rotations beyond the in-flight depth the
    #: engine must keep (zero-copy aliasing safety margin)
    staging_slack: int = 1

    def __post_init__(self):
        caps = frozenset(self.capabilities)
        object.__setattr__(self, "capabilities", caps)
        vocab = set(STAGE_KINDS) | set(FEATURE_CAPS)
        unknown = caps - vocab
        if unknown:
            raise ValueError(
                f"backend {self.name!r} declares unknown capabilities "
                f"{sorted(unknown)}; known: {sorted(vocab)}")
        if self.donation not in ("auto", "never"):
            raise ValueError(
                f"backend {self.name!r}: donation policy must be 'auto' "
                f"or 'never', got {self.donation!r}")
        if self.spec is None:
            from repro.core.vectorize import V5E
            object.__setattr__(self, "spec", V5E)

    # ------------------------------------------------------------------
    # identity
    # ------------------------------------------------------------------
    def constants(self) -> dict[str, Any]:
        """The tuning-relevant constants, JSON-ready."""
        spec_fields = sorted(
            (f, repr(getattr(self.spec, f)))
            for f in getattr(self.spec, "__dataclass_fields__", ()))
        return {"lane": self.lane, "sublane": self.sublane,
                "default_max_tile": list(self.default_max_tile),
                "spec": spec_fields}

    def to_json(self) -> dict[str, Any]:
        """Structural form for cache keying (see ``CompileCache``)."""
        return {"name": self.name,
                "capabilities": sorted(self.capabilities),
                "native_platforms": list(self.native_platforms),
                "requires_platform": self.requires_platform,
                "donation": self.donation,
                "staging_slack": self.staging_slack,
                "constants": self.constants()}

    def digest(self) -> str:
        """Stable digest of capabilities + constants.

        Compile and tuning caches key on this (via
        :meth:`cache_key`): a backend whose capability set or hardware
        constants change gets a fresh cache namespace, so a schedule
        measured for one target is never served to an incompatible
        one.
        """
        blob = json.dumps(self.to_json(), sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:12]

    def cache_key(self) -> str:
        """``name@digest`` — the string caches store for this backend."""
        return f"{self.name}@{self.digest()}"

    def with_spec(self, spec: Any) -> "Backend":
        """A copy of this record carrying ``spec`` as its constants.

        The calibration path (:func:`repro.backends.resolve_calibrated`)
        uses this to swap a fitted
        :class:`~repro.tune.calibrate.CalibratedSpec` in: the copy's
        :meth:`digest` — and therefore every compile/tuning cache key —
        reflects the new constants, while the registered (uncalibrated)
        record and its digest are untouched.
        """
        if spec is self.spec:
            return self
        return dataclasses.replace(self, spec=spec)

    # ------------------------------------------------------------------
    # capability gating
    # ------------------------------------------------------------------
    def supports(self, *caps: str) -> bool:
        return all(c in self.capabilities for c in caps)

    def missing(self, *caps: str) -> tuple[str, ...]:
        return tuple(sorted(set(caps) - self.capabilities))

    def require(self, *caps: str, context: str = "") -> None:
        """Raise :class:`UnsupportedBackendError` naming absent caps."""
        absent = self.missing(*caps)
        if absent:
            where = f" ({context})" if context else ""
            raise UnsupportedBackendError(
                f"backend {self.name!r} does not support "
                f"{', '.join(absent)}{where}; its capabilities are "
                f"{sorted(self.capabilities)}",
                backend=self.name, missing=absent)

    def available(self) -> bool:
        """True when the backend's platform requirement is met here."""
        if self.requires_platform is None:
            return True
        return _default_platform() == self.requires_platform

    def is_native(self) -> bool:
        """True when kernels compile natively on the current platform."""
        return _default_platform() in self.native_platforms

    # ------------------------------------------------------------------
    # policy resolution (the decisions consumers used to re-derive)
    # ------------------------------------------------------------------
    def resolve_interpret(self, interpret: bool | None) -> bool:
        """Resolve the interpret-vs-compiled mode.

        An explicit ``True``/``False`` wins; ``None`` defers to the
        backend: interpreted unless its kernels compile natively on
        the current platform (a pallas backend on a real TPU runs
        compiled; everywhere else — and for the XLA backends, which
        have no pallas kernels at all — the historical interpreted
        default is kept).  Interpreting on a platform where the
        backend compiles natively raises
        :class:`UnsupportedBackendError`: an interpreted kernel on the
        chip is never what a caller measuring that chip meant.
        """
        mode = not self.is_native() if interpret is None else bool(interpret)
        if mode and self.is_native():
            raise UnsupportedBackendError(
                f"backend {self.name!r} compiles natively on "
                f"{_default_platform()!r}; refusing to run its kernels "
                f"interpreted there (pass interpret=None or False)",
                backend=self.name, missing=("compiled",))
        return mode

    def resolve_donate(self, donate: bool, platform: str | None = None) -> bool:
        """Whether the batcher should build donating entries.

        ``donation="never"`` wins outright; ``"auto"`` donates except
        on CPU, where XLA categorically ignores donation and warns on
        every call.
        """
        if not donate or self.donation == "never":
            return False
        plat = platform if platform is not None else _default_platform()
        return plat != "cpu"

    def staging_depth(self, inflight: int) -> int:
        """Staging rotations the engine must allocate for ``inflight``
        concurrently unforced launches (zero-copy aliasing margin)."""
        return inflight + self.staging_slack

    # ------------------------------------------------------------------
    # lowering
    # ------------------------------------------------------------------
    def lower_group(self, group, *, spec: Any = None,
                    vector_factor: int | None = None,
                    interpret: bool | None = None,
                    valid_rows: tuple[int, int] | None = None) -> Callable:
        """Capability-check ``group`` then hand it to the lower hook.

        Every stage kind in the group must be in the capability set
        and the platform requirement must hold; violations raise the
        typed :class:`UnsupportedBackendError` before any lowering
        machinery runs.
        """
        kinds = {st.kind for st in group.stages}
        self.require(*sorted(kinds),
                     context="stages " + ",".join(s.name
                                                  for s in group.stages))
        if not self.available():
            raise UnsupportedBackendError(
                f"backend {self.name!r} requires platform "
                f"{self.requires_platform!r} but this host runs "
                f"{_default_platform()!r}; it is registered (capabilities "
                f"{sorted(self.capabilities)}) but cannot lower here",
                backend=self.name,
                missing=(f"platform:{self.requires_platform}",))
        if self.lower is None:
            raise UnsupportedBackendError(
                f"backend {self.name!r} has no lowering hook; it is a "
                f"registered stub awaiting an implementation",
                backend=self.name, missing=("lower",))
        return self.lower(group, backend=self,
                          spec=spec if spec is not None else self.spec,
                          vector_factor=vector_factor,
                          interpret=self.resolve_interpret(interpret),
                          valid_rows=valid_rows)

    def __repr__(self) -> str:  # keep logs/keys short and readable
        return f"Backend({self.name!r})"
