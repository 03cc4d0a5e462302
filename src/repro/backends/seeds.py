"""The seed backends: the registry entries the repo ships with.

``xla``, ``xla_staged`` and ``pallas`` are the paper's three lowering
regimes (portable baseline, AnyHLS-style staged baseline, the fused
streaming artifact) with behaviour bit-identical to the pre-registry
if/elif chains.  ``pallas_gpu`` is the proof that a fourth target is a
registry entry, not a repo-wide grep: it registers, reports its
capabilities, and is rejected with a typed
:class:`~repro.backends.spec.UnsupportedBackendError` — never a crash
— when asked to lower something it cannot serve (a stencil stage, or
any stage on a host without a GPU).
"""
from __future__ import annotations

from typing import Any, Callable

from repro.backends.registry import register
from repro.backends.spec import Backend, STAGE_KINDS

__all__ = ["XLA", "XLA_STAGED", "PALLAS", "PALLAS_GPU", "SEED_BACKENDS"]


# ----------------------------------------------------------------------
# lower hooks: thin adapters over the kernel generators in core.fusion
# ----------------------------------------------------------------------
def _lower_xla(group, *, backend: Backend, spec: Any,
               vector_factor: int | None, interpret: bool,
               valid_rows: tuple[int, int] | None,
               staged: bool = False) -> Callable:
    from repro.core.fusion import lower_group_xla
    return lower_group_xla(group, staged=staged, valid_rows=valid_rows)


def _lower_xla_staged(group, **kw) -> Callable:
    # custom/reduce groups are single opaque stages: there is nothing to
    # stage *between*, and the plain composition is what the
    # pre-registry chain ran for them on every backend
    return _lower_xla(group, staged=group.kind != "custom", **kw)


def _lower_pallas(group, *, backend: Backend, spec: Any,
                  vector_factor: int | None, interpret: bool,
                  valid_rows: tuple[int, int] | None) -> Callable:
    from repro.core.fusion import lower_group_pallas, lower_group_xla
    if not group.is_kernel:
        # custom/reduce singletons have no streaming tile structure and
        # run as host-composed jnp on every backend; a group of splits
        # only hands its input on (the readers share one HBM buffer)
        return lower_group_xla(group, staged=False, valid_rows=valid_rows)
    return lower_group_pallas(group, spec, interpret=interpret,
                              vector_factor=vector_factor,
                              valid_rows=valid_rows)


def _tuner_measure(graph, backend, config, **kw) -> float:
    """Default measurement harness: lower under ``config`` and time on
    the live backend (:func:`repro.tune.search.default_measure`).
    Lazy import: the spec layer must not depend on the tuner."""
    from repro.tune.search import default_measure
    return default_measure(graph, backend, config, **kw)


# ----------------------------------------------------------------------
# the registered seeds
# ----------------------------------------------------------------------
XLA = register(Backend(
    name="xla",
    description="portable baseline: stages composed as jnp ops, "
                "XLA's own fuser handles them",
    capabilities=frozenset(STAGE_KINDS) | {"tuning", "replication"},
    native_platforms=(),          # no pallas kernels: interpret is inert
    lower=_lower_xla,
    measure=_tuner_measure,
))

XLA_STAGED = register(Backend(
    name="xla_staged",
    description="AnyHLS/no-dataflow baseline: optimization barrier "
                "after every stage, each intermediate round-trips HBM",
    capabilities=frozenset(STAGE_KINDS)
    | {"tuning", "replication", "staged_hbm"},
    native_platforms=(),
    lower=_lower_xla_staged,
    measure=_tuner_measure,
))

PALLAS = register(Backend(
    name="pallas",
    description="THE paper artifact: one fused streaming Pallas kernel "
                "per fusion group (interpreted off-TPU)",
    capabilities=frozenset(STAGE_KINDS)
    | {"tuning", "replication", "fused_streaming"},
    native_platforms=("tpu",),
    lower=_lower_pallas,
    measure=_tuner_measure,
))

#: registered but capability-gated: declares what a Mosaic-GPU/Triton
#: lowering WILL serve (elementwise pipelines first), requires a GPU,
#: and has no lower hook yet — every rejection is a typed
#: UnsupportedBackendError naming the missing capability or platform.
PALLAS_GPU = register(Backend(
    name="pallas_gpu",
    description="Mosaic GPU / Triton target (stub): elementwise "
                "pipelines only, gated on a GPU host",
    capabilities=frozenset({"point", "pointN", "split", "tuning"}),
    native_platforms=("gpu", "cuda", "rocm"),
    requires_platform="gpu",
    lower=None,
    measure=_tuner_measure,
))

#: the lowerable seed trio — what tests/benchmarks sweep; the gated
#: ``pallas_gpu`` stub is registered but intentionally NOT in this
#: tuple (it cannot lower on non-GPU hosts)
SEED_BACKENDS = ("xla", "xla_staged", "pallas")
