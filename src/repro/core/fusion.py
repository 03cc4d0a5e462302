"""Top-level kernel generation (FLOWER contribution C2).

Lowers a :class:`FusionGroup` to one of three backends:

- ``xla``        — the stages composed as ordinary jnp ops; XLA's own
                   fuser handles them (portable backend #1).
- ``xla_staged`` — same, but with ``lax.optimization_barrier`` after
                   every stage so each intermediate materializes to HBM.
                   This reproduces the paper's *AnyHLS / no-dataflow*
                   baseline: disjoint per-stage kernels with a global
                   memory round-trip between stages.
- ``pallas``     — THE paper artifact: one fused streaming kernel.  The
                   grid walks output tiles; each grid step DMAs an
                   (optionally halo-expanded) tile of every group input
                   HBM→VMEM (the generated *read task* / burst
                   transfer), pushes it through all stages in
                   topological order inside VMEM (tasks connected by
                   depth-2 FIFOs == Pallas' double-buffered pipeline),
                   and DMAs the output tile back (the *write task*).

Boundary semantics are zero-padding and are *bit-exact* across all
three backends: inside the fused kernel, every stage output is masked
to zero outside the logical image domain, which reproduces exactly the
reference's per-stage ``jnp.pad`` behaviour at tile borders.

A ``resample`` group (one REDUCE or EXPAND) is a ``pallas_call`` of
its own whose grid walks the *output's* tiles: a REDUCE step reads a
fine block twice the tile plus its halo, an EXPAND step a coarse block
half the tile plus its halo.  The prefiltered fine plane of a REDUCE
and the zero-stuffed plane of an EXPAND exist only in VMEM.  Rows are
picked or placed with sublane-strided loads and stores; columns with a
0/1 selection matmul at ``Precision.HIGHEST``, which moves each float32
value exactly, so the taps still run in the reference's order and the
result stays bit-exact.

In :func:`lower_graph` each group's pad, call and crop run under
``jax.named_scope(<app>_g<k>)``, so the wrapper ops around a kernel
carry its name in the compiled program's op names.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import (Channel, DataflowGraph, GraphError, Stage,
                              _apply_stage_reference, resample_up)
from repro.core.schedule import (FusionGroup, Schedule, build_schedule,
                                 split_root)
from repro.core.vectorize import LANE, SUBLANE, TPUSpec, select_tile

__all__ = ["lower_group", "lower_graph", "BACKENDS"]

#: the lowerable seed backends (kept as a tuple for the historical
#: sweep idiom); the authoritative list is the registry
#: (:func:`repro.backends.names`), which also holds gated stubs
BACKENDS = ("xla", "xla_staged", "pallas")


# ----------------------------------------------------------------------
# XLA backends
# ----------------------------------------------------------------------
def lower_group_xla(group: FusionGroup, staged: bool = False,
                    valid_rows: tuple[int, int] | None = None) -> Callable:
    """Compose the group's stages as whole-array jnp ops.

    With ``staged=True`` an optimization barrier follows every stage, so
    XLA cannot fuse across stages — each intermediate round-trips
    through HBM, exactly like AnyHLS' disjoint IP blocks.

    ``valid_rows=(r0, r1)`` narrows the logical image to that row band:
    every stage output is zeroed outside it, reproducing the per-stage
    zero-padding semantics of a *window* of a larger plane.  The
    replicator (:mod:`repro.parallel.replicate`) uses this for shards
    at the global top/bottom edge.
    """

    def run(env_in: dict[Channel, Any]) -> dict[Channel, Any]:
        env = dict(env_in)
        for st in group.stages:
            vals = [env[c] for c in st.inputs]
            outs = _apply_stage_reference(st, vals)
            outs = [o.astype(c.dtype) for o, c in zip(outs, st.outputs)]
            if valid_rows is not None:
                outs = [_window_rows(o, valid_rows) for o in outs]
            if staged:
                outs = list(lax.optimization_barrier(tuple(outs)))
            for ch, v in zip(st.outputs, outs):
                env[ch] = v
        return {ch: env[ch] for ch in group.outputs}

    return run


def _window_rows(x, valid_rows: tuple[int, int]):
    """Zero rows of a 2-D plane outside the [r0, r1) band."""
    if getattr(x, "ndim", 0) != 2:
        return x
    r0, r1 = valid_rows
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where((rows >= r0) & (rows < r1), x, jnp.zeros_like(x))


# ----------------------------------------------------------------------
# Pallas streaming backend (the generated top-level kernel)
# ----------------------------------------------------------------------
def lower_group_pallas(group: FusionGroup, spec: TPUSpec, *,
                       interpret: bool,
                       vector_factor: int | None = None,
                       valid_rows: tuple[int, int] | None = None) -> Callable:
    """One streaming ``pallas_call`` for a fused group.

    Every input block meets Mosaic's tiling rule: the halo-expanded
    window ``(th + 2hy, tw + 2hx)`` is rounded up to whole
    ``(SUBLANE, LANE)`` tiles, the host pad supplies the extra rows and
    columns, and the kernel crops the window back before the first
    stage.  ``spec.vmem_bytes`` is both the budget the tile picker fits
    into and the scoped-VMEM limit handed to Mosaic.  The kernel is
    named after the group (``<app>_g<k>``).
    """
    if not group.is_kernel:
        raise GraphError(f"cannot pallas-lower a {group.kind} group")
    if group.kind == "resample":
        return _lower_resample_pallas(group, spec, interpret=interpret,
                                      vector_factor=vector_factor,
                                      valid_rows=valid_rows)
    tile = group.tile or select_tile(group, spec, vector_factor)[0]
    th, tw = tile
    H, W = group.stages[0].outputs[0].shape
    Hp, Wp = _round_up(H, th), _round_up(W, tw)
    grid = (Hp // th, Wp // tw)
    rows = valid_rows if valid_rows is not None else (0, H)

    # a split's copies are one buffer: an output that copies a group
    # input is that input, and a plane fanned out is written once
    inside = set(group.stages)
    roots = {ch: split_root(ch, inside) for ch in group.outputs}
    written = list(dict.fromkeys(r for r in roots.values()
                                 if r not in group.inputs))

    blocks = [_input_block(tile, group.halo.get(ch, (0, 0)))
              for ch in group.inputs]
    in_specs = [pl.BlockSpec(tuple(pl.Element(s) for s in blk),
                             functools.partial(_in_index, th=th, tw=tw))
                for blk in blocks]
    out_specs = [pl.BlockSpec((th, tw), lambda i, j: (i, j))
                 for _ in written]
    out_shapes = [jax.ShapeDtypeStruct((Hp, Wp), ch.dtype)
                  for ch in written]

    kernel = functools.partial(
        _group_kernel, group=group, outputs=written, tile=tile,
        plane=(H, W), n_in=len(group.inputs), rows=rows)

    call = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes, interpret=interpret, name=group.name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(spec.vmem_bytes)))

    def run(env_in: dict[Channel, Any]) -> dict[Channel, Any]:
        ins = []
        for ch, (bh, bw) in zip(group.inputs, blocks):
            hy, hx = group.halo.get(ch, (0, 0))
            x = jnp.asarray(env_in[ch], dtype=ch.dtype)
            # The generated read task: zero-pad by the cumulative halo
            # and out to where the last aligned block ends; each grid
            # step then bursts a contiguous (bh, bw) block into VMEM.
            x = jnp.pad(x, ((hy, Hp - th + bh - H - hy),
                            (hx, Wp - tw + bw - W - hx)))
            ins.append(x)
        outs = call(*ins)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        planes = {ch: o[:H, :W] for ch, o in zip(written, outs)}
        return {ch: env_in[r] if r in group.inputs else planes[r]
                for ch, r in roots.items()}

    return run


def _input_block(tile: tuple[int, int], halo: tuple[int, int]
                ) -> tuple[int, int]:
    """The VMEM block an input with ``halo`` is DMA'd in: the expanded
    window rounded up to whole (SUBLANE, LANE) tiles."""
    th, tw = tile
    hy, hx = halo
    return (_round_up(th + 2 * hy, SUBLANE), _round_up(tw + 2 * hx, LANE))


def _in_index(i, j, *, th, tw):
    # Element-indexed: the block's top-left corner in the *padded* input
    # is (i*th, j*tw); with the host-side pad of (hy, hx) this centers
    # the halo window on the output tile.
    return (i * th, j * tw)


def _group_kernel(*refs, group: FusionGroup, outputs: list[Channel],
                  tile: tuple[int, int], plane: tuple[int, int], n_in: int,
                  rows: tuple[int, int]) -> None:
    th, tw = tile
    H, W = plane
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    i = pl.program_id(0)
    j = pl.program_id(1)

    env: dict[Channel, Any] = {}
    for ch, ref in zip(group.inputs, in_refs):
        # crop the aligned block back to the halo-expanded window
        hy, hx = group.halo.get(ch, (0, 0))
        env[ch] = ref[...][:th + 2 * hy, :tw + 2 * hx]

    halo = group.halo
    for st in group.stages:  # already in topological order
        oh = _stage_out_halo(st, halo)
        vals = []
        for ch in st.inputs:
            need = (oh[0] + st.halo[0], oh[1] + st.halo[1])
            vals.append(_crop(env[ch], halo.get(ch, (0, 0)), need, th, tw))
        outs = _apply_stage_tile(st, vals, oh, th, tw)
        for ch, v in zip(st.outputs, outs):
            ch_halo = halo.get(ch, (0, 0))
            v = _crop(v, oh, ch_halo, th, tw).astype(ch.dtype)
            # zero outside the logical image: reproduces per-stage
            # zero-padding semantics bit-exactly at tile borders.
            env[ch] = _mask_to_image(v, ch_halo, i, j, th, tw, rows, W)

    for ch, ref in zip(outputs, out_refs):
        ref[...] = _crop(env[ch], halo.get(ch, (0, 0)), (0, 0), th, tw)


# ----------------------------------------------------------------------
# resampling kernels (REDUCE / EXPAND)
# ----------------------------------------------------------------------
def _lower_resample_pallas(group: FusionGroup, spec: TPUSpec, *,
                           interpret: bool, vector_factor: int | None,
                           valid_rows: tuple[int, int] | None) -> Callable:
    """One ``pallas_call`` for a REDUCE or EXPAND stage.

    The grid walks the output's tiles.  A REDUCE step reads the fine
    block under its tile, ``(2th + 2hy, 2tw + 2hx)`` rounded to whole
    (SUBLANE, LANE) tiles, as 128-lane column chunks (a sublane-strided
    read needs a 128-lane base); an EXPAND step reads the coarse block
    ``(th/2 + 2cy, tw/2 + 2cx)``, ``c = ceil(h/2)``.  The host pads
    each input by its halo and out to the last block, as for a fused
    group, and crops the output.
    """
    if valid_rows is not None:
        raise GraphError("a resampling stage changes the plane's rows; "
                         "it cannot run on a band of a larger plane")
    st = group.stages[0]
    src, dst = st.inputs[0], st.outputs[0]
    tile = group.tile or select_tile(group, spec, vector_factor)[0]
    th, tw = tile
    H, W = dst.shape
    Hp, Wp = _round_up(H, th), _round_up(W, tw)
    hy, hx = st.halo
    if resample_up(st):
        cy, cx = -(-hy // 2), -(-hx // 2)
        step = (th // 2, tw // 2)
        block = _input_block(step, (cy, cx))
        in_specs = [pl.BlockSpec(tuple(pl.Element(s) for s in block),
                                 lambda i, j: (i * (th // 2), j * (tw // 2)))]
        kernel = functools.partial(_expand_kernel, st=st, tile=tile,
                                   offset=(2 * cy - hy, 2 * cx - hx))
        scratch = [pltpu.VMEM((2 * block[0], LANE), jnp.float32)
                   for _ in range(2 * block[1] // LANE)]
        lead = (cy, cx)
    else:
        step = (2 * th, 2 * tw)
        block = _input_block(step, (hy, hx))
        n_chunks = block[1] // LANE
        in_specs = [pl.BlockSpec((pl.Element(block[0]), pl.Element(LANE)),
                                 functools.partial(_chunk_index, step=step,
                                                   k=k))
                    for k in range(n_chunks)]
        kernel = functools.partial(_reduce_kernel, st=st, tile=tile,
                                   n_in=n_chunks)
        scratch = [pltpu.VMEM((st.window[0], th, block[1]), src.dtype)]
        lead = (hy, hx)
    grid = (Hp // th, Wp // tw)
    call = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((th, tw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Hp, Wp), dst.dtype),
        scratch_shapes=scratch, interpret=interpret, name=group.name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(spec.vmem_bytes)))
    n_refs = len(in_specs)
    # the padded input covers the last block: grid * step + the block's
    # overhang, starting ``lead`` samples before the plane
    rows_total = (grid[0] - 1) * step[0] + block[0]
    cols_total = (grid[1] - 1) * step[1] + block[1]
    h_in, w_in = src.shape

    def run(env_in: dict[Channel, Any]) -> dict[Channel, Any]:
        x = jnp.asarray(env_in[src], dtype=src.dtype)
        x = jnp.pad(x, ((lead[0], rows_total - h_in - lead[0]),
                        (lead[1], cols_total - w_in - lead[1])))
        return {dst: call(*[x] * n_refs)[:H, :W]}

    return run


def _chunk_index(i, j, *, step, k):
    # element offsets that Mosaic can prove whole (SUBLANE, LANE) tiles
    return (i * step[0], (j * (step[1] // LANE) + k) * LANE)


def _reduce_kernel(*refs, st: Stage, tile: tuple[int, int],
                   n_in: int) -> None:
    th, tw = tile
    kh, kw = st.window
    chunks, out_ref, rows_ref = refs[:n_in], refs[n_in], refs[n_in + 1]
    # rows_ref[a]: the window's even rows from row offset a, every
    # column.  The taps read them back from VMEM like any block, so
    # they run exactly as in a stencil kernel.
    for a in range(kh):
        for k, c in enumerate(chunks):
            rows_ref[a, :, k * LANE:(k + 1) * LANE] = \
                c[pl.ds(a, th, stride=2), :]
    views = [rows_ref[a][:, b:b + 2 * tw]
             for a in range(kh) for b in range(kw)]
    fine = st.fn(jnp.stack(views, axis=0))        # (th, 2tw)
    out_ref[...] = _even_columns(fine).astype(out_ref.dtype)


def _expand_kernel(in_ref, out_ref, *stuffed, st: Stage,
                   tile: tuple[int, int], offset: tuple[int, int]) -> None:
    th, tw = tile
    kh, kw = st.window
    dy, dx = offset
    wide = _spread_columns(in_ref[...])          # x on the even columns
    n = in_ref.shape[0]
    for k, ref in enumerate(stuffed):            # and on the even rows
        ref[...] = jnp.zeros_like(ref)
        ref[pl.ds(0, n, stride=2), :] = wide[:, k * LANE:(k + 1) * LANE]
    up = jnp.concatenate([ref[...] for ref in stuffed], axis=1)
    up = up.astype(st.inputs[0].dtype)
    views = [up[dy + a:dy + a + th, dx + b:dx + b + tw]
             for a in range(kh) for b in range(kw)]
    out_ref[...] = st.fn(jnp.stack(views, axis=0)).astype(out_ref.dtype)


def _select(x, sel):
    return jnp.dot(x.astype(jnp.float32), sel, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _even_columns(x):
    """``x[:, ::2]``: a 0/1 selection matmul per 256 columns."""
    r = lax.broadcasted_iota(jnp.int32, (2 * LANE, LANE), 0)
    c = lax.broadcasted_iota(jnp.int32, (2 * LANE, LANE), 1)
    sel = (r == 2 * c).astype(jnp.float32)
    return jnp.concatenate(
        [_select(x[:, m:m + 2 * LANE], sel)
         for m in range(0, x.shape[1], 2 * LANE)], axis=1)


def _spread_columns(x):
    """``x`` on the even columns of a plane twice as wide, zeros on the
    odd: a 0/1 selection matmul per 128 columns."""
    r = lax.broadcasted_iota(jnp.int32, (LANE, 2 * LANE), 0)
    c = lax.broadcasted_iota(jnp.int32, (LANE, 2 * LANE), 1)
    sel = (c == 2 * r).astype(jnp.float32)
    return jnp.concatenate(
        [_select(x[:, m:m + LANE], sel) for m in range(0, x.shape[1], LANE)],
        axis=1)


def _stage_out_halo(st: Stage, halo: dict[Channel, tuple[int, int]]
                    ) -> tuple[int, int]:
    hs = [halo.get(ch, (0, 0)) for ch in st.outputs]
    return (max(h[0] for h in hs), max(h[1] for h in hs))


def _crop(x, have: tuple[int, int], need: tuple[int, int],
          th: int, tw: int):
    dy, dx = have[0] - need[0], have[1] - need[1]
    if dy < 0 or dx < 0:
        raise GraphError(f"halo underflow: have {have}, need {need}")
    if dy == 0 and dx == 0:
        return x
    return x[dy:dy + th + 2 * need[0], dx:dx + tw + 2 * need[1]]


def _apply_stage_tile(st: Stage, vals: list, oh: tuple[int, int],
                      th: int, tw: int) -> list:
    if st.kind == "point":
        return [st.fn(vals[0])]
    if st.kind == "pointN":
        return [st.fn(*vals)]
    if st.kind == "split":
        return [vals[0] for _ in st.outputs]
    if st.kind == "stencil":
        kh, kw = st.window
        x = vals[0]  # (th + 2(oh+sh), tw + 2(ow+sw))
        out_h, out_w = th + 2 * oh[0], tw + 2 * oh[1]
        views = [x[di:di + out_h, dj:dj + out_w]
                 for di in range(kh) for dj in range(kw)]
        patches = jnp.stack(views, axis=0)
        return [st.fn(patches)]
    raise GraphError(f"stage kind {st.kind!r} is not tile-streamable")


def _mask_to_image(v, oh: tuple[int, int], i, j, th: int, tw: int,
                   row_band: tuple[int, int], W: int):
    eh, ew = th + 2 * oh[0], tw + 2 * oh[1]
    r0, r1 = row_band
    rows = lax.broadcasted_iota(jnp.int32, (eh, ew), 0) + i * th - oh[0]
    cols = lax.broadcasted_iota(jnp.int32, (eh, ew), 1) + j * tw - oh[1]
    ok = (rows >= r0) & (rows < r1) & (cols >= 0) & (cols < W)
    return jnp.where(ok, v, jnp.zeros_like(v))


# ----------------------------------------------------------------------
# whole-graph lowering
# ----------------------------------------------------------------------
def lower_group(group: FusionGroup, backend, spec: TPUSpec | None = None,
                vector_factor: int | None = None,
                interpret: bool | None = None,
                valid_rows: tuple[int, int] | None = None) -> Callable:
    """Lower one fusion group through the backend registry.

    ``backend`` is a registered name or a
    :class:`~repro.backends.Backend` spec; the resolved record
    capability-checks the group's stage kinds, resolves the
    interpret-vs-compiled mode, and dispatches its ``lower`` hook.
    ``valid_rows`` applies to trivial groups too: a 2-D custom/reduce
    output outside the row band must read as zero downstream
    (``_window_rows`` no-ops on non-2-D outputs).
    """
    from repro.backends import resolve
    be = resolve(backend)
    return be.lower_group(group, spec=spec, vector_factor=vector_factor,
                          interpret=interpret, valid_rows=valid_rows)


def lower_graph(graph: DataflowGraph, backend="pallas",
                schedule: Schedule | None = None,
                spec: TPUSpec | None = None,
                vector_factor: int | None = None,
                interpret: bool | None = None, *,
                canonicalize: bool = True, strict: bool = False,
                max_tile: tuple[int, int] | None = None,
                valid_rows: tuple[int, int] | None = None,
                ) -> tuple[Callable, Schedule]:
    """Lower a whole dataflow graph; returns ``(run, schedule)``.

    ``run`` maps ``{input_name: array} -> {output_name: array}`` and is
    jit-compatible.  One source program, any backend — the paper's
    portability claim (Fig. 8/9) maps to ``backend=`` here: a
    registered name or a :class:`~repro.backends.Backend` spec, whose
    constants also seed the schedule (VMEM budget, tile cap) when no
    explicit ``spec``/``max_tile`` is passed.  Unless a pre-built
    ``schedule`` is passed (the compiler driver and the autotuner both
    pass one, with tiles already selected and provenance-labeled), the
    graph first goes through the canonicalization pass pipeline
    (``strict=True`` to enforce the explicit canonical form instead;
    see :func:`repro.core.schedule.build_schedule`); ``max_tile`` then
    caps the tile shapes the schedule may select.
    """
    from repro.backends import resolve
    be = resolve(backend)
    sched = schedule or build_schedule(graph, canonicalize=canonicalize,
                                       strict=strict, spec=spec,
                                       vector_factor=vector_factor,
                                       max_tile=max_tile, backend=be)
    graph = sched.graph
    fns = [be.lower_group(g, spec=spec, vector_factor=vector_factor,
                          interpret=interpret, valid_rows=valid_rows)
           for g in sched.groups]

    def run(inputs: dict[str, Any]) -> dict[str, Any]:
        env: dict[Channel, Any] = {}
        for ch in graph.graph_inputs:
            env[ch] = jnp.asarray(inputs[ch.name], dtype=ch.dtype)
        for fn, g in zip(fns, sched.groups):
            with jax.named_scope(g.name):
                outs = fn({ch: env[ch] for ch in g.inputs})
            env.update(outs)
        return {ch.name: env[ch] for ch in graph.graph_outputs}

    return run, sched


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
