"""Pipeline parallelism: GPipe-style microbatched execution over a
``stage`` mesh axis via shard_map + ppermute.

This is FLOWER's dataflow pipeline at the *device* scale: stages are
devices, the FIFO channel is the ICI link between neighbours, the
items are microbatches.  The same latency law applies (and is asserted
in tests): total steps = n_micro + n_stages - 1, versus
n_micro * n_stages for sequential execution.

Off by default in the 40-cell table (the production mesh spends its
axes on DP×TP); enable by building a mesh with a ``stage`` axis and
wrapping the per-layer body with :func:`pipeline_apply`.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, params_stacked, x: jnp.ndarray,
                   mesh: Mesh, n_micro: int, axis: str = "stage"
                   ) -> jnp.ndarray:
    """Run ``x`` through ``n_stages`` sequential stages, pipelined.

    stage_fn(params_stage, x_micro) -> x_micro  (same shape)
    params_stacked: pytree with leading dim n_stages (sharded over
    ``axis``); x: (batch, ...) with batch % n_micro == 0.

    GPipe schedule: microbatch m enters stage s at step m + s; each
    device runs its stage every step on whatever the ring delivered,
    for n_micro + n_stages - 1 steps total (the Fig.-1 law).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    def body(params_local, xs):
        # params_local: stage's own params (leading dim 1); xs: the
        # full local copy of the batch (replicated over `axis`).
        sid = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        n_steps = n_micro + n_stages - 1

        micro = xs.reshape(n_micro, mb, *xs.shape[1:])
        out = jnp.zeros_like(micro)
        # `hold` is the activation each device currently owns
        hold = jnp.zeros((mb,) + xs.shape[1:], xs.dtype)

        def step(t, carry):
            hold, out = carry
            # stage 0 injects microbatch t (if any remain)
            inject = micro[jnp.clip(t, 0, n_micro - 1)]
            hold = jnp.where(sid == 0,
                             jnp.where(t < n_micro, inject,
                                       jnp.zeros_like(inject)), hold)
            y = stage_fn(p, hold)
            # last stage retires microbatch t - (n_stages - 1)
            mi = t - (n_stages - 1)
            out = jnp.where(
                (sid == n_stages - 1) & (mi >= 0) & (mi < n_micro),
                jax.lax.dynamic_update_slice(
                    out, y[None], (jnp.clip(mi, 0, n_micro - 1), 0)
                    + (0,) * (y.ndim - 1)),
                out)
            # FIFO hand-off to the next stage
            y = jax.lax.ppermute(y, axis, perm)
            return y, out

        hold, out = jax.lax.fori_loop(0, n_steps, step, (hold, out))
        # only the last stage holds real outputs; broadcast them back
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(B, *xs.shape[1:])

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis), P()),
                   out_specs=P(), check_vma=False)
    return fn(params_stacked, x)
