"""Communication/compute-overlap collectives (shard_map building blocks).

The FLOWER idea at cluster scale: a collective + matmul chain is a
2-stage dataflow pipeline, so it should *stream* — each ring step's
ppermute overlaps the previous chunk's matmul, instead of a barrier
all-gather followed by one big matmul.  On TPU the ring maps directly
onto ICI neighbours.

Property-tested against the barrier (einsum) versions in
tests/test_distribution.py (8 host devices, subprocess).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_allgather_matmul", "ring_matmul_reducescatter",
           "psum_scatter_grads", "halo_exchange_rows"]


def halo_exchange_rows(x: jnp.ndarray, hy: int, n_shards: int,
                       axis: str = "replica") -> jnp.ndarray:
    """Row-halo exchange for a row-partitioned 2-D plane (shard_map body).

    Each shard holds ``(H/k, W)`` rows; stencils near the cut need
    ``hy`` rows from the neighbouring shards.  The top shard's upper
    halo and the bottom shard's lower halo have no neighbour —
    ``ppermute`` leaves zeros there, which is exactly the compiler's
    zero-padding boundary semantics, so the replicated app reproduces
    the single-device app bit-for-bit.  With one shard both perms are
    empty and the whole halo is zeros: the single-device fallback runs
    the same code path CI exercises on CPU.
    """
    if hy == 0:
        return x
    # my bottom rows become the next shard's upper halo, and vice versa
    from_above = jax.lax.ppermute(
        x[-hy:], axis, [(j, j + 1) for j in range(n_shards - 1)])
    from_below = jax.lax.ppermute(
        x[:hy], axis, [(j + 1, j) for j in range(n_shards - 1)])
    return jnp.concatenate([from_above, x, from_below], axis=0)


def ring_allgather_matmul(x: jnp.ndarray, w: jnp.ndarray, mesh: Mesh,
                          axis: str = "model") -> jnp.ndarray:
    """Column-parallel matmul with streamed input all-gather.

    x: (m, k) row-sharded over ``axis`` (sequence-parallel residual);
    w: (k, n) col-sharded.  Returns (m, n) col-sharded.

    Instead of ``all_gather(x) @ w_local`` (a barrier), x's row blocks
    travel the ring; each arriving block is contracted immediately —
    P-1 ppermutes of an (m/P, k) tile hide behind P matmuls.
    """
    n_shards = mesh.shape[axis]

    def body(xs: jnp.ndarray, ws: jnp.ndarray) -> jnp.ndarray:
        idx = jax.lax.axis_index(axis)
        mb = xs.shape[0]                      # m/P local rows
        n_loc = ws.shape[1]
        out = jnp.zeros((mb * n_shards, n_loc), jnp.float32)
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

        def step(i, carry):
            out, blk = carry
            owner = (idx - i) % n_shards      # who produced blk
            part = jnp.dot(blk.astype(jnp.float32),
                           ws.astype(jnp.float32))
            out = jax.lax.dynamic_update_slice(out, part, (owner * mb, 0))
            blk = jax.lax.ppermute(blk, axis, perm)
            return out, blk

        out, _ = jax.lax.fori_loop(0, n_shards, step, (out, xs))
        return out.astype(x.dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis, None), P(None, axis)),
                   out_specs=P(None, axis), check_vma=False)
    return fn(x, w)


def ring_matmul_reducescatter(x: jnp.ndarray, w: jnp.ndarray, mesh: Mesh,
                              axis: str = "model") -> jnp.ndarray:
    """Row-parallel matmul with streamed output reduce-scatter.

    x: (m, k) col-sharded over ``axis``; w: (k, n) row-sharded.
    partial_p = x_p @ w_p needs a sum over shards; the output comes
    back row-sharded (sequence-parallel) — the reduce-scatter rides
    the ring, one (m/P, n) tile per step, overlapping the reduction
    adds with the neighbouring shards' sends.
    """
    n_shards = mesh.shape[axis]

    def body(xs, ws):
        idx = jax.lax.axis_index(axis)
        part = jnp.dot(xs.astype(jnp.float32), ws.astype(jnp.float32))
        m = part.shape[0]
        mb = m // n_shards
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

        def blk(i):
            # the acc held here at step i has P-1-i hops left; it ends
            # at shard idx-1-i, so add that destination's row block.
            owner = (idx - 1 - i) % n_shards
            return jax.lax.dynamic_slice_in_dim(part, owner * mb, mb, 0)

        acc = blk(0)

        def step(i, acc):
            acc = jax.lax.ppermute(acc, axis, perm)
            return acc + blk(i)

        acc = jax.lax.fori_loop(1, n_shards, step, acc)
        return acc.astype(x.dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, axis), P(axis, None)),
                   out_specs=P(axis, None), check_vma=False)
    return fn(x, w)


def psum_scatter_grads(grads, axis: str = "data"):
    """Leaf-wise reduce-scatter gradient sync (half the bytes of
    all-reduce) for use inside shard_map FSDP steps: each shard ends
    with the fully-reduced slice it owns and updates only that slice."""

    def one(g):
        return jax.lax.psum_scatter(g, axis, scatter_dimension=0,
                                    tiled=True)

    return jax.tree.map(one, grads)
