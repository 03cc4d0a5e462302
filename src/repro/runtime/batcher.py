"""Continuous-batching serving scheduler.

Real serving fleets don't run lock-step batches: requests arrive and
finish at different times.  This scheduler keeps a fixed pool of
decode *slots* (the jitted decode step never re-compiles), admits new
requests into free slots between steps, and retires sequences on EOS
or length budget — the dataflow view of serving: the decode step is a
pipeline stage, slots are its channels.

Per-slot state lives in the shared cache via a position vector: every
slot decodes against its own history length (the attention bias uses
per-slot lengths, not the global index), so sequences of different
ages coexist in one batch.

Pure-JAX + host scheduling; works with every assigned architecture
that exposes attention caches (SSM-state archs need per-slot state
reset on admit, also handled).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.runtime.slots import SlotPool

__all__ = ["Request", "ContinuousBatcher"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                   # -1: run to the length budget
    # filled by the batcher:
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Fixed-slot continuous batching on top of prefill/decode.

    Simplification vs a full paged server: prompts are prefilled one
    slot at a time (B=1 prefill into the slot's cache rows), decode
    runs across all active slots every step.  Cache layout is the
    stacked (layers, B, ...) tree from ``M.init_cache``.
    """

    def __init__(self, cfg: ModelConfig, params, n_slots: int,
                 max_len: int, dtype=jnp.float32):
        self.cfg, self.params = cfg, params
        self.n_slots, self.max_len = n_slots, max_len
        self.cache = M.init_cache(cfg, n_slots, max_len, dtype=dtype)
        # per-slot sequence lengths (host copy is the scheduler truth)
        self.lengths = np.zeros(n_slots, np.int32)
        # slot occupancy / admission queue / retirement: the machinery
        # shared with the dataflow StreamEngine (see runtime/slots.py)
        self.pool: SlotPool = SlotPool(n_slots)
        #: retired requests, in retirement order
        self.finished: list[Request] = []
        self._decode = jax.jit(self._decode_step)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.pool.submit(req)

    @property
    def active(self) -> int:
        return self.pool.active

    @property
    def queue(self):
        return self.pool.queue

    @property
    def slot_req(self) -> list[Request | None]:
        return self.pool.slots

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Prefill queued requests into free slots (one at a time)."""
        for slot, req in self.pool.admit():
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]
            tmp_cache = M.init_cache(self.cfg, 1, self.max_len,
                                     dtype=jnp.float32)
            logits, tmp_cache = M.prefill(self.params, self.cfg, prompt,
                                          tmp_cache)
            self._copy_slot(tmp_cache, slot)
            tok = int(jnp.argmax(logits[0], -1))
            req.tokens.append(tok)
            self.lengths[slot] = len(req.prompt)

    def _copy_slot(self, src_cache, slot: int) -> None:
        """Copy a B=1 cache into slot ``slot`` of the pool cache."""

        def copy(pool, one):
            if pool.ndim == 0 or one.ndim == 0 or pool.ndim != one.ndim:
                return pool
            # the batch axis is the one where pool has n_slots, the
            # B=1 cache has 1, and every other dim matches (axis 1 for
            # stacked (layers, B, ...) leaves, axis 0 for enc_out).
            axis = None
            for a in range(pool.ndim):
                if (pool.shape[a] == self.n_slots and one.shape[a] == 1
                        and pool.shape[:a] == one.shape[:a]
                        and pool.shape[a + 1:] == one.shape[a + 1:]):
                    axis = a
                    break
            if axis is None:
                return pool
            idx = [slice(None)] * pool.ndim
            idx[axis] = slice(slot, slot + 1)
            return pool.at[tuple(idx)].set(one.astype(pool.dtype))

        self.cache = jax.tree.map(
            copy, self.cache,
            {k: v for k, v in src_cache.items() if k != "index"}
            | {"index": jnp.zeros((), jnp.int32)})

    # ------------------------------------------------------------------
    def _decode_step(self, params, cache, tokens, lengths):
        """One decode step with PER-SLOT lengths: the model's vector
        cache-index path writes each slot's KV at its own position and
        masks attention per slot (see layers.attention_block)."""
        cache = dict(cache)
        cache["index"] = lengths
        logits, cache = M.decode_step(params, self.cfg, tokens, cache)
        return logits, cache

    def step(self) -> int:
        """Admit, decode once for all active slots, retire finished.

        Returns the number of tokens produced this step."""
        self._admit()
        if self.active == 0:
            return 0
        tokens = np.zeros(self.n_slots, np.int32)
        for i, r in enumerate(self.slot_req):
            if r is not None:
                tokens[i] = r.tokens[-1]
        logits, new_cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.lengths))
        # keep host lengths authoritative (the jitted step +1s them all,
        # including idle slots; we re-install our own vector next step)
        self.cache = new_cache
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        produced = 0
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            self.lengths[i] += 1
            r.tokens.append(int(nxt[i]))
            produced += 1
            over = len(r.tokens) >= r.max_new_tokens
            eos = r.eos_id >= 0 and int(nxt[i]) == r.eos_id
            if over or eos or self.lengths[i] >= self.max_len - 1:
                r.done = True
        # continuous refill: reap every finished sequence's slot (the
        # machinery shared with StreamEngine's in-flight launch pool),
        # then the next _admit() backfills them without a drain barrier
        for slot in self.pool.ready(lambda r: r.done):
            self.finished.append(self.pool.retire(slot))
            self.lengths[slot] = 0
        return produced

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished
