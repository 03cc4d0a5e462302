"""Continuous-batching correctness: ragged slots == isolated decoding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.models import model as M
from repro.runtime.batcher import ContinuousBatcher, Request


def _greedy_isolated(cfg, params, prompt, n_new, max_len=64):
    cache = M.init_cache(cfg, 1, max_len, dtype=jnp.float32)
    lg, cache = M.prefill(params, cfg, jnp.asarray(prompt)[None], cache)
    toks = []
    t = jnp.argmax(lg, -1).astype(jnp.int32)
    for _ in range(n_new):
        toks.append(int(t[0]))
        lg, cache = M.decode_step(params, cfg, t, cache)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
    return toks


def test_continuous_batching_matches_isolated():
    cfg = dataclasses.replace(get_smoke("granite_3_2b"),
                              capacity_factor=8.0)
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (5, 9, 7, 4)]
    n_new = 6

    batcher = ContinuousBatcher(cfg, params, n_slots=2, max_len=64)
    for i, p in enumerate(prompts):
        batcher.submit(Request(rid=i, prompt=p, max_new_tokens=n_new))
    finished = batcher.run_to_completion()
    assert len(finished) == len(prompts)

    for req in finished:
        ref = _greedy_isolated(cfg, params, req.prompt, n_new)
        assert req.tokens == ref, (req.rid, req.tokens, ref)


def test_batcher_overlaps_requests():
    """More requests than slots: later requests are admitted as soon
    as earlier ones retire (continuous, not lock-step)."""
    cfg = dataclasses.replace(get_smoke("mamba2_2p7b"),
                              capacity_factor=8.0)
    params = M.init(cfg, jax.random.PRNGKey(1))
    batcher = ContinuousBatcher(cfg, params, n_slots=2, max_len=48)
    rng = np.random.default_rng(1)
    for i in range(5):
        batcher.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=(4 + i,)
                                ).astype(np.int32),
            max_new_tokens=3 + i))
    finished = batcher.run_to_completion()
    assert sorted(r.rid for r in finished) == [0, 1, 2, 3, 4]
    for r in finished:
        assert len(r.tokens) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)


def test_finished_lists_retired_requests_in_retirement_order():
    """The batcher, not the slot pool it shares with StreamEngine, keeps
    what it retired: shorter budgets retire first and free their slot."""
    cfg = dataclasses.replace(get_smoke("mamba2_2p7b"),
                              capacity_factor=8.0)
    params = M.init(cfg, jax.random.PRNGKey(2))
    batcher = ContinuousBatcher(cfg, params, n_slots=2, max_len=48)
    prompt = np.arange(4, dtype=np.int32)
    for rid, budget in enumerate((4, 2, 2)):
        batcher.submit(Request(rid=rid, prompt=prompt,
                               max_new_tokens=budget))
    finished = batcher.run_to_completion()
    assert finished is batcher.finished
    assert [r.rid for r in finished] == [1, 2, 0]
    assert not hasattr(batcher.pool, "finished")
