"""End-to-end behaviour: train->learn->checkpoint->resume, serve."""
import dataclasses
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def test_train_learns_and_resumes(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = get_smoke("granite_3_2b")
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32,
                       global_batch=8)
    opt = AdamWConfig(lr_peak=1e-2, warmup_steps=5, decay_steps=40)
    tc = TrainerConfig(total_steps=25, ckpt_every=10, ckpt_dir=ckpt_dir,
                       log_every=1000)
    tr = Trainer(cfg, opt, tc, data)
    hist = tr.run()
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.5, "did not learn"

    # resume picks up from the last checkpoint
    tr2 = Trainer(cfg, opt, tc, data)
    assert tr2.step >= 20
    h2 = tr2.run(steps=28)
    assert h2, "no steps after resume"
    assert h2[-1]["loss"] < hist[0]["loss"]


def test_train_all_families_one_step():
    """One optimizer step on every family (weights actually move)."""
    for arch in ("granite_moe_3b_a800m", "mamba2_2p7b", "zamba2_1p2b",
                 "whisper_base", "internvl2_26b", "minicpm3_4b"):
        cfg = get_smoke(arch)
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                           global_batch=4)
        from repro.optim.adamw import adamw_init
        from repro.runtime.steps import make_train_step
        params = M.init(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw_init(params)}
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        if cfg.family == "encdec":
            batch["enc_embeds"] = jnp.zeros(
                (4, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            batch["extra_embeds"] = jnp.zeros(
                (4, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
        step = jax.jit(make_train_step(cfg, AdamWConfig(lr_peak=1e-3,
                                                        warmup_steps=1,
                                                        decay_steps=10)))
        new_state, metrics = step(state, batch)
        assert np.isfinite(metrics["loss"]), arch
        moved = any(
            float(jnp.abs(a.astype(jnp.float32)
                          - b.astype(jnp.float32)).max()) > 0
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(new_state["params"])))
        assert moved, arch


def test_greedy_generation_is_deterministic():
    cfg = dataclasses.replace(get_smoke("granite_3_2b"),
                              capacity_factor=8.0)
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.ones((2, 8), jnp.int32)

    def gen():
        cache = M.init_cache(cfg, 2, 32, dtype=jnp.float32)
        lg, cache = M.prefill(params, cfg, prompt, cache)
        toks = []
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        for _ in range(8):
            toks.append(np.asarray(t))
            lg, cache = M.decode_step(params, cfg, t, cache)
            t = jnp.argmax(lg, -1).astype(jnp.int32)
        return np.stack(toks)

    a, b = gen(), gen()
    np.testing.assert_array_equal(a, b)


def test_dryrun_skip_rule():
    """long_500k skipped for full-attention archs, runs for ssm/hybrid."""
    from repro.launch.dryrun import skip_reason
    from repro.configs import get_config
    from repro.models.config import SHAPES
    assert skip_reason(get_config("qwen1.5-32b"), SHAPES["long_500k"])
    assert skip_reason(get_config("whisper-base"), SHAPES["long_500k"])
    assert skip_reason(get_config("mamba2-2.7b"), SHAPES["long_500k"]) is None
    assert skip_reason(get_config("zamba2-1.2b"), SHAPES["long_500k"]) is None
    assert skip_reason(get_config("qwen1.5-32b"), SHAPES["train_4k"]) is None


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(from_env, tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
    it the cache is the fixed .jax_cache/ at the checkout's root."""
    import pathlib
    from repro.launch import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.use_compile_cache()
        root = pathlib.Path(__file__).resolve().parent.parent
        assert pathlib.Path(path) == root / ".jax_cache"
        assert updates["jax_compilation_cache_dir"] == path


def test_benchmark_driver_counts_failed_modules(tmp_path, monkeypatch):
    """A module that raises is reported and becomes a non-zero exit."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "run.py"
    spec = importlib.util.spec_from_file_location("bench_driver", path)
    driver = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(driver)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(driver, "MODULES", ["benchmarks.no_such_module"])
    assert driver.main() == 1
    assert (tmp_path / "experiments" / "bench_results.json").exists()
