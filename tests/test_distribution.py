"""Multi-device distribution tests (8 host devices via subprocess —
conftest keeps the main process at 1 device on purpose)."""
import subprocess
import sys

import pytest

PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
"""


def run_sub(code: str, timeout: int = 560):
    r = subprocess.run([sys.executable, "-c", PREAMBLE + code],
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_ring_collectives_match_barrier():
    run_sub("""
from repro.parallel.collectives import (ring_allgather_matmul,
                                        ring_matmul_reducescatter)
mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
w = jnp.asarray(rng.normal(size=(128, 96)), jnp.float32)
y1 = ring_allgather_matmul(x, w, mesh)
assert np.allclose(y1, x @ w, atol=1e-3), float(jnp.abs(y1 - x@w).max())
y2 = ring_matmul_reducescatter(x, w, mesh)
assert np.allclose(y2, x @ w, atol=1e-3), float(jnp.abs(y2 - x@w).max())
""")


def test_pipeline_parallel_matches_sequential():
    run_sub("""
from repro.parallel.pipeline import pipeline_apply
mesh = jax.make_mesh((8,), ("stage",))
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.normal(size=(8, 32, 32)) * 0.3, jnp.float32)
xb = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
def stage(p, x): return jnp.tanh(x @ p)
yp = pipeline_apply(stage, ws, xb, mesh, n_micro=4, axis="stage")
yref = xb
for i in range(8): yref = jnp.tanh(yref @ ws[i])
assert np.allclose(yp, yref, atol=1e-4)
""")


def test_sharded_train_step_matches_single_device():
    """DPxTP sharded training step == unsharded step (same math)."""
    run_sub("""
import dataclasses
from repro.configs import get_smoke
from repro.optim.adamw import AdamWConfig
from repro.runtime import steps as S
from repro.models import model as M
from repro.data.pipeline import SyntheticLM

cfg = dataclasses.replace(get_smoke("granite_3_2b"), remat="none")
opt = AdamWConfig(lr_peak=1e-3, warmup_steps=1, decay_steps=10)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

params = M.init(cfg, jax.random.PRNGKey(0))
from repro.optim.adamw import adamw_init
state = {"params": params, "opt": adamw_init(params)}

# single device
step1 = jax.jit(S.make_train_step(cfg, opt))
s1, m1 = step1(jax.tree.map(jnp.copy, state), batch)

# sharded 2x4
mesh = make_mesh((2, 4), ("data", "model"))
sh = S.train_state_shardings(cfg, mesh)
from repro.models.config import ShapeConfig
shp = ShapeConfig("t", 16, 8, "train")
bsh = S.batch_shardings(cfg, shp, mesh, S.TRAIN_RULES)
step2 = jax.jit(S.make_train_step(cfg, opt, mesh=mesh),
                in_shardings=(sh, bsh), out_shardings=(sh, None))
s2, m2 = step2(jax.tree.map(jnp.copy, state), batch)

d = abs(float(m1["loss"]) - float(m2["loss"]))
assert d < 1e-4, f"loss mismatch {d}"
for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])):
    err = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    assert err < 1e-2, err
print("loss", float(m1["loss"]))
""")


def test_microbatched_step_matches_full_batch():
    run_sub("""
import dataclasses
from repro.configs import get_smoke
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime import steps as S
from repro.models import model as M
from repro.data.pipeline import SyntheticLM

cfg = dataclasses.replace(get_smoke("granite_3_2b"), remat="none")
opt = AdamWConfig(lr_peak=1e-3, warmup_steps=1, decay_steps=10)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
params = M.init(cfg, jax.random.PRNGKey(0))
state = {"params": params, "opt": adamw_init(params)}
s1, m1 = jax.jit(S.make_train_step(cfg, opt))(jax.tree.map(jnp.copy, state), batch)
cfg4 = dataclasses.replace(cfg, microbatches=4)
s4, m4 = jax.jit(S.make_train_step(cfg4, opt))(jax.tree.map(jnp.copy, state), batch)
d = abs(float(m1["loss"]) - float(m4["loss"]))
assert d < 1e-4, d
for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s4["params"])):
    err = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    assert err < 1e-2, err
""")


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Checkpoint on a 2x4 mesh, restore on 4x2 and on 1 device."""
    run_sub(f"""
import dataclasses
from repro.configs import get_smoke
from repro.models import model as M
from repro.optim.adamw import adamw_init
from repro.runtime import steps as S
from repro.checkpoint.checkpointer import save_pytree, restore_pytree

cfg = get_smoke("granite_3_2b")
params = M.init(cfg, jax.random.PRNGKey(1))
state = {{"params": params, "opt": adamw_init(params)}}
mesh1 = make_mesh((2, 4), ("data", "model"))
sh1 = S.train_state_shardings(cfg, mesh1)
state = jax.device_put(state, sh1)
save_pytree(state, r"{tmp_path}", 3)

mesh2 = make_mesh((4, 2), ("data", "model"))
sh2 = S.train_state_shardings(cfg, mesh2)
like = jax.eval_shape(lambda: state)
restored = restore_pytree(like, r"{tmp_path}", 3, shardings=sh2)
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
    assert np.allclose(np.asarray(jax.device_get(a), np.float32),
                       np.asarray(jax.device_get(b), np.float32)), "mismatch"
print("elastic ok")
""")


def test_grad_compression_in_sharded_step():
    run_sub("""
import dataclasses
from repro.configs import get_smoke
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.optim.compression import ef_init
from repro.runtime import steps as S
from repro.models import model as M
from repro.data.pipeline import SyntheticLM

cfg = dataclasses.replace(get_smoke("granite_3_2b"), remat="none")
opt = AdamWConfig(lr_peak=1e-3, warmup_steps=1, decay_steps=10)
data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
params = M.init(cfg, jax.random.PRNGKey(0))
state = {"params": params, "opt": adamw_init(params), "ef": ef_init(params)}
mesh = make_mesh((2, 4), ("data", "model"))
step = jax.jit(S.make_train_step(cfg, opt, mesh=mesh, compress_grads=True))
s, m = step(state, batch)
assert np.isfinite(float(m["loss"]))
# error-feedback buffers are now non-zero (quantization residue)
nz = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(s["ef"]))
assert nz > 0
print("compressed step ok", float(m["loss"]))
""")
