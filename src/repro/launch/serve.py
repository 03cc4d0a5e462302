"""Production serving launcher: batched prefill + decode.

``python -m repro.launch.serve --arch mamba2_2p7b --batch 8``

The serving twin of launch/train.py: builds the cache, jits the
prefill/decode steps (with mesh shardings when requested) and runs a
greedy generation loop with per-phase throughput stats.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.runtime.steps import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b",
                    help=f"one of {ARCHS}")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--mesh-data", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh_data:
        mesh = make_mesh((args.mesh_data, args.mesh_model),
                         ("data", "model"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    B = args.batch
    max_len = args.prompt_len + args.gen_len + 8
    cache = M.init_cache(cfg, B, max_len,
                         dtype=jnp.dtype(cfg.dtype))
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (B, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompt}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jnp.zeros(
            (B, cfg.n_frontend_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["extra_embeds"] = jnp.zeros(
            (B, cfg.n_frontend_tokens, cfg.d_model), jnp.dtype(cfg.dtype))

    prefill = jax.jit(make_prefill_step(cfg, mesh=mesh))
    decode = jax.jit(make_decode_step(cfg, mesh=mesh),
                     donate_argnums=(2,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch, cache)
    logits.block_until_ready()
    tp = time.perf_counter() - t0

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [np.asarray(tok)]
    t0 = time.perf_counter()
    for _ in range(args.gen_len - 1):
        logits, cache = decode(params, {"token": tok}, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(tok))
    jax.block_until_ready(logits)
    td = time.perf_counter() - t0

    print(f"{cfg.name}: prefill {tp*1e3:.1f} ms "
          f"({B*args.prompt_len/tp:.0f} tok/s), decode {td*1e3:.1f} ms "
          f"({B*(args.gen_len-1)/td:.0f} tok/s)")
    gen = np.stack(outs, 1)
    assert np.isfinite(gen).all()
    print("first row:", gen[0][:12], "... OK")


if __name__ == "__main__":
    main()
