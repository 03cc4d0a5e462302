"""Production training launcher.

``python -m repro.launch.train --arch granite_3_2b --steps 100``

Wires together everything the framework generates: mesh construction,
sharding rules, the jitted+donated train step, deterministic data,
async checkpoints, preemption & straggler handling.  On this CPU
container use ``--smoke`` (reduced config, 1 device); on a real fleet
drop the flag and pass ``--mesh-data/--mesh-model``.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, get_config, get_smoke
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig
from repro.runtime.steps import train_state_shardings
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b",
                    help=f"one of {ARCHS}")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="data-axis size (0 = no mesh / single device)")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    print(f"{cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"{jax.device_count()} devices")

    mesh = state_sh = None
    if args.mesh_data:
        mesh = make_mesh((args.mesh_data, args.mesh_model),
                         ("data", "model"))
        state_sh = train_state_shardings(cfg, mesh)

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.global_batch)
    opt = AdamWConfig(lr_peak=args.lr, warmup_steps=max(args.steps // 10, 1),
                      decay_steps=args.steps)
    tcfg = TrainerConfig(total_steps=args.steps,
                         ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, log_every=10,
                         compress_grads=args.compress_grads)
    trainer = Trainer(cfg, opt, tcfg, data, mesh=mesh,
                      state_shardings=state_sh)
    hist = trainer.run()
    if hist:
        print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
