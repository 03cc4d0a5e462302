import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run compiles for 512 virtual host devices and never needs a
# chip; pinning the CPU here (inherited by the per-cell children) keeps
# it off any accelerator another process holds.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the *real* step function (train / prefill /
decode) with full sharding and donation, lowers it against
ShapeDtypeStruct stand-ins (no allocation), compiles it for the
production mesh, and records:

- ``compiled.memory_analysis()``  (fits-per-device proof)
- ``compiled.cost_analysis()``    (FLOPs / bytes for the roofline)
- collective bytes parsed from the optimized HLO
- the three roofline terms + dominant bottleneck

Usage:
  python -m repro.launch.dryrun --arch granite_3_2b --shape train_4k \
      --mesh pod                      # one cell (subprocess-friendly)
  python -m repro.launch.dryrun --sweep --mesh both --jobs 3
                                      # all cells via subprocesses
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.roofline import analyze
from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.config import ModelConfig, ShapeConfig
from repro.optim.adamw import AdamWConfig
from repro.runtime import steps as S

OUT_DIR = "experiments/dryrun"


# ----------------------------------------------------------------------
# per-shape runtime knobs (NOT architecture: execution strategy)
# ----------------------------------------------------------------------
def runtime_cfg(cfg: ModelConfig, shape: ShapeConfig,
                overrides: dict | None = None) -> ModelConfig:
    kw: dict = {}
    if shape.seq_len > 2048 and cfg.family not in ("ssm",):
        kw["attn_chunk"] = 2048 if shape.seq_len >= 32768 else 1024
    if shape.kind == "train":
        kw["remat"] = "dots"
        kw["microbatches"] = 8      # fits 16 GB/chip (see EXPERIMENTS.md)
    kw.update(overrides or {})
    global EP_OVER_DATA
    EP_OVER_DATA = bool(kw.pop("ep_over_data", False))
    return dataclasses.replace(cfg, **kw)


EP_OVER_DATA = False   # set by --overrides {"ep_over_data": true}


def arch_rules(cfg: ModelConfig, mesh, rules):
    """Per-arch fallbacks and EP placement.

    - experts %% model axis != 0 (granite-moe 40/16): fall back to
      tensor parallelism *inside* each expert (d_ff sharded).
    - ep_over_data (perf knob, §Perf cell 1): shard experts over the
      *data* axis instead of FSDP'ing their weights — expert weights
      stop being all-gathered every microbatch; the token all-to-all
      rides the data axis instead.
    """
    msize = mesh.shape.get("model", 1)
    dsize = mesh.shape.get("data", 1)
    if cfg.n_experts and EP_OVER_DATA and cfg.n_experts % dsize == 0:
        return rules.replace(experts="data", expert_ff="model")
    if cfg.n_experts and cfg.n_experts % msize != 0:
        rules = rules.replace(experts=None, expert_ff="model")
    return rules


def calib_layers(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.family == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every
    return 1, 2


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return ("long_500k needs sub-quadratic context state; "
                f"{cfg.name} is pure full-attention (assignment rule: skip)")
    return None


# ----------------------------------------------------------------------
# one cell
# ----------------------------------------------------------------------
def _lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, notes):
    """Build + lower the real step function for one cell."""
    from repro.models import model as M
    from repro.parallel.sharding import (SERVE_RULES, TRAIN_RULES,
                                         make_param_shardings)
    if shape.kind == "train":
        rules = arch_rules(cfg, mesh, TRAIN_RULES)
        state_av = S.abstract_train_state(cfg)
        state_sh = S.train_state_shardings(cfg, mesh, rules=rules,
                                           notes=notes)
        batch_av = S.batch_specs(cfg, shape)
        batch_sh = S.batch_shardings(cfg, shape, mesh, rules)
        step = S.make_train_step(cfg, AdamWConfig(), mesh=mesh,
                                 rules=rules)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        return jitted.lower(state_av, batch_av)
    rules = arch_rules(cfg, mesh, SERVE_RULES)
    params_av = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    params_sh = make_param_shardings(mesh, M.param_axes(cfg), rules,
                                     params_av, notes)
    cache_av = S.abstract_cache(cfg, shape)
    cache_sh = S.cache_shardings(cfg, shape, mesh, rules)
    batch_av = S.batch_specs(cfg, shape)
    batch_sh = S.batch_shardings(cfg, shape, mesh, rules)
    if shape.kind == "prefill":
        step = S.make_prefill_step(cfg, mesh=mesh, rules=rules)
    else:
        step = S.make_decode_step(cfg, mesh=mesh, rules=rules)
    jitted = jax.jit(step, in_shardings=(params_sh, batch_sh, cache_sh),
                     out_shardings=(None, cache_sh), donate_argnums=(2,))
    return jitted.lower(params_av, batch_av, cache_av)


def _cell_costs(compiled) -> dict:
    from repro.analysis.hlo import collective_bytes
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll["total"],
            "coll_breakdown": {k: v for k, v in coll.items()
                               if k not in ("total", "ops")}}


def calibrate(cfg: ModelConfig, shape: ShapeConfig, mesh, notes
              ) -> dict:
    """Exact per-layer costs from unrolled L1/L2 compiles.

    XLA cost analysis counts while-loop bodies ONCE, so the production
    (scan-over-layers) module undercounts by the trip count.  The
    unrolled modules contain no layer loop and no attention-chunk loop
    (attn_chunk=0 -> naive attention: identical matmul FLOPs), so
    body = cost(L2) - cost(L1) and rest = cost(L1) - L1*body are
    exact; total(L) = L*body + rest.  All per-device (SPMD module).
    """
    L1, L2 = calib_layers(cfg)
    enc_scale = cfg.n_enc_layers // cfg.n_layers if cfg.n_enc_layers else 0
    out = []
    for Lc in (L1, L2):
        kw = dict(scan_layers=False, attn_unroll=True, microbatches=1,
                  n_layers=Lc, remat=cfg.remat)
        if cfg.n_enc_layers:
            kw["n_enc_layers"] = Lc * max(enc_scale, 1)
        cfg_c = dataclasses.replace(cfg, **kw)
        lowered = _lower_cell(cfg_c, shape, mesh, notes)
        out.append(_cell_costs(lowered.compile()))
    c1, c2 = out
    dL = L2 - L1
    body = {k: (c2[k] - c1[k]) / dL for k in ("flops", "bytes", "coll")}
    rest = {k: c1[k] - L1 * body[k] for k in ("flops", "bytes", "coll")}
    L = cfg.n_layers
    total = {k: max(L * body[k] + rest[k], 0.0)
             for k in ("flops", "bytes", "coll")}
    return {"body": body, "rest": rest, "total": total,
            "coll_breakdown_L1": c1["coll_breakdown"]}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None) -> dict:
    cfg0 = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multipod" if multi_pod else "pod"
    reason = skip_reason(cfg0, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}
    cfg = runtime_cfg(cfg0, shape, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    t0 = time.time()
    notes: list[str] = []

    # 1) the production module: scan-over-layers, chunked attention.
    #    This is the compile/memory PROOF for the cell.
    lowered = _lower_cell(cfg, shape, mesh, notes)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")
           if hasattr(ma, k)}
    raw = _cell_costs(compiled)

    if multi_pod:
        # multi-pod pass proves the "pod" axis shards + memory; the
        # roofline table is single-pod only (assignment spec).
        row = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "ok", "chips": chips,
               "lower_s": round(t_lower, 1),
               "compile_s": round(t_compile, 1),
               "bytes_per_chip": mem, "raw_uncalibrated": raw,
               "note": "compile+memory proof; roofline from pod mesh"}
        return row

    # 2) calibration: exact per-layer costs (see docstring).
    cal = calibrate(cfg, shape, mesh, notes)
    cost = {"flops": cal["total"]["flops"] * chips,
            "bytes accessed": cal["total"]["bytes"] * chips}
    coll_text_stub = ""   # collectives taken from calibration directly

    report = analyze(arch, shape, mesh_name, chips, cost, coll_text_stub,
                     mem, cfg, note="; ".join(sorted(set(notes))))
    # patch in calibrated collective bytes (analyze parsed empty text)
    from repro.analysis.roofline import V5E_HW
    report.coll_bytes = cal["total"]["coll"] * chips
    report.t_collective = cal["total"]["coll"] / V5E_HW.link_bw
    report.coll_breakdown = cal["coll_breakdown_L1"]
    terms = {"compute": report.t_compute, "memory": report.t_memory,
             "collective": report.t_collective}
    report.dominant = max(terms, key=terms.get)

    row = report.row()
    row.update({"status": "ok", "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1), "n_chips": chips,
                "raw_uncalibrated": raw,
                "calibration": cal})
    return row


# ----------------------------------------------------------------------
# sweep orchestration (subprocess per cell for isolation/parallelism)
# ----------------------------------------------------------------------
def cell_path(arch: str, shape: str, mesh: str) -> str:
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh}.json")


def sweep(mesh_opt: str, jobs: int, force: bool = False,
          archs: list[str] | None = None) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[mesh_opt]
    cells = [(a, s, mp) for a in (archs or ARCHS) for s in SHAPES
             for mp in meshes]
    todo = [(a, s, mp) for a, s, mp in cells
            if force or not os.path.exists(
                cell_path(a, s, "multipod" if mp else "pod"))]
    print(f"{len(todo)}/{len(cells)} cells to run, {jobs} parallel jobs")
    procs: list[tuple, subprocess.Popen] = []

    def launch(cell):
        a, s, mp = cell
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", s, "--mesh", "multipod" if mp else "pod"]
        return subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                stderr=subprocess.PIPE)

    queue = list(todo)
    running: list[tuple] = []
    while queue or running:
        while queue and len(running) < jobs:
            cell = queue.pop(0)
            running.append((cell, launch(cell), time.time()))
            print(f"  start {cell}")
        time.sleep(2)
        for item in list(running):
            cell, proc, t0 = item
            rc = proc.poll()
            if rc is None:
                continue
            running.remove(item)
            dt = time.time() - t0
            if rc == 0:
                print(f"  done  {cell} ({dt:.0f}s)")
            else:
                err = proc.stderr.read().decode()[-4000:]
                print(f"  FAIL  {cell} rc={rc} ({dt:.0f}s)\n{err[-800:]}")
                a, s, mp = cell
                path = cell_path(a, s, "multipod" if mp else "pod")
                if not os.path.exists(path):  # never clobber a good row
                    with open(path, "w") as f:
                        json.dump({"arch": a, "shape": s,
                                   "mesh": "multipod" if mp else "pod",
                                   "status": "fail", "rc": rc,
                                   "error": err}, f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of ModelConfig overrides (perf knobs)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.sweep:
        sweep(args.mesh, args.jobs, args.force,
              [args.arch] if args.arch else None)
        return

    assert args.arch and args.shape, "--arch and --shape required"
    overrides = json.loads(args.overrides) if args.overrides else None
    for mp in ({"pod": [False], "multipod": [True],
                "both": [False, True]}[args.mesh]):
        mesh_name = "multipod" if mp else "pod"
        try:
            row = run_cell(args.arch, args.shape, mp, overrides)
        except Exception:
            row = {"arch": args.arch, "shape": args.shape,
                   "mesh": mesh_name, "status": "fail",
                   "error": traceback.format_exc()[-4000:]}
        os.makedirs(OUT_DIR, exist_ok=True)
        path = args.out or cell_path(args.arch, args.shape, mesh_name)
        with open(path, "w") as f:
            json.dump(row, f, indent=1, default=str)
        status = row["status"]
        print(f"{args.arch} {args.shape} {mesh_name}: {status}")
        if status == "ok" and "t_compute" in row:
            print(f"  Tc={row['t_compute']*1e3:.3f}ms "
                  f"Tm={row['t_memory']*1e3:.3f}ms "
                  f"Tx={row['t_collective']*1e3:.3f}ms "
                  f"dom={row['dominant']} useful={row['useful_ratio']:.3f}")
            print(f"  mem/device: {row['bytes_per_chip']}")
        elif status == "fail":
            print(row["error"][-1500:])
            sys.exit(1)


if __name__ == "__main__":
    main()
