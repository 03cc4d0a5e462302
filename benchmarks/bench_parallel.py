"""Hardware-parallelism sweep: vector factor x replica count.

The paper's third transformation pillar, measured end-to-end:

- **vectorization** — compile one stencil app per vector factor
  (tile minor dim = ``128 * vf``) and time the fused pallas kernel;
  the cost model's prediction (:func:`repro.core.vectorize.
  modeled_plane_time`) rides along so the sweep validates the model
  that drives automatic selection.
- **replication** — serve one request stream through
  ``StreamEngine(replicas=k)`` for k = 1, 2, 4 (the batch-parallel
  farm) and through :func:`repro.parallel.replicate.replicate_app`
  (spatial row partitioning), recording measured throughput next to
  the model's predicted linear scaling.  On an accelerator the rows run
  in this process on the visible devices (a child could not claim
  them); on the CPU they run in a child with four forced host devices,
  like tests/test_distribution.

``--smoke`` (CI) asserts the two correctness properties cheaply: the
vector-factor sweep is monotone-feasible with exact ``128*vf`` minor
dims, and replicated serving (the 1-replica shard_map fallback)
matches single-device outputs bit-for-bit.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import build_schedule, compile_graph, sweep_vector_factor
from repro.core.apps import build_app

_ROOT = os.path.join(os.path.dirname(__file__), "..")
_APP = "gaussian_blur"


def _time_call(fn, reps: int) -> float:
    fn()                                            # warmup (compiles)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # us/call


def vf_rows(smoke: bool) -> list[dict]:
    from repro.obs.drift import DriftLog, drift_report
    from repro.tune.calibrate import calibrate

    # a ladder of shapes, not one: the calibration fit needs rows where
    # the grid-step count and the padded element count move separately,
    # or step overhead and per-element cost are not identifiable
    shapes = ([(96, 256), (64, 512), (64, 1024)] if smoke
              else [(256, 640), (64, 1024), (256, 1024), (128, 2048)])
    h, w = shapes[0]
    reps = 2 if smoke else 5
    rng = np.random.default_rng(0)

    # every (modeled, measured) pair from the sweep goes to the on-disk
    # drift log — with the cost-model features behind each modeled time
    # — so drift_report() is the model's report card and calibrate()
    # can refit its constants; $REPRO_DRIFT_LOG redirects (CI does)
    drift = DriftLog(os.environ.get("REPRO_DRIFT_LOG", "").strip()
                     or os.path.join(_ROOT, "experiments",
                                     "bench_parallel_drift.jsonl"))

    rows = []
    primary_records = None
    for hh, ww in shapes:
        x = rng.normal(size=(hh, ww)).astype(np.float32)
        sched = build_schedule(build_app(_APP, hh, ww))
        records = sweep_vector_factor(sched.groups[0])
        if primary_records is None:
            primary_records = records
        sig = sched.graph.signature()
        baseline = None
        for rec in records:
            if not rec["feasible"]:
                continue
            vf = rec["vector_factor"]
            app = compile_graph(build_app(_APP, hh, ww), backend="pallas",
                                vector_factor=vf)
            out = np.asarray(app(img=x)["out"])
            if baseline is None:
                baseline = out
            assert np.array_equal(out, baseline), f"vf={vf} changed bits"
            us = _time_call(lambda: np.asarray(app(img=x)["out"]), reps)
            drift.record("vf_sweep", sig, [[hh, ww]], "pallas",
                         rec["modeled_s"], us / 1e6, vector_factor=vf,
                         tile=list(rec["tile"]), app=_APP,
                         features={"groups": [rec["features"]]})
            name = (f"parallel_vf{vf}" if (hh, ww) == (h, w)
                    else f"parallel_vf{vf}_{hh}x{ww}")
            rows.append({"name": name, "us": us,
                         "vector_factor": vf, "tile": rec["tile"],
                         "modeled_us": rec["modeled_s"] * 1e6,
                         "h": hh, "w": ww, "app": _APP})
    drift.flush()
    report = drift_report(drift)
    auto = build_schedule(build_app(_APP, h, w)).groups[0]
    rows.append({"name": "parallel_vf_auto", "us": 0.0,
                 "vector_factor": auto.vector_factor, "tile": auto.tile,
                 "h": h, "w": w, "app": _APP,
                 "drift_spearman": report["spearman"],
                 "drift_bias": report["bias"],
                 "drift_log": drift.path,
                 "sweep": [{k: r[k] for k in
                            ("vector_factor", "feasible", "modeled_s")}
                           for r in primary_records]})
    rows.append(calibration_row(drift, report, calibrate, drift_report))
    return rows


def calibration_row(drift, report, calibrate, drift_report) -> dict:
    """Fit the cost model from the accumulated drift log and report the
    before/after rank correlation — ROADMAP item 3's exit criterion as
    a benchmark row."""
    result = calibrate(drift)
    row = {"name": "parallel_calibration", "us": 0.0,
           "fitted": result.fitted, "n_rows": result.n_rows,
           "seed_spearman": report["spearman"],
           "seed_bias": report["bias"]}
    if result.fitted:
        after = drift_report(drift, spec=result.spec)["with_spec"]
        s = result.spec
        row.update({"fitted_spearman": after["spearman"],
                    "fitted_bias": after["bias"],
                    "clock_hz": s.clock_hz, "hbm_bw": s.hbm_bw,
                    "step_overhead_s": s.step_overhead_s,
                    "ii_scale": [list(p) for p in s.ii_scale]})
    else:
        row["warning"] = result.warning
    return row


def replica_sweep(backend: str, counts: tuple[int, ...] = (1, 2, 4)
                  ) -> list[dict]:
    """Engine-farm and spatial replication rows for each replica count.

    Runs in the process that holds the devices; every count must be at
    most the visible device count.
    """
    from repro.parallel.replicate import replicate_app
    from repro.runtime import StreamEngine

    H, W, N = 64, 256, 96
    rng = np.random.default_rng(0)
    frames = [rng.normal(size=(H, W)).astype(np.float32) for _ in range(N)]
    g = build_app("filter_chain", H, W)
    app = compile_graph(build_app("filter_chain", H, W), backend=backend)
    ref = np.asarray(app(img=frames[0])["out"])

    rows = []
    for k in counts:
        with StreamEngine(backend=backend, max_batch=8, replicas=k,
                          max_queue=N) as eng:
            eng.submit(g, {"img": frames[0]}).result()        # warm
            t0 = time.perf_counter()
            hs = [eng.submit(g, {"img": f}) for f in frames]
            outs = [h.result() for h in hs]
            dt = time.perf_counter() - t0
            rep = eng.report(n_items=N)
        assert np.array_equal(np.asarray(outs[0]["out"]), ref), k
        mod = next(iter(rep["modeled"].values()))
        rows.append({"name": f"parallel_engine_r{k}", "us": dt / N * 1e6,
                     "replicas": k, "throughput_rps": N / dt,
                     "throughput_per_replica_rps": N / dt / k,
                     "modeled_scaling": mod.get("replica_scaling_modeled",
                                                1.0),
                     "h": H, "w": W, "n": N, "backend": backend})

    for k in counts:
        rapp = replicate_app(app, k)
        out = np.asarray(rapp(img=frames[0])["out"])
        assert np.array_equal(out, ref), k
        t0 = time.perf_counter()
        for f in frames[:32]:
            np.asarray(rapp(img=f)["out"])
        dt = time.perf_counter() - t0
        rows.append({"name": f"parallel_spatial_r{k}", "us": dt / 32 * 1e6,
                     "replicas": k, "throughput_rps": 32 / dt,
                     "halo_rows": rapp.halo_rows, "h": H, "w": W,
                     "backend": backend})
    return rows


#: the CPU path: a child with four virtual host devices (the parent's
#: CPU backend is already fixed at one device)
_VIRTUAL_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, "src")
sys.path.insert(0, ".")
from benchmarks.bench_parallel import replica_sweep
print(json.dumps(replica_sweep("xla")))
"""


def replica_rows(smoke: bool) -> list[dict]:
    if smoke:
        # in-process 1-replica fallback: same shard_map code path,
        # asserts replicated == single-device bit-exactly
        from repro.parallel.replicate import replicate_app
        h, w = 32, 128
        rng = np.random.default_rng(0)
        x = rng.normal(size=(h, w)).astype(np.float32)
        app = compile_graph(build_app("filter_chain", h, w), backend="xla")
        rapp = replicate_app(app)
        a, b = np.asarray(app(img=x)["out"]), np.asarray(rapp(img=x)["out"])
        assert np.array_equal(a, b), "replicated != single-device"
        return [{"name": "parallel_spatial_r1_smoke", "us": 0.0,
                 "replicas": 1, "bit_exact": True,
                 "halo_rows": rapp.halo_rows, "h": h, "w": w}]
    import jax
    devices = jax.devices()
    if devices[0].platform != "cpu":
        # real devices belong to this process: a child could not get
        # them, so the replica rows run here on what is visible
        return replica_sweep("pallas", tuple(
            k for k in (1, 2, 4) if k <= len(devices)))
    r = subprocess.run([sys.executable, "-c", _VIRTUAL_SUB],
                       capture_output=True, text=True, timeout=560,
                       cwd=_ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"replica sweep failed:\n{r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(smoke: bool = False) -> list[dict]:
    rows = vf_rows(smoke)
    if smoke:
        recs = next(r for r in rows if r["name"] == "parallel_vf_auto")
        feas = [s["feasible"] for s in recs["sweep"]]
        assert feas == sorted(feas, reverse=True), \
            f"vector-factor feasibility not monotone: {feas}"
        assert recs["tile"][1] == 128 * recs["vector_factor"], recs
    rows += replica_rows(smoke)
    return rows


def main() -> None:
    smoke = "--smoke" in sys.argv
    rows = run(smoke=smoke)
    for r in rows:
        extra = {k: v for k, v in r.items() if k not in ("name", "us")}
        print(f"{r['name']}: {r['us']:.1f} us/call {extra}")
    payload = {"rows": rows, "smoke": smoke}
    os.makedirs(os.path.join(_ROOT, "experiments"), exist_ok=True)
    with open(os.path.join(_ROOT, "experiments", "bench_parallel.json"),
              "w") as f:
        json.dump(payload, f, indent=1)
    if not smoke:
        with open(os.path.join(_ROOT, "BENCH_parallel.json"), "w") as f:
            json.dump(payload, f, indent=1)
    if smoke:
        print("smoke ok: monotone-feasible vector sweep, replicated "
              "serving bit-exact vs single-device")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
