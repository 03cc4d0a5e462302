"""Serving benchmark: equal-width dispatch ladder + latency-budget sweep.

Closed-loop ladder — the same compiled diamond app dispatched four
ways, with micro-batch widths compared at EQUAL width so the engine's
scheduling overhead is visible next to the raw batched launch it
amortizes:

- ``sequential`` — one ``CompiledApp.__call__`` per request, forced
  to host memory before the next (the bare-callable baseline),
- ``launch_pipelined`` — async ``CompiledApp.launch`` with a depth-2
  in-flight window (double buffering without batching),
- ``direct micro-batch[b]`` — ``MicroBatcher.launch`` over width-``b``
  slices: stacking + one vmapped kernel, no queue/threads/futures,
- ``engine[b]`` — the full :class:`repro.runtime.engine.StreamEngine`
  submit→form→dispatch→complete path at ``max_batch=b``.

Open-loop sweep — requests arrive paced below capacity while the
engine forms batches under a per-request ``latency_budget``; each row
records the offered load next to achieved throughput and p50/p99, so
the deadline-based batch formation is visible: p99 tracks the budget
(plus service + scheduler noise), not the queue depth.

The benchmark runs in the overhead-dominated regime (small planes):
that is where per-launch host overhead is the bottleneck and
micro-batching pays.  On large planes a vmapped stencil batch becomes
compute/bandwidth-bound and batching itself stops winning — no
scheduler can recover that, so benchmarking there would measure XLA
codegen, not the serving runtime.

Full mode writes ``experiments/bench_serving.json`` plus the repo-root
``BENCH_serving.json`` baseline; ``--smoke`` runs a small
configuration in CI and asserts:

- micro-batched dispatch beats one-at-a-time dispatch,
- batching pays through the FULL engine path: ``engine[b=8]`` beats
  ``engine[b=1]`` by >= 1.4x (this is the continuous-batching claim —
  the seed engine lost its batching win to fixed-width padding and
  lock-step draining),
- under paced open-loop load, p99 stays bounded by the configured
  latency budget plus service/scheduler slack.

``--trace out.json`` records the whole run into the flight recorder
(:mod:`repro.obs`) and exports a Perfetto-loadable Chrome trace;
``$REPRO_DRIFT_LOG=path`` additionally appends a modeled-vs-measured
drift row per engine launch (see ``docs/observability.md``).

Single-core caveat: engine-vs-direct at equal width is recorded
(``vs_direct_equal_batch``) but not asserted — on a 1-core host the
submit path, worker loop and caller futures all serialize with the
kernel, so the engine cannot reach direct-dispatch throughput no
matter how it schedules; on multi-core hosts the worker overlaps with
submitters and the ratio approaches 1.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import DataflowGraph, compile_graph
from repro.core.apps import JACOBI3, LAPLACE3, _conv
from repro.runtime import MicroBatcher, StreamEngine, modeled_latency

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _diamond(h: int, w: int) -> DataflowGraph:
    g = DataflowGraph("diamond")
    x = g.input("x", (h, w))
    s1 = g.stencil(x, (3, 3), _conv(LAPLACE3), name="lap")
    s2 = g.stencil(x, (3, 3), _conv(JACOBI3), name="jac")
    g.output(g.point2(s1, s2, lambda u, v: u - v, name="merge"), "y")
    return g


def _requests(h: int, w: int, n: int) -> list[np.ndarray]:
    rng = np.random.default_rng(0)
    return [rng.normal(size=(h, w)).astype(np.float32) for _ in range(n)]


def _sequential(app, reqs) -> float:
    """One-at-a-time __call__ dispatch; returns items/sec."""
    np.asarray(app(x=reqs[0])["y"])                    # warmup
    t0 = time.perf_counter()
    for x in reqs:
        np.asarray(app(x=x)["y"])
    return len(reqs) / (time.perf_counter() - t0)


def _launch_pipelined(app, reqs, depth: int = 2) -> float:
    """Async launch() with a bounded in-flight window; items/sec."""
    app.launch(x=reqs[0]).result()                     # warmup
    inflight: list = []
    t0 = time.perf_counter()
    for x in reqs:
        if len(inflight) >= depth:
            inflight.pop(0).result()
        inflight.append(app.launch(x=x))
    for h in inflight:
        h.result()
    return len(reqs) / (time.perf_counter() - t0)


class _Req:
    def __init__(self, x):
        self.inputs = {"x": x}


def _microbatched(app, mb, reqs, b: int) -> float:
    """Direct width-``b`` micro-batched dispatch (no engine); items/sec."""
    wrapped = [_Req(x) for x in reqs]
    np.asarray(mb.launch(app, wrapped[:b])["y"])       # warmup
    t0 = time.perf_counter()
    outs = [mb.launch(app, wrapped[i:i + b], check_shapes=False)
            for i in range(0, len(wrapped), b)]
    for o in outs:
        np.asarray(o["y"])
    return len(reqs) / (time.perf_counter() - t0)


def _warm_engine(eng, g, reqs, max_batch: int) -> None:
    """Compile every power-of-two bucket the engine can launch."""
    w = 1
    while w <= max_batch:
        handles = [eng.submit(g, {"x": reqs[i]}) for i in range(w)]
        for hd in handles:
            hd.result(timeout=600)
        w <<= 1


def _engine_round(eng, g, reqs) -> float:
    """One closed-loop round through a warm engine; items/sec."""
    t0 = time.perf_counter()
    handles = [eng.submit(g, {"x": x}) for x in reqs]
    for hd in handles:
        hd.result(timeout=600)
    return len(reqs) / (time.perf_counter() - t0)


def _engine_paced(g, reqs, backend: str, budget_s: float,
                  rate_rps: float, burst: int = 8) -> dict:
    """Open-loop round: paced arrivals against a latency budget.

    Submits ``burst`` requests every ``burst/rate`` seconds (offered
    load below capacity) into a FRESH engine, so the recorded p50/p99
    reflect deadline-based batch formation, not queue backlog.
    """
    with StreamEngine(backend=backend, max_batch=8,
                      max_queue=len(reqs) + 16, inflight=2,
                      latency_budget=budget_s) as eng:
        _warm_engine(eng, g, reqs, 8)
        eng.telemetry.reset()      # drop warmup compile latencies
        period = burst / rate_rps
        next_t = time.perf_counter()
        t0 = next_t
        handles = []
        for i in range(0, len(reqs), burst):
            now = time.perf_counter()
            if now < next_t:
                time.sleep(next_t - now)
            for x in reqs[i:i + burst]:
                handles.append(eng.submit(g, {"x": x}))
            next_t += period
        for hd in handles:
            hd.result(timeout=600)
        wall = time.perf_counter() - t0
        rep = eng.report()
    m = rep["measured"]
    return {
        "budget_ms": budget_s * 1e3,
        "offered_load_rps": rate_rps,
        "achieved_rps": len(reqs) / wall,
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "batch_size_mean": m["batch_size_mean"],
    }


def run(smoke: bool = False) -> list[dict]:
    # small planes: the overhead-dominated regime micro-batching
    # amortizes (see module docstring).  Modes are measured in
    # interleaved rounds (best-of-k per mode) so machine-load swings
    # hit every mode alike.
    h, w = (8, 128)
    n = 128 if smoke else 512
    rounds = 3
    backend = "xla"
    batch_widths = (1, 8) if smoke else (1, 2, 4, 8)
    reqs = _requests(h, w, n)
    g = _diamond(h, w)
    app = compile_graph(_diamond(h, w), backend=backend)
    model = modeled_latency(app, n)

    engines = {b: StreamEngine(backend=backend, max_batch=b,
                               max_queue=n + 16, inflight=2,
                               latency_budget=0.002)
               for b in batch_widths}
    for b, eng in engines.items():
        _warm_engine(eng, g, reqs, b)
    mbs = {b: MicroBatcher(max_batch=b) for b in batch_widths}
    seq_tput = pipe_tput = 0.0
    mb_tput = {b: 0.0 for b in batch_widths}
    eng_tput = {b: 0.0 for b in batch_widths}
    for _ in range(rounds):
        seq_tput = max(seq_tput, _sequential(app, reqs))
        pipe_tput = max(pipe_tput, _launch_pipelined(app, reqs))
        for b in batch_widths:
            mb_tput[b] = max(mb_tput[b], _microbatched(app, mbs[b], reqs, b))
            eng_tput[b] = max(eng_tput[b], _engine_round(engines[b], g, reqs))

    rows: list[dict] = []
    rows.append({"name": "serving_sequential", "us": 1e6 / seq_tput,
                 "throughput_rps": seq_tput, "mode": "one-at-a-time",
                 "h": h, "w": w, "n": n,
                 "modeled_speedup": model["speedup"]})
    rows.append({"name": "serving_launch_pipelined", "us": 1e6 / pipe_tput,
                 "throughput_rps": pipe_tput, "mode": "async-depth2",
                 "h": h, "w": w, "n": n})
    for b in batch_widths:
        rows.append({"name": f"serving_microbatch_b{b}",
                     "us": 1e6 / mb_tput[b], "throughput_rps": mb_tput[b],
                     "mode": f"direct micro-batch={b}",
                     "h": h, "w": w, "n": n,
                     "speedup_vs_sequential": mb_tput[b] / seq_tput})
    for b, eng in engines.items():
        rep = eng.report(n_items=n)
        eng.close()
        m = rep["measured"]
        tput = eng_tput[b]
        rows.append({"name": f"serving_engine_b{b}", "us": 1e6 / tput,
                     "throughput_rps": tput, "mode": f"engine batch={b}",
                     "h": h, "w": w, "n": n,
                     "latency_p50_ms": m["latency_p50_ms"],
                     "latency_p99_ms": m["latency_p99_ms"],
                     "batch_size_mean": m["batch_size_mean"],
                     "compiles": rep["cache"]["misses"],
                     "cache_requests": rep["cache"]["requests"],
                     "buckets": {str(k): v
                                 for k, v in rep["buckets"].items()},
                     "speedup_vs_sequential": tput / seq_tput,
                     "vs_direct_equal_batch": tput / mb_tput[b]})

    # open-loop latency-budget sweep at ~half the closed-loop capacity
    cap = max(eng_tput.values())
    budgets = (0.002,) if smoke else (0.0005, 0.002, 0.008)
    for budget in budgets:
        r = _engine_paced(g, reqs, backend, budget, rate_rps=0.5 * cap)
        r["name"] = f"serving_budget_{r['budget_ms']:g}ms"
        r["mode"] = "engine open-loop"
        r.update(h=h, w=w, n=n)
        rows.append(r)
    return rows


def _trace_arg(argv: list[str]) -> str | None:
    """Pull the ``--trace out.json`` output path from argv (None if absent)."""
    if "--trace" not in argv:
        return None
    i = argv.index("--trace")
    if i + 1 >= len(argv):
        raise SystemExit("--trace requires an output path")
    return argv[i + 1]


def main() -> None:
    smoke = "--smoke" in sys.argv
    trace_out = _trace_arg(sys.argv)
    tracer = None
    if trace_out is not None:
        # install the process-global recorder: every engine and compile
        # in run() resolves trace=None to it (see docs/observability.md)
        from repro.obs import install
        tracer = install()
    rows = run(smoke=smoke)
    if tracer is not None:
        from repro.obs import export_chrome_trace
        payload = export_chrome_trace(tracer, trace_out)
        print(f"trace: {len(payload['traceEvents'])} events "
              f"({tracer.dropped} dropped) -> {trace_out}")
    for r in rows:
        extra = ""
        if "speedup_vs_sequential" in r:
            extra += f" ({r['speedup_vs_sequential']:.2f}x vs sequential)"
        if "vs_direct_equal_batch" in r:
            extra += f" ({r['vs_direct_equal_batch']:.2f}x vs direct@b)"
        if "offered_load_rps" in r:
            extra += (f" (offered {r['offered_load_rps']:.0f} rps, "
                      f"p99 {r['latency_p99_ms']:.1f}ms @ budget "
                      f"{r['budget_ms']:g}ms)")
        print(f"{r['name']}: {r['throughput_rps']:.1f} items/s{extra}"
              if "throughput_rps" in r else
              f"{r['name']}: {r['achieved_rps']:.1f} items/s{extra}")
    payload = {"rows": rows, "smoke": smoke}
    os.makedirs(os.path.join(_ROOT, "experiments"), exist_ok=True)
    with open(os.path.join(_ROOT, "experiments", "bench_serving.json"),
              "w") as f:
        json.dump(payload, f, indent=1)
    # the repo-root baseline is what benchmarks/compare.py gates CI
    # against — a smoke run must never overwrite it with itself, or
    # the gate compares a fresh run to a copy of the fresh run
    if not smoke:
        with open(os.path.join(_ROOT, "BENCH_serving.json"), "w") as f:
            json.dump(payload, f, indent=1)
    if smoke:
        by_name = {r["name"]: r for r in rows}
        seq = by_name["serving_sequential"]["throughput_rps"]
        best_mb = max(r["throughput_rps"] for r in rows
                      if r["name"].startswith("serving_microbatch"))
        assert best_mb > seq, (
            f"micro-batched dispatch ({best_mb:.1f} items/s) did not beat "
            f"one-at-a-time dispatch ({seq:.1f} items/s)")
        e1 = by_name["serving_engine_b1"]["throughput_rps"]
        e8 = by_name["serving_engine_b8"]["throughput_rps"]
        assert e8 >= 1.4 * e1, (
            f"continuous batching regressed: engine[b=8] {e8:.1f} items/s "
            f"< 1.4x engine[b=1] {e1:.1f} items/s")
        paced = next(r for r in rows if "budget_ms" in r)
        slack_ms = 50.0            # service + GIL/scheduler noise on CI
        assert paced["latency_p99_ms"] <= paced["budget_ms"] + slack_ms, (
            f"open-loop p99 {paced['latency_p99_ms']:.1f}ms exceeds "
            f"budget {paced['budget_ms']:g}ms + {slack_ms:g}ms slack")
        print(f"smoke ok: micro-batch {best_mb:.0f} > sequential "
              f"{seq:.0f} items/s; engine b8/b1 {e8 / e1:.2f}x; "
              f"paced p99 {paced['latency_p99_ms']:.1f}ms within "
              f"budget+slack")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
