"""Benchmark driver: one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV per the harness contract; full
row dicts go to experiments/bench_results.json.

``--trace out.json`` installs the process-global flight recorder
(:mod:`repro.obs`) for the whole run and exports a Chrome trace-event
file loadable in Perfetto / ``chrome://tracing`` — every compile pass,
vectorize sweep, tuner trial and engine phase across every benchmark
module lands in one timeline.  See ``docs/observability.md``.
"""
from __future__ import annotations

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MODULES = [
    "benchmarks.fig1_dataflow_latency",
    "benchmarks.fig5_app_latency",
    "benchmarks.fig6_opt_ladder",
    "benchmarks.fig8_backends",
    "benchmarks.table3_resources",
    "benchmarks.bench_kernels",
    "benchmarks.bench_serving",
    "benchmarks.bench_parallel",
    "benchmarks.bench_tuning",
    "benchmarks.lm_roofline",
]


def smoke() -> None:
    """Import every benchmark module and check its contract (--smoke).

    Keeps the scripts import-clean in CI without paying for the full
    measurement sweep.
    """
    import importlib
    failed = []
    for mod_name in MODULES:
        try:
            mod = importlib.import_module(mod_name)
            assert callable(getattr(mod, "run", None)), \
                f"{mod_name} has no run()"
            print(f"{mod_name}: import ok")
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"smoke failed for {failed}")
    print(f"smoke ok: {len(MODULES)} benchmark modules import clean")


def main() -> int:
    """Run every module; return the number of modules that raised.

    A failed module prints an ``ERROR`` row and the sweep goes on, so
    one broken figure does not hide the others — but the count becomes
    the exit code, so a sweep with a failure never reads as a pass.
    """
    import importlib
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.run()
        except Exception:
            print(f"{mod_name},nan,ERROR")
            traceback.print_exc()
            failed.append(mod_name)
            continue
        for r in rows:
            us = r.get("us", r.get("cpu_wall_us", r.get("ms", 0.0)))
            if "ms" in r and "us" not in r and "cpu_wall_us" not in r:
                us = r["ms"] * 1e3
            derived = ";".join(f"{k}={v}" for k, v in r.items()
                               if k not in ("name", "us", "cpu_wall_us"))
            print(f"{r['name']},{float(us):.1f},{derived}")
        all_rows.extend(rows)
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    if failed:
        print(f"FAILED modules: {failed}", file=sys.stderr)
    return len(failed)


def _trace_arg(argv: list[str]) -> str | None:
    """Pull the ``--trace out.json`` output path from argv (None if absent)."""
    if "--trace" not in argv:
        return None
    i = argv.index("--trace")
    if i + 1 >= len(argv):
        raise SystemExit("--trace requires an output path")
    return argv[i + 1]


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    _trace_out = _trace_arg(sys.argv)
    _tracer = None
    if _trace_out is not None:
        from repro.obs import install
        _tracer = install()
    _n_failed = 0
    if "--smoke" in sys.argv:
        smoke()
    else:
        _n_failed = main()
    if _tracer is not None:
        from repro.obs import export_chrome_trace
        _payload = export_chrome_trace(_tracer, _trace_out)
        print(f"trace: {len(_payload['traceEvents'])} events "
              f"({_tracer.dropped} dropped) -> {_trace_out}")
    sys.exit(1 if _n_failed else 0)
