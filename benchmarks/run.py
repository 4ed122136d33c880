"""Benchmark runner: one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV (stdout).  Select subsets with
``python -m benchmarks.run --only table2,fig3``.  The ``grid`` benchmark
additionally writes a machine-readable ``BENCH_grid.json`` perf-trajectory
record (``--json-dir`` controls where; ``--quick`` selects the small CI
profile).
"""

import argparse
import sys
import time

REGISTRY = [
    ("table2", "benchmarks.table2_pasmo"),
    ("fig3", "benchmarks.fig3_stepsizes"),
    ("fig4", "benchmarks.fig4_multi"),
    ("ablation", "benchmarks.ablation_wss"),
    ("grid", "benchmarks.grid_bench"),
    ("lm_step", "benchmarks.lm_step_bench"),
    ("roofline", "benchmarks.roofline_table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _ in REGISTRY))
    ap.add_argument("--quick", action="store_true",
                    help="small CI profile for benchmarks that support "
                         "profiles (currently: grid)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for machine-readable BENCH_*.json "
                         "records (currently: BENCH_grid.json)")
    ap.add_argument("--check-only", action="store_true",
                    help="validate the schema/fingerprint of every "
                         "BENCH_*.json under --json-dir and exit — no "
                         "benchmark runs, no jax import")
    args = ap.parse_args()
    if args.check_only:
        import glob
        import os

        from benchmarks.bench_gate import check_only
        paths = sorted(glob.glob(os.path.join(args.json_dir,
                                              "BENCH_*.json")))
        if not paths:
            sys.exit(f"run --check-only: no BENCH_*.json under "
                     f"{args.json_dir}")
        sys.exit(check_only(paths))

    import os

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)  # f64 QP solves (paper)
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import emit
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {k for k, _ in REGISTRY}
        if unknown:
            sys.exit(f"unknown benchmark(s): {','.join(sorted(unknown))}; "
                     f"choose from: {','.join(k for k, _ in REGISTRY)}")

    import importlib
    import json

    # provenance header: the same environment fingerprint every
    # BENCH_*.json record carries, for runs that only keep the CSV
    from repro.telemetry import env_fingerprint
    print(f"# fingerprint: {json.dumps(env_fingerprint(), sort_keys=True)}",
          flush=True)

    failures = 0
    for key, module in REGISTRY:
        if only is not None and key not in only:
            continue
        t0 = time.time()
        print(f"# --- {key} ({module}) ---", flush=True)
        try:
            mod = importlib.import_module(module)
            if key == "grid":
                json_path = os.path.join(args.json_dir, "BENCH_grid.json")
                rows = mod.run(profile="quick" if args.quick else "full",
                               json_path=json_path)
                emit(rows)
                print(f"# wrote {json_path}", flush=True)
            else:
                emit(mod.run())
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{key}/ERROR,0,{type(e).__name__}:{e}", flush=True)
        print(f"# {key} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
