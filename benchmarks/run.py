"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,table2]

Prints ``name,us_per_call,derived`` CSV rows (harness contract).
"""

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


MODULES = [
    ("fig5", "benchmarks.fig5_latency"),          # Fig 5A/5B latency + blocking
    ("table2", "benchmarks.table2_convergence"),  # Table 2 FSDP/DiLoCo/NoLoCo
    ("fig2", "benchmarks.fig2_curves"),           # Fig 2 loss trajectories
    ("fig3", "benchmarks.fig3_weight_variance"),  # Fig 3B std ~ LR (Thm 1)
    ("fig4", "benchmarks.fig4_routing"),          # Fig 4 routing ablation
    ("table3", "benchmarks.table3_batch_size"),   # Table 3 batch-size ablation
    ("kernels", "benchmarks.kernel_bench"),       # Pallas kernel roofline est.
    ("engine", "benchmarks.engine_bench"),        # TrainLoop throughput -> BENCH_engine.json
    ("serve", "benchmarks.serve_bench"),          # continuous vs static batching -> BENCH_serve.json
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset keys")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for key, modname in MODULES:
        if only and key not in only:
            continue
        try:
            mod = __import__(modname, fromlist=["main"])
            mod.main()
        except Exception:
            failures += 1
            print(f"{key},0,FAILED", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
