"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Output: ``name,us_per_call,derived`` CSV rows.
  * bench_drspmm   — Fig. 11 (DR-SpMM fwd/bwd vs dense baseline, K × dim)
  * bench_parallel — Fig. 12 / Table 3 (kernel vs parallel-scheduling savings)
  * bench_kvalues  — Fig. 10 (K sweep: correlations + step time)
  * bench_table2   — Table 2 (DR-CircuitGNN vs GCN/SAGE/GAT correlations)
  * bench_lm       — LM substrate step timings (reduced configs)
"""

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer epochs")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = 0.04 if args.fast else 0.08
    epochs = 2 if args.fast else 6

    from benchmarks import (bench_drspmm, bench_kvalues, bench_lm,
                            bench_parallel, bench_table2)
    suites = {
        "drspmm": lambda: bench_drspmm.bench(scale=scale),
        "parallel": lambda: bench_parallel.bench(scale=scale),
        "kvalues": lambda: bench_kvalues.bench(scale=max(scale * 0.6, 0.03),
                                               epochs=max(epochs // 2, 2)),
        "table2": lambda: bench_table2.bench(scale=max(scale * 0.6, 0.03),
                                             epochs=epochs),
        "lm": bench_lm.bench,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    failed = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if name not in only:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
