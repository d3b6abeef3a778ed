"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus per-table headers).

  Table 1 / Fig 1   -> table1_memory       (params & bytes per profile)
  Tables 2/3        -> glue_sim            (xp vs ho vs sa ordering proxy)
  Fig 5 a/b/c       -> ablations           (N, soft/hard, tied masks, k)
  Tables 8/9        -> train_time          (step time vs N)
  kernels           -> kernel_bench        (sparse agg + fused adapter,
                                            emits BENCH_kernels.json)
  serve             -> serve_bench         (decode tok/s + admission bytes,
                                            emits BENCH_serve.json)
  train lifecycle   -> train_bench         (gang step + onboarding rate,
                                            emits BENCH_train.json)

The dry-run roofline (``benchmarks/roofline_report.py``) reads artifacts
that ``repro.launch.dryrun`` writes; run it on its own after a dry-run.
"""
from __future__ import annotations

import time
import traceback


def main() -> None:
    from benchmarks import (ablations, glue_sim, kernel_bench, serve_bench,
                            table1_memory, train_bench, train_time)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    suites = [
        ("table1_memory", table1_memory.main),
        ("kernel_bench", kernel_bench.main),
        ("serve_bench", serve_bench.main),
        ("train_bench", train_bench.main),
        ("train_time", train_time.main),
        ("ablations", ablations.main),
        ("glue_sim", glue_sim.main),
    ]
    failures = 0
    for name, fn in suites:
        print(f"\n==== {name} ====")
        t0 = time.time()
        try:
            fn()
            print(f"# {name} done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"# {name} FAILED")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
