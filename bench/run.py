"""Run one benchmark cell once on the accelerator this process starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (weights and profiles from the seed, warm-up of every shape the
cell's traffic uses, compile-cache loads) counts as ``setup_s``; then the
window measures for ``--seconds``; then the served output is compared
with the plain reference. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace. The
last stdout line is the JSON result; the numbers compared, each with its
limit, are the last stderr lines and the result's last key.

Exits 2, printing no result, unless JAX finds a TPU whose device kind is
in ``bench/peaks.json`` and as many chips as the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device, harness
    from bench.registry import Bench

    bench = Bench(ROOT)
    run = harness.Run(bench=bench, name=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START).load()
    try:
        run.device = device.require(run.workload["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    run.peaks = device.load_peaks()[run.device["kind"]]

    device.start_process()
    run.clock = device.CompileClock()
    run.gc_clock = device.GcClock()
    harness.execute(run)
    harness.report(run, harness.result(run))
    return 0


if __name__ == "__main__":
    sys.exit(main())
