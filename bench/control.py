"""Read the correctness numbers of a cell on many seeds in one process: the
program's widest logit gap and, beside it, the control's (the reference
computed with float8 weights). These readings set a cell's limit
(``bench/cells/<cell>.json``); the benchmark's own runs never compute the
control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 8

One JSON line per seed on stdout. Exits 2 unless a TPU listed in
``bench/peaks.json`` is present.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program's numbers only")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import check, device, harness
    from bench.registry import Bench

    bench = Bench(ROOT)
    try:
        dev = device.require(bench.workload(args.workload)["chips"])
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    device.start_process()
    clock = device.CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(bench=bench, name=args.workload, seed=seed,
                          seconds=args.seconds, trace=False,
                          t_start=time.perf_counter(), clock=clock,
                          control=not args.no_control).load()
        run.device = dict(dev)
        run.peaks = device.load_peaks()[dev["kind"]]
        harness.execute(run)
        print(json.dumps({"seed": seed, "setup_s": run.setup_s,
                          "correct": check.passed(run.checks),
                          **run.info["check"],
                          "compiles_in_window":
                              run.info.get("compiles_in_window"),
                          "e2e": run.e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
