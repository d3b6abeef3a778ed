"""Find the knee of an open-loop serving cell: one engine, set up once, then
one window per offered rate. The knee is the highest rate at which the
queue does not grow over the window; a cell's fixed rate is set below it.

    python bench/sweep.py --workload <cell> --rates 8,12,16 --seconds 20

One JSON line per rate: first-token and inter-token percentiles, queued
requests over the window's thirds, and the share of requests due in the
window that had their first token before it closed.
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
    ap.add_argument("--rates", required=True, help="requests/s, comma-"
                    "separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import device, harness, serving, traffic
    from bench.registry import Bench

    bench = Bench(ROOT)
    run = harness.Run(bench=bench, name=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=False,
                      t_start=time.perf_counter()).load()
    try:
        run.device = device.require(run.workload["chips"])
    except device.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    device.start_process()
    run.clock = device.CompileClock()
    vocab = run.conf["vocab_size"]
    _, _, eng = serving.build(run)
    hooks = serving.Hooks(eng)
    serving.warm_up(eng, run.mix, run.mix["profiles"]["count"], run.seed,
                    vocab)
    base = run.mix["arrivals"]["rate_per_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        serving.prewarm_cache(eng, run.mix, run.conf,
                              run.cell["engine"]["cache_bytes"], run.seed,
                              vocab)
        eng.reset_stats()
        arrivals = traffic.generate(run.mix, run.seed, args.seconds, vocab,
                                    rate_scale=rate / base)
        book = serving.Book(args.seconds)
        run.clock.reset()
        serving.window(run, eng, arrivals, book, True, hooks)
        e2e = serving.end_to_end(book, args.seconds, open_loop=True)
        d = np.asarray(book.depth or [(0.0, 0)], np.float64)
        thirds = [float(d[(d[:, 0] >= args.seconds * i / 3)
                          & (d[:, 0] < args.seconds * (i + 1) / 3), 1]
                        .mean()) if len(d) else None for i in range(3)]
        in_time = sum(r.first_s is not None and r.first_s <= args.seconds
                      for r in book.records) / max(len(book.records), 1)
        print(json.dumps({"rate_per_s": rate, "requests": len(arrivals),
                          "queued_by_third": thirds,
                          "first_token_in_window": in_time,
                          "compiles_in_window": len(run.clock.events),
                          "hit_rate": eng.serve_stats()["profile_cache"]
                          ["hit_rate"], **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
