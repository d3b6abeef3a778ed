"""Host cost of one program span (``repro.obs.trace``), with the profiler
off and on: microseconds per ``with tracer.span(...)`` carrying two args,
and per ``tracer.instant(...)``, on the disabled bundle every engine runs
on by default (``NULL_OBS``: a profiler annotation, no ring buffer) and on
an enabled one (annotation and ring buffer).

    PYTHONPATH=src python benchmarks/span_cost.py [--n 100000]

Prints one JSON line: ``{"off": {...}, "on": {...}}``, each with
``null_span_us``, ``enabled_span_us`` and ``null_instant_us``.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro.obs import NULL_OBS, Observability


def _per_call_us(fn, n: int) -> float:
    t = time.perf_counter()
    fn(n)
    return (time.perf_counter() - t) / n * 1e6


def measure(n: int) -> dict:
    null, on = NULL_OBS.tracer, Observability(trace_capacity=1024).tracer

    def spans(tr):
        def go(k):
            for i in range(k):
                with tr.span("bench", "serve.cost", slot=i, rows=8) as sp:
                    sp["tokens"] = i
        return go

    def instants(k):
        for i in range(k):
            null.instant("bench", "serve.cost_point", slot=i)

    return {"null_span_us": _per_call_us(spans(null), n),
            "enabled_span_us": _per_call_us(spans(on), n),
            "null_instant_us": _per_call_us(instants, n)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    args = ap.parse_args(argv)
    measure(1000)                                  # warm up
    out = {"n": args.n, "off": measure(args.n)}
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["on"] = measure(args.n)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
