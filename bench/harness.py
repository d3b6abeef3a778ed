"""One run of one cell: the state a driver fills, and the result line."""
from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, field
from typing import Optional

from bench import check
from bench.registry import Bench


@dataclass
class Run:
    bench: Bench
    name: str
    seed: int
    seconds: float
    trace: bool
    t_start: float
    clock: object = None
    gc_clock: object = None
    kernel_impl: str = "auto"
    control: bool = False        # also read the float8 control (control.py)
    peaks: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    # filled from the registry
    workload: dict = field(default_factory=dict)
    conf: dict = field(default_factory=dict)
    mix: dict = field(default_factory=dict)
    cell: dict = field(default_factory=dict)
    trace_dir: str = ""
    # filled by the driver
    setup_s: Optional[float] = None
    e2e: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    stats: dict = field(default_factory=dict)
    extra: dict = field(default_factory=dict)
    info: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    trace_path: Optional[str] = None
    reduced: object = None

    def load(self) -> "Run":
        self.workload = self.bench.workload(self.name)
        self.conf = self.bench.config(self.workload["config"])
        self.mix = self.bench.traffic(self.workload["traffic"])
        self.cell = self.bench.cell(self.name)
        if not self.trace_dir:
            self.trace_dir = os.path.join(self.bench.root, ".bench_out",
                                          "trace", self.name)
        return self


def execute(run: Run) -> None:
    """Drive the cell, then reduce its trace when there is one."""
    from bench import tracing

    run.bench.driver(run.mix["driver"]).run(run)
    if run.trace and run.trace_path:
        run.reduced = tracing.Reduced(tracing.extract(run.trace_path))


def metrics(run: Run) -> dict:
    """End-to-end metrics (untraced run) or per-layer metrics (traced)."""
    out = {}
    if not run.trace:
        for m in run.bench.metrics_for(run.name, "end_to_end"):
            v = run.setup_s if m["name"] == "setup_s" \
                else run.e2e.get(m["name"])
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out
    for m in run.bench.metrics_for(run.name, "per_layer"):
        v = run.bench.reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result(run: Run) -> dict:
    dev = dict(run.device)
    out = {"correct": check.passed(run.checks), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics(run), "device": dev}
    if run.trace and run.reduced is not None:
        dev["busy_s"] = run.reduced.busy_s
        dev["window_s"] = run.reduced.window_s
        out["breakdown"] = run.reduced.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out


def report(run: Run, res: dict) -> None:
    """Earlier lines (set-up facts, window facts, end-to-end extras), then
    the compared numbers last on stderr, and the result line last on
    stdout."""
    info = dict(run.info)
    info["setup_s"] = run.setup_s
    info["e2e"] = run.e2e
    if run.trace and run.reduced is not None:
        info["trace_window_s"] = run.reduced.window_s
    print("info " + json.dumps(info, default=str), flush=True)
    for k, (v, lim) in run.checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
