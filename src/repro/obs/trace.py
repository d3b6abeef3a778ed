"""The program's one span system: every span is a
``jax.profiler.TraceAnnotation`` on the profiler's clock, and, when the
bundle is enabled, also a Chrome-trace-event record in a ring buffer
(``--trace PATH`` of the launchers exports it; open in Perfetto /
``chrome://tracing``).

Spans cover the host-side orchestration the aggregate counters can't
explain: admission waves and their parts (probe, hydrate, aggregate, mask
scatter, prefill), the decode sync and its parts (fetch, distribute,
window refresh), preempt/resume, spec rounds, gang-step flushes, the
onboarding poll and graduation, degraded/quarantine events. Nothing here
ever touches the device: a span brackets work the host was already
doing, so tracing changes no compiled program and no sync schedule.

The annotation is always opened, whether or not the bundle is enabled
(``NULL_OBS`` included): with the profiler off it is an inert native
object (under a microsecond), with it on the span lands on the host
plane of any ``jax.profiler`` capture beside the device's ops. Args are
attached only while a capture runs. Every span name starts with
``serve.`` or ``train.``.

The ring buffer is bounded (``deque(maxlen=capacity)``): leaving the
tracer on forever costs a fixed few MB and drops the OLDEST events, never
blocks. ``dropped`` counts evictions so an exported trace says whether it
is a suffix of the run.

Each category gets its own fake thread id so Perfetto renders one lane
per subsystem; "M" metadata events name the lanes.
"""
from __future__ import annotations

import json
import os
import re
import time
from collections import deque
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

# Canonical categories. Emitters may use others, but these are the lanes
# the obs smoke asserts are present end-to-end.
CAT_ADMISSION = "admission"
CAT_PREFILL = "prefill"
CAT_DECODE_WINDOW = "decode-window"
CAT_PREEMPT = "preempt"
CAT_SPEC = "spec"
CAT_GANG_STEP = "gang-step"
CAT_GRADUATION = "graduation"
CAT_RESILIENCE = "resilience"

# span names start with one of these, so no name of the program's can
# equal one a caller (a benchmark harness) writes around it
PREFIXES = ("serve.", "train.")


def _profiler_args(args: dict) -> dict:
    """Args as the profiler's metadata takes them: numbers and bools as
    they are, anything else as a string without the encoding's ``,#=``."""
    out = {}
    for k, v in args.items():
        if not isinstance(v, (bool, int, float)):
            v = re.sub(r"[,#=]", " ", str(v))
        out[k] = v
    return out


class SpanTracer:
    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 clock=time.perf_counter):
        self.enabled = enabled
        self.capacity = capacity
        self.clock = clock
        self.dropped = 0
        self._events = deque(maxlen=capacity)
        self._tids: Dict[str, int] = {}
        self._pid = os.getpid()

    # ------------------------------------------------------------------ write
    def _tid(self, cat: str) -> int:
        tid = self._tids.get(cat)
        if tid is None:
            tid = self._tids[cat] = len(self._tids) + 1
        return tid

    def _emit(self, ev: dict) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append(ev)

    def span(self, cat: str, name: str, **args) -> "_Span":
        """A span around a host-side block (``with tracer.span(...) as
        args:``): always a profiler annotation, and a complete-event ("X")
        record when enabled. Yields the args dict so the body can attach
        results (e.g. admitted count); the profiler gets them as the
        span's metadata at its end."""
        return _Span(self, cat, name, args)

    def complete(self, cat: str, name: str, t0: float, t1: float,
                 **args) -> None:
        """Retroactive "X" span over [t0, t1] (same clock as `span`) — for
        intervals whose start predates the emit site, e.g. a decode window
        opened by the previous sync. Ring buffer only: the profiler takes
        no span after the fact."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "X", "ts": t0 * 1e6,
                    "dur": (t1 - t0) * 1e6, "pid": self._pid,
                    "tid": self._tid(cat), "args": args})

    def instant(self, cat: str, name: str, **args) -> None:
        """Zero-duration marker ("i") for point events (degraded request,
        quarantine, retry, preemption): a profiler annotation of no
        length, and a record when enabled."""
        on = TraceAnnotation.is_enabled()
        with TraceAnnotation(name, **(_profiler_args(args) if on else {})):
            pass
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": self.clock() * 1e6, "pid": self._pid,
                    "tid": self._tid(cat), "args": args})

    # ------------------------------------------------------------------- read
    def events(self) -> list:
        return list(self._events)

    def category_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for ev in self._events:
            out[ev["cat"]] = out.get(ev["cat"], 0) + 1
        return out

    def export(self, path: str) -> dict:
        """Write Chrome JSON trace format; returns the written object."""
        meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                 "tid": tid, "args": {"name": cat}}
                for cat, tid in self._tids.items()]
        doc = {"traceEvents": meta + self.events(),
               "displayTimeUnit": "ms",
               "otherData": {"dropped_events": self.dropped}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc

    def reset(self) -> None:
        self._events.clear()
        self.dropped = 0


class _Span:
    """One open span of a ``SpanTracer`` (a plain class: a generator-based
    context manager costs several times the annotation itself)."""

    __slots__ = ("tracer", "cat", "name", "args", "tm", "t0")

    def __init__(self, tracer: SpanTracer, cat: str, name: str, args: dict):
        self.tracer, self.cat, self.name, self.args = tracer, cat, name, args

    def __enter__(self) -> dict:
        self.tm = TraceAnnotation(self.name)
        self.tm.__enter__()
        self.t0 = self.tracer.clock() if self.tracer.enabled else 0.0
        return self.args

    def __exit__(self, *exc) -> None:
        tr = self.tracer
        t1 = tr.clock() if tr.enabled else 0.0
        if self.args and TraceAnnotation.is_enabled():
            self.tm.set_metadata(**_profiler_args(self.args))
        self.tm.__exit__(None, None, None)
        if tr.enabled:
            tr._emit({"name": self.name, "cat": self.cat, "ph": "X",
                      "ts": self.t0 * 1e6, "dur": (t1 - self.t0) * 1e6,
                      "pid": tr._pid, "tid": tr._tid(self.cat),
                      "args": self.args})


def validate_chrome_trace(doc: dict) -> Optional[str]:
    """Return None if `doc` is a loadable Chrome trace, else the problem.
    Used by the obs smoke and tests; intentionally strict about the fields
    Perfetto's importer needs."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return "missing traceEvents"
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            return f"event {i} not an object"
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                return f"event {i} missing {k!r}"
        if ev["ph"] in ("X", "i") and "ts" not in ev:
            return f"event {i} ({ev['ph']}) missing ts"
        if ev["ph"] == "X" and "dur" not in ev:
            return f"event {i} (X) missing dur"
    return None
