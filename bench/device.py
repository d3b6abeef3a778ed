"""The accelerator a run is on: the refusal to run anywhere else, the table
of peaks, the compile counter and the memory peak."""
from __future__ import annotations

import gc
import json
import os
import time

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark can measure."""


def load_peaks(path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)["devices"]


def require(chips: int, peaks: dict = None) -> dict:
    """The device record of the result line, or NoChip: the benchmark runs
    only on a TPU whose ``device_kind`` is in ``peaks.json``, with at least
    ``chips`` devices. A CPU is never a fallback."""
    import jax

    peaks = load_peaks() if peaks is None else peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"({sorted(peaks)})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def start_process() -> None:
    """Compile-cache settings of every benchmark process: the program's own
    fixed cache directory, and every program kept there however fast it
    compiled, so only a cell's first run in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Seconds and events JAX spent tracing, lowering and compiling since
    `reset()`, with the name of each function compiled. A program that
    comes from the persistent cache still traces and lowers, so any event
    inside a measured window means a shape was not warmed up."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs
            if event.endswith("backend_compile_duration"):
                self.events.append(str(kw.get("fun_name", "?")))

    def reset(self) -> None:
        self.secs = 0.0
        self.events = []


class GcClock:
    """Seconds the Python garbage collector ran since ``reset()``, and its
    longest single pass: a host stall the window's timings include."""

    def __init__(self):
        self.reset()
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.secs += d
            self.longest = max(self.longest, d)
            self._t = None

    def reset(self) -> None:
        self.secs = 0.0
        self.longest = 0.0


def window_facts(run) -> dict:
    """What the clocks saw inside the window: compiles (any tracing,
    lowering or compiling means a shape was not warmed up) and garbage
    collection."""
    out = {"compiles_in_window": len(run.clock.events),
           "compiled_in_window": sorted(set(run.clock.events))[:20],
           "compile_s_in_window": run.clock.secs}
    if run.gc_clock is not None:
        out.update(gc_s_in_window=run.gc_clock.secs,
                   gc_longest_s_in_window=run.gc_clock.longest)
    return out


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    keeps no statistics)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
