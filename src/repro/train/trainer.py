"""Trainer loop driver: checkpoint hooks, straggler watchdog, preemption,
resume, and host syncs ONLY at log/checkpoint cadence.

Production posture: the loop is restartable at any step (data position is
part of the checkpoint), SIGTERM triggers checkpoint-and-exit, slow steps
are recorded and fed to the data re-balancer.

Metrics stay on DEVICE per step — the loop buffers the (async) metric trees
and fetches them in ONE device→host transfer at each sync boundary
(`log_every`, checkpoint, end of run). Straggler detection moves with it:
per-step device time is unobservable without a per-step block, so the
watchdog scores each flushed WINDOW's per-step average wall time
(`StepWatchdog.window_end`) and flags the whole window. Subclasses hook
the boundaries:

- `next_batch()`      — how a step's batch is assembled
- `on_sync(recs)`     — runs after every flush with the new host records
                        (onboarding admits/evicts/graduates here)
- `should_stop()`     — early-exit check (e.g. onboarding queue drained)
- `extra_state()` / `restore_extra()` — manifest payload for exact resume
"""
from __future__ import annotations

import time
import zipfile
from typing import Callable, List, Optional

import jax
import numpy as np

from repro import obs as OBS
from repro.checkpoint import CheckpointManager
from repro.distributed.fault import PreemptionHandler, StepWatchdog
from repro.obs import trace as TR
from repro.resilience.integrity import CheckpointCorruptError


class Trainer:
    def __init__(self, step_fn: Callable, state, loader, *,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
                 keep_last: int = 3, watchdog: Optional[StepWatchdog] = None,
                 preemption: Optional[PreemptionHandler] = None,
                 log_every: int = 10, rng=None, fault_plan=None, obs=None):
        self.step_fn = step_fn
        self.state = state
        self.loader = loader
        self.step = 0
        self.ckpt_every = ckpt_every
        self.mgr = CheckpointManager(ckpt_dir, keep_last,
                                     fault_plan=fault_plan) \
            if ckpt_dir else None
        # observability: the straggler watchdog IS the train-side metric
        # source (repro.obs.metrics absorbed it) — wiring the bundle's
        # registry in gives p50/p99 gang-step time for free, and the gang
        # step's trace counter feeds the retrace sentinel below
        self.obs = OBS.get(obs)
        if watchdog is None:
            watchdog = StepWatchdog(
                registry=self.obs.metrics if self.obs.enabled else None)
        self.watchdog = watchdog
        tc = getattr(step_fn, "trace_counter", None)
        if tc is not None:
            self.obs.sentinel.watch("train.gang_step",
                                    lambda: tc["traces"], budget=1)
        self.preemption = preemption
        self.log_every = log_every
        self.rng = rng if rng is not None else jax.random.key(0)
        self.history = []
        # buffered (step, device-metric-tree) tuples since the last flush:
        # nothing here blocks on the device
        self._pending: List[tuple] = []
        self._window_t0: Optional[float] = None
        self.host_syncs = 0

    # ------------------------------------------------------------- recovery
    def try_resume(self) -> bool:
        """Resume from the newest checkpoint that verifies — a torn or
        corrupt latest checkpoint falls back to the one before it (and so
        on), never fails the run."""
        if not self.mgr:
            return False
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        for latest in reversed(self.mgr.all_steps()):
            try:
                state = self.mgr.restore(latest, abstract)
            except (CheckpointCorruptError, OSError, ValueError,
                    zipfile.BadZipFile):
                continue  # torn/corrupt payload: walk back one checkpoint
            self.state = state
            man = self.mgr.manifest(latest)
            self.step = man["step"]
            self.restore_extra(man["extra"])
            return True
        return False

    def extra_state(self) -> dict:
        """Manifest payload for exact resume (subclasses extend)."""
        rng_data = np.asarray(jax.random.key_data(self.rng)).tolist()
        return {"loader": self.loader.state_dict(), "rng": rng_data}

    def restore_extra(self, extra: dict) -> None:
        self.loader.load_state_dict(extra["loader"])
        if "rng" in extra:
            self.rng = jax.random.wrap_key_data(
                jax.numpy.asarray(extra["rng"], dtype="uint32"))

    def checkpoint(self, blocking=True):
        if self.mgr:
            self.flush()  # history/manifest must reflect all taken steps
            self.mgr.save(self.step, self.state, blocking=blocking,
                          extra=self.extra_state())

    # ----------------------------------------------------------------- hooks
    def next_batch(self) -> dict:
        return {k: jax.numpy.asarray(v)
                for k, v in self.loader.next().items()}

    def on_sync(self, recs: list) -> None:
        """Called after each metric flush with the new host records."""

    def should_stop(self) -> bool:
        return False

    # ----------------------------------------------------------------- sync
    def flush(self) -> list:
        """ONE device→host transfer for every buffered step's metrics;
        appends the float records to `history` and returns them. The
        transfer drains the window's queued device work, so the elapsed
        wall time here is the window's true step time — fed to the
        watchdog as the per-step average."""
        if not self._pending:
            return []
        steps, mets = zip(*self._pending)
        self._pending = []
        with self.obs.tracer.span(TR.CAT_GANG_STEP, "train.flush",
                                  steps=len(steps)):
            host = jax.device_get(list(mets))
        self.host_syncs += 1
        slow = False
        if self._window_t0 is not None:
            now = time.perf_counter()
            slow = self.watchdog.window_end(
                len(steps), now - self._window_t0)
            # one span per flushed WINDOW (per-step device time is not
            # observable without a per-step block — same reasoning as the
            # watchdog scoring above); sentinel check rides the boundary
            self.obs.tracer.complete(TR.CAT_GANG_STEP, "train.gang_window",
                                     self._window_t0, now,
                                     steps=len(steps), straggler=slow)
            self.obs.metrics.inc("train.steps", len(steps))
            self._window_t0 = None
        self.obs.sentinel.check()
        recs = []
        for s, mh in zip(steps, host):
            rec = {k: float(v) for k, v in mh.items()}
            rec["step"] = s
            rec["straggler"] = slow
            recs.append(rec)
        self.history.extend(recs)
        return recs

    def sync(self) -> list:
        recs = self.flush()
        if recs:
            self.on_sync(recs)
        return recs

    # ----------------------------------------------------------------- loop
    def run(self, num_steps: int) -> list:
        for _ in range(num_steps):
            if self.preemption and self.preemption.preempted():
                self.sync()
                self.checkpoint(blocking=True)
                break
            if self.should_stop():
                break
            batch = self.next_batch()
            self.rng, sub = jax.random.split(self.rng)
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            self.state, metrics = self.step_fn(self.state, batch, sub)
            self.step += 1
            self._pending.append((self.step, metrics))
            if self.step % self.log_every == 0:
                recs = self.sync()
                if recs:
                    rec = recs[-1]
                    print(f"step {self.step} " +
                          " ".join(f"{k}={v:.4f}" for k, v in rec.items()
                                   if isinstance(v, float)))
            if self.mgr and self.step % self.ckpt_every == 0:
                self.sync()
                self.checkpoint(blocking=False)
        self.sync()
        if self.mgr:
            self.mgr.wait()
        return self.history
