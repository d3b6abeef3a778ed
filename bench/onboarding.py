"""Onboarding cells: the program's roster trainer, built by
``build_onboarding_run`` and driven through ``trainer.run``, on rows the
benchmark makes from the seed.

Set-up makes the frozen weights and the roster's first trainables from the
seed, then drives the trainer through its first steps: step 1 (its Adam
state gives the first gradient as the optimizer got it), steps 2-3 (the
trainables after three steps), and on to the first graduation, so that
graduating, freeing and refilling slots has run once before the window.
The window then runs the trainer in calls of one sync interval each,
slots graduating and refilling as they reach the step count, until the
window's time is up; the rate counts every step of every call and the
time to the end of the last. Afterwards the reference
(``bench/train_reference.py``) repeats the first three steps from the
same trainables, rows and keys, and the check compares the two.

Spans: ``gang_step`` around each call into the trainer, ``lifecycle``
around its graduation and refill pass.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import check, device, model, tracing
from bench.tracing import span

TRACE_S = 8.0           # traced part of the window, from its start
CHECK_STEPS = 3


class Rows:
    """Training rows from the seed: token ids uniform over the vocabulary,
    labels the next token; every row of every step differs. Any profile's
    rows are drawn alike (what a slot learns does not change the work)."""

    def __init__(self, seed: int, vocab: int):
        self.seed, self.vocab = int(seed), vocab

    def sample(self, step: int, batch: int, seq_len: int, profile_ids=None):
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 40, int(step)]))
        x = rng.integers(0, self.vocab, (batch, seq_len + 1), dtype=np.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    def batch(self, step: int, slots: int, per_slot: int, seq_len: int):
        """Step ``step``'s rows as the roster batcher shapes them:
        [slots, per_slot, seq_len]."""
        b = self.sample(step, slots * per_slot, seq_len)
        return {k: v.reshape(slots, per_slot, seq_len) for k, v in b.items()}


def _keys(seed: int):
    w = model.seed_words(seed, 3)
    return int(w[1] >> 1), int(w[2] >> 1)


def build(run):
    """Frozen weights, rows, and the program's onboarding trainer, with the
    roster's first trainables replaced by the benchmark's."""
    import jax
    import jax.numpy as jnp

    from repro.train.onboarding import GraduationPolicy, build_onboarding_run

    conf, R, mix = run.conf, run.cell["roster"], run.mix
    s = model.sizes(conf)
    cfg = model.model_config(conf, run.kernel_impl)
    frozen = model.init_params(run.seed, conf)
    jax.block_until_ready(frozen)
    rows = Rows(run.seed, s["V"])
    roster_seed, rng_seed = _keys(run.seed)
    policy = GraduationPolicy(min_steps=R["graduate_at"],
                              max_steps=R["graduate_at"])
    trainer, _ = build_onboarding_run(
        cfg, rows, range(mix["profiles"]["count"]), slots=R["slots"],
        per_slot=R["per_slot"], seq_len=mix["seq_len"], policy=policy,
        lr=R["lr"], seed=roster_seed, frozen=frozen,
        rng=jax.random.key(rng_seed), log_every=R["sync_every"])
    first = model.init_profiles(run.seed, conf, R["slots"])
    st = trainer.state["roster"]
    table = {k: jax.device_put(jnp.asarray(first[k], v.dtype), v.sharding)
             for k, v in st["trainable"]["table"].items()}
    trainer.state["roster"] = dict(
        st, trainable=dict(st["trainable"], table=table))
    return frozen, rows, trainer, first


def _host(tree) -> dict:
    import jax

    return {k: np.asarray(v, np.float32) for k, v in
            jax.device_get(tree).items()}


def first_steps(trainer) -> dict:
    """Drive the trainer through its first three steps with its own call,
    reading what the check compares."""
    rows0 = _host(trainer.state["roster"]["trainable"]["table"])
    trainer.run(1)
    m1 = _host(trainer.state["roster"]["opt"]["m"]["table"])
    trainer.run(CHECK_STEPS - 1)
    rows3 = _host(trainer.state["roster"]["trainable"]["table"])
    return {"rows0": rows0, "rows3": rows3,
            "grad1": {k: v / 0.1 for k, v in m1.items()},   # m1 = (1-b1) g
            "losses": [r["loss"] for r in trainer.history[:CHECK_STEPS]]}


class Lifecycle:
    """The harness's span around the scheduler's graduation and refill
    pass."""

    def __init__(self, scheduler):
        orig = scheduler.poll

        def poll(rstate, batcher):
            with span("lifecycle"):
                return orig(rstate, batcher)

        scheduler.poll = poll


def window(run, trainer) -> dict:
    """Calls of one sync interval until the window's time is up."""
    tw = tracing.Window(run.trace_dir) if run.trace else None
    K = run.cell["roster"]["sync_every"]
    marks = {"steps0": trainer.step, "history0": len(trainer.history)}
    if tw is not None:
        tw.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        if trainer.should_stop():
            # the roster drained: the rest of the window has no work
            marks["drained_s"] = time.perf_counter() - t0
            break
        with span("gang_step"):
            trainer.run(K)
        if tw is not None and tw.on and \
                time.perf_counter() - t0 >= min(TRACE_S, run.seconds):
            marks["trace_end"] = tw.stop()
            marks["trace_steps"] = trainer.step - marks["steps0"]
    marks["elapsed"] = time.perf_counter() - t0
    if tw is not None and tw.on:
        marks["trace_end"] = tw.stop()
        marks["trace_steps"] = trainer.step - marks["steps0"]
    if tw is not None:
        run.trace_path = tw.path
    return marks


def run(run) -> None:
    """One onboarding run: set-up, window, readings, correctness."""
    R, mix = run.cell["roster"], run.mix
    frozen, rows, trainer, first = build(run)
    Lifecycle(trainer.scheduler)
    prog = first_steps(trainer)
    prog["rng_seed"] = _keys(run.seed)[1]
    # on to the first graduation: every lifecycle op runs before the window
    trainer.run(R["graduate_at"] - CHECK_STEPS)
    run.info["setup_steps"] = trainer.step
    run.info["graduated_in_setup"] = len(trainer.scheduler.graduated)
    gc.collect()
    run.setup_s = time.perf_counter() - run.t_start
    run.clock.reset()
    if run.gc_clock is not None:
        run.gc_clock.reset()
    marks = window(run, trainer)
    run.info["roster_drained_s"] = marks.get("drained_s")
    run.info.update(device.window_facts(run))
    run.device["memory_peak_bytes"] = device.peak_bytes()
    steps = trainer.step - marks["steps0"]
    tokens_per_step = R["slots"] * R["per_slot"] * mix["seq_len"]
    recs = trainer.history[marks["history0"]:]
    run.e2e = {"onboard_tokens_per_s": steps * tokens_per_step
               / marks["elapsed"], "steps": steps,
               "window_s": marks["elapsed"]}
    run.attempted = steps
    run.failed = sum(r.get("nonfinite_slots", 0) > 0 for r in recs)
    run.stats = dict(trainer.scheduler.stats())
    run.extra.update(examples=R["slots"] * R["per_slot"],
                     seq_len=mix["seq_len"],
                     trace_steps=marks.get("trace_steps"))
    del trainer
    gc.collect()
    batches = [rows.batch(t, R["slots"], R["per_slot"], mix["seq_len"])
               for t in range(CHECK_STEPS)]
    run.checks = check.training(run, frozen, first, batches, prog,
                                control=run.control)
