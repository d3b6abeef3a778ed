"""Chaos soak: the resilience layer under a seeded FaultPlan.

Serves a multi-wave request stream against an engine whose hydration path
persistently fails for >= 20% of profiles and whose store carries >= 2
corrupted records, then proves the degradation contract end to end.
Records emitted into BENCH_fault.json (gated by benchmarks/check_bench.py):

- resilience.serve_chaos      every wave completes (failed_waves == 0);
                              degraded_requests == the count the PLAN
                              predicts (persistent failures + quarantined
                              corrupt records); no checksum-failing record
                              was ever served adapted; flaky hydrations
                              recovered via retry; unaffected requests
                              decode BITWISE identical to a no-fault run
- resilience.gang_guard       NaN-poisoned roster slot: healthy slots'
                              params AND Adam moments bitwise-equal to a
                              clean run, the poisoned slot's untouched,
                              the nonfinite counter saw every strike
- resilience.ckpt_fallback    torn-write (truncated) checkpoint: verify
                              rejects it, resume falls back to the last
                              good step
- resilience.onboard_quarantine  poisoned profiles are quarantined without
                              graduation and the lifecycle accounting
                              still closes: graduated + evicted +
                              quarantined == streamed
- resilience.elastic          (>= 8 devices only) surviving-mesh reshard
                              keeps roster values bitwise
"""
from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import BenchWriter, bench_config
from repro.configs import get_config, reduce_for_smoke
from repro.resilience import FaultPlan, RetryPolicy

# fast, deadline-safe retries for the soak (the defaults sleep for real)
SOAK_RETRY = RetryPolicy(attempts=3, delay_s=1e-4, max_delay_s=1e-3,
                         deadline_s=10.0)


def _build_engine(cfg, n_profiles, max_slots, plan=None):
    from repro.core import xpeft as XP
    from repro.core.profiles import ProfileStore
    from repro.models import init_lm
    from repro.serve.engine import ServeEngine

    xp = cfg.xpeft
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, xp.num_adapters, xp.bottleneck,
                         xp.mask_type, xp.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(n_profiles):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    eng = ServeEngine(cfg, params, store, max_slots=max_slots, max_seq=64,
                      fault_plan=plan, retry_policy=SOAK_RETRY)
    return eng, store


def serve_chaos(w: BenchWriter, smoke: bool):
    from repro.serve.engine import Request

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    n_prof = 8 if smoke else 16
    n_reqs = 2 * n_prof
    max_slots = 2 if smoke else 4

    # plan first, so the expected-degraded set is computed from the plan
    # alone (never from observing the run): >= 20% persistent hydration
    # failures by rate draw, plus 2 corrupted records chosen OUTSIDE the
    # failing set so every degraded request has exactly one cause
    plan = FaultPlan(seed=1234, hydration_fail_rate=0.25,
                     hydration_flaky_rate=0.2)
    pids = list(range(n_prof))
    fail_set = set(plan.persistent_fail_pids(pids))
    if len(fail_set) < max(1, n_prof // 5):  # guarantee the >= 20% floor
        extra = [p for p in pids if p not in fail_set]
        need = max(1, n_prof // 5) - len(fail_set)
        plan = FaultPlan(seed=plan.seed, hydration_fail_rate=0.25,
                         hydration_flaky_rate=0.2,
                         fail_pids=tuple(extra[:need]))
        fail_set = set(plan.persistent_fail_pids(pids))
    healthy = [p for p in pids if plan.hydration_mode(p) is None]
    assert len(healthy) >= 4, "chaos plan left too few healthy profiles"
    corrupt = tuple(healthy[-2:])
    plan = FaultPlan(seed=plan.seed, hydration_fail_rate=0.25,
                     hydration_flaky_rate=0.2, fail_pids=plan.fail_pids,
                     corrupt_pids=corrupt)
    flaky_set = set(plan.flaky_hydration_pids(pids))
    degraded_pids = fail_set | set(corrupt)

    def make_reqs():
        return [Request(uid=i, prompt=np.arange(4 + i % 5) % cfg.vocab_size,
                        profile_id=i % n_prof, max_new_tokens=6)
                for i in range(n_reqs)]

    # no-fault reference: same seed/requests on an uncorrupted store
    ref_eng, _ = _build_engine(cfg, n_prof, max_slots)
    ref = make_reqs()
    ref_eng.run_until_drained(list(ref))

    eng, store = _build_engine(cfg, n_prof, max_slots, plan)
    corrupt_events = plan.corrupt_store(store)
    reqs = make_reqs()
    eng.scheduler.submit(list(reqs))
    waves = failed_waves = 0
    for _ in range(10_000):
        free = eng.free_slots()
        if free and eng.scheduler.pending():
            waves += 1
            try:
                eng.admit_many(eng.scheduler.next_batch(len(free)))
            except Exception:
                failed_waves += 1
        if not eng.active_count():
            if not eng.scheduler.pending():
                break
            continue
        eng.step()
    eng.sync()

    stats = eng.serve_stats()
    expected = sum(1 for r in reqs if r.profile_id in degraded_pids)
    unaffected_bitwise = all(
        r.generated == rr.generated for r, rr in zip(reqs, ref)
        if r.profile_id not in degraded_pids)
    # a checksum-failing record "served" = a corrupt-pid request that
    # completed NON-degraded (i.e. its adapters were actually hydrated)
    corrupt_served = sum(1 for r in reqs
                         if r.profile_id in corrupt and not r.degraded)
    flaky_degraded = sum(1 for r in reqs
                         if r.profile_id in flaky_set and r.degraded)
    w.emit("resilience.serve_chaos", None,
           requests=len(reqs), waves=waves, failed_waves=failed_waves,
           all_done=int(all(r.done for r in reqs)),
           injected_fail_rate=round(len(fail_set) / n_prof, 3),
           corrupt_records=len(corrupt_events),
           corrupt_detected=stats["store_integrity"]["corrupt_detected"],
           corrupt_served=corrupt_served,
           expected_degraded=expected,
           degraded_requests=stats["degraded_requests"],
           flaky_profiles=len(flaky_set), flaky_degraded=flaky_degraded,
           hydration_retries=stats["hydration_retries"],
           quarantined_profiles=stats["quarantined_profiles"],
           unaffected_bitwise=bool(unaffected_bitwise))


def gang_guard(w: BenchWriter):
    from repro.data import ProfileClassification
    from repro.models import init_lm
    from repro.train.roster import Roster, init_roster_state
    from repro.train.steps import make_gang_step

    cfg = bench_config()
    S, m, steps = 4, 2, 3
    data = ProfileClassification(cfg.vocab_size, cfg.num_labels,
                                 num_profiles=S, seed=7)
    frozen = init_lm(jax.random.key(0), cfg)

    def run(plan):
        roster = Roster(cfg, jax.random.key(2), S)
        rstate = init_roster_state(jax.random.key(1), cfg, S)
        for s in range(S):
            rstate = roster.admit(rstate, s, s)
        step = jax.jit(make_gang_step(cfg, lr=5e-2, fault_plan=plan))
        state = {"frozen": frozen, "roster": rstate}
        pids = np.repeat(np.arange(S), m)
        b = data.sample(0, S * m, 12, profile_ids=pids)
        batch = {k: jnp.asarray(np.asarray(v).reshape((S, m) + v.shape[1:]))
                 for k, v in b.items()}
        met = None
        for _ in range(steps):
            state, met = step(state, batch, jax.random.key(3))
        return jax.device_get(state["roster"]), jax.device_get(met)

    poisoned_slot = 1
    # bitwise reference = the SAME compiled program (identical plan, poison
    # window that never fires): injection on vs off, not two different HLO
    # programs whose fusion differs by a ulp
    clean, _ = run(FaultPlan(poison_slots=(poisoned_slot,),
                             poison_from_step=10 ** 9))
    faulty, met = run(FaultPlan(poison_slots=(poisoned_slot,)))

    def rows(tree, s):
        return [np.asarray(leaf[s]) for leaf in jax.tree.leaves(tree)]

    healthy_bitwise = all(
        np.array_equal(a, b)
        for s in range(S) if s != poisoned_slot
        for a, b in zip(rows(clean, s), rows(faulty, s)))
    # untouched = params frozen at admission (clean trained them away) and
    # Adam moments still exactly zero
    poisoned_untouched = (
        all(not np.array_equal(a, b) for a, b in
            zip(rows(clean["trainable"], poisoned_slot),
                rows(faulty["trainable"], poisoned_slot))) and
        all(np.all(np.asarray(leaf)[poisoned_slot] == 0.0)
            for leaf in jax.tree.leaves(faulty["opt"]["m"]) +
            jax.tree.leaves(faulty["opt"]["v"])) and
        int(faulty["opt"]["step"][poisoned_slot]) == 0)
    w.emit("resilience.gang_guard", None,
           slots=S, steps=steps, poisoned_slot=poisoned_slot,
           healthy_bitwise=bool(healthy_bitwise),
           poisoned_untouched=bool(poisoned_untouched),
           nonfinite_detected=int(faulty["nonfinite"][poisoned_slot]),
           nonfinite_metric=int(met["nonfinite_slots"]),
           loss_finite=bool(np.isfinite(met["loss"])))


def ckpt_fallback(w: BenchWriter, tmp):
    from repro.checkpoint import CheckpointManager
    from repro.resilience import CheckpointCorruptError

    state = {"w": jnp.arange(16.0), "b": jnp.ones((4,))}
    torn = 20
    mgr = CheckpointManager(str(tmp), keep_last=5,
                            fault_plan=FaultPlan(truncate_ckpt_steps=(torn,)))
    mgr.save(10, state)
    mgr.save(torn, jax.tree.map(lambda x: x + 1, state))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    torn_rejected = False
    try:
        mgr.restore(torn, abstract)
    except CheckpointCorruptError:
        torn_rejected = True
    good = mgr.latest_good_step()
    restored = mgr.restore(good, abstract) if good is not None else None
    fallback_ok = (torn_rejected and good == 10 and restored is not None
                   and bool(np.array_equal(np.asarray(restored["w"]),
                                           np.arange(16.0))))
    w.emit("resilience.ckpt_fallback", None, torn_step=torn,
           torn_rejected=bool(torn_rejected),
           resumed_step=-1 if good is None else good,
           fallback_ok=bool(fallback_ok))


def onboard_quarantine(w: BenchWriter):
    from repro.data import ProfileClassification
    from repro.train import GraduationPolicy
    from repro.train.onboarding import build_onboarding_run

    cfg = bench_config()
    n_prof = 4
    data = ProfileClassification(cfg.vocab_size, cfg.num_labels,
                                 num_profiles=n_prof, seed=5)
    pol = GraduationPolicy(min_steps=3, max_steps=6, target_acc=2.0,
                           max_poison_strikes=2)
    trainer, _ = build_onboarding_run(
        cfg, data, range(n_prof), slots=2, per_slot=2, seq_len=12,
        policy=pol, lr=5e-2, log_every=3, rng=jax.random.key(1),
        fault_plan=FaultPlan(poison_slots=(0,)))
    trainer.run_until_drained(max_steps=400)
    st = trainer.scheduler.stats()
    qpids = {r["pid"] for r in trainer.scheduler.quarantined}
    w.emit("resilience.onboard_quarantine", None,
           profiles=n_prof, graduated=st["graduated"],
           evicted=st["evicted"], quarantined=st["quarantined"],
           accounting_ok=bool(st["graduated"] + st["evicted"] +
                              st["quarantined"] == n_prof),
           quarantined_served=len(
               qpids & set(trainer.scheduler.store.profile_ids())))


def elastic(w: BenchWriter):
    """Cheap reshard drill (only meaningful with >= 8 devices): values must
    survive a shrink to the surviving mesh bitwise. The full mid-onboarding
    resume drill lives in tests/test_fault.py (subprocess)."""
    if jax.device_count() < 8:
        return
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.fault import reshard_state, surviving_mesh
    from repro.launch.mesh import make_mesh

    mesh8 = make_mesh((4, 2), ("data", "model"))
    state = {"a": jnp.arange(64.0).reshape(8, 8),
             "b": jnp.arange(16, dtype=jnp.int32)}
    sh8 = jax.tree.map(
        lambda _: NamedSharding(mesh8, PartitionSpec("data")), state)
    on8 = reshard_state(state, sh8)
    mesh4 = surviving_mesh(("data", "model"), (4, 2), "data", 2)
    sh4 = jax.tree.map(
        lambda _: NamedSharding(mesh4, PartitionSpec("data")), state)
    on4 = reshard_state(on8, sh4)
    ok = all(np.array_equal(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(on4)))
    w.emit("resilience.elastic", None, devices=jax.device_count(),
           surviving_devices=len(mesh4.devices.flatten()),
           bitwise=bool(ok))


def main(smoke: bool = False):
    import tempfile

    w = BenchWriter("fault")
    serve_chaos(w, smoke)
    gang_guard(w)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt_fallback(w, tmp)
    onboard_quarantine(w)
    elastic(w)
    w.write()


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    main(**vars(p.parse_args()))
