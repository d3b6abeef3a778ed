"""Fault tolerance: watchdog, preemption, trainer integration, elastic
resize (8 -> 4 devices mid-onboarding, in a subprocess)."""
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.distributed.fault import (PreemptionHandler, StepWatchdog,
                                     rebalance_assignment)


def test_watchdog_flags_slow_steps():
    t = [0.0]

    def clock():
        return t[0]

    wd = StepWatchdog(deadline_factor=2.0, clock=clock)
    for dur in [1.0] * 8:
        wd.step_start()
        t[0] += dur
        assert not wd.step_end()
    wd.step_start()
    t[0] += 5.0  # straggler
    assert wd.step_end()
    assert wd.slow_steps == 1
    assert abs(wd.median - 1.0) < 1e-6


def test_watchdog_window_attribution():
    """Buffered-metrics trainers observe device time only at sync
    boundaries: window_end spreads a window's wall time over its steps and
    flags the whole window against the trailing median."""
    wd = StepWatchdog(deadline_factor=2.0)
    assert not wd.window_end(4, 4.0)   # no history yet -> baseline 1.0/step
    assert wd.slow_steps == 0 and abs(wd.median - 1.0) < 1e-9
    assert wd.window_end(2, 10.0)      # 5.0/step > 2x median 1.0
    assert wd.slow_steps == 2
    assert not wd.window_end(0, 1.0)   # empty window is a no-op
    assert wd.slow_steps == 2


def test_preemption_checkpoints_and_stops(tmp_path):
    from repro.configs import get_config, reduce_for_smoke
    from repro.data import MarkovLM
    from repro.data.loader import ShardedLoader
    from repro.train.steps import init_train_state, make_train_step
    from repro.train.trainer import Trainer

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    step = jax.jit(make_train_step(cfg, "xpeft", lr=1e-3))
    loader = ShardedLoader(MarkovLM(cfg.vocab_size, 4, seed=0), 2, 8)
    pre = PreemptionHandler.__new__(PreemptionHandler)  # no signal handler
    import threading
    pre._flag = threading.Event()
    tr = Trainer(step, init_train_state(jax.random.key(0), cfg, "xpeft"),
                 loader, ckpt_dir=str(tmp_path), preemption=pre,
                 log_every=1000)
    tr.run(2)
    pre.trigger()
    tr.run(10)  # should stop immediately and checkpoint
    assert tr.step == 2
    assert tr.mgr.latest_step() == 2


def test_watchdog_step_end_without_start_is_noop():
    """step_end before any step_start must not crash (the trainer can hit
    this on a resume path) — it returns False and records nothing."""
    wd = StepWatchdog()
    assert wd.step_end() is False
    assert wd.slow_steps == 0 and wd.median == 0.0
    # a consumed step_start does not leak into a second step_end
    t = [0.0]
    wd = StepWatchdog(clock=lambda: t[0])
    wd.step_start()
    t[0] += 1.0
    assert wd.step_end() is False
    assert wd.step_end() is False      # no start since -> no-op
    assert len(wd._durations) == 1


def test_rebalance_zero_speeds_and_empty_hosts():
    # every host at speed 0: clamped to a positive floor -> even split,
    # full coverage, no NaN ranges
    asg = rebalance_assignment(90, [0, 1, 2], {0: 0.0, 1: 0.0, 2: 0.0})
    assert sum(len(r) for r in asg.values()) == 90
    assert all(len(r) == 30 for r in asg.values())
    # one dead host among live ones: gets (almost) nothing, total preserved
    asg = rebalance_assignment(100, [0, 1], {0: 0.0})
    assert sum(len(r) for r in asg.values()) == 100
    assert len(asg[0]) < len(asg[1])
    with pytest.raises(ValueError):
        rebalance_assignment(10, [], {})


def test_preemption_chains_previous_handler():
    """Installing a PreemptionHandler must not silently replace a
    previously-installed handler — both fire on the signal."""
    sig = signal.SIGUSR1
    calls = []
    original = signal.getsignal(sig)
    try:
        signal.signal(sig, lambda s, f: calls.append(s))
        pre = PreemptionHandler(sigs=(sig,))
        os.kill(os.getpid(), sig)
        assert pre.preempted()
        assert calls == [sig]
    finally:
        signal.signal(sig, original)


def test_preemption_does_not_chain_default_sigint():
    """SIGINT's default KeyboardInterrupt handler is NOT chained: raising
    it would defeat the graceful checkpoint the handler exists for."""
    sig = signal.SIGINT
    original = signal.getsignal(sig)
    try:
        signal.signal(sig, signal.default_int_handler)
        pre = PreemptionHandler(sigs=(sig,))
        os.kill(os.getpid(), sig)   # must NOT raise KeyboardInterrupt
        assert pre.preempted()
    finally:
        signal.signal(sig, original)


def test_preemption_accepts_multiple_signals():
    sig1, sig2 = signal.SIGUSR1, signal.SIGUSR2
    orig = {s: signal.getsignal(s) for s in (sig1, sig2)}
    try:
        pre = PreemptionHandler(sigs=(sig1, sig2))
        assert not pre.preempted()
        os.kill(os.getpid(), sig2)
        assert pre.preempted()
    finally:
        for s, h in orig.items():
            signal.signal(s, h)


def test_rebalance_total_preserved_and_monotone():
    for n in (7, 64, 100):
        asg = rebalance_assignment(n, [0, 1, 2], {1: 0.25})
        assert sum(len(r) for r in asg.values()) == n
        ranges = [asg[h] for h in (0, 1, 2)]
        # contiguous, ordered partition
        assert ranges[0].start == 0
        assert ranges[0].stop == ranges[1].start
        assert ranges[1].stop == ranges[2].start
        assert ranges[2].stop == n


# --------------------------------------------------------------- elastic

def test_elastic_shrink_resumes_onboarding(tmp_path):
    """Node-failure drill on 8 fake devices: onboard on a (4,2) mesh,
    checkpoint mid-run, 'lose' half the data axis, resume on the surviving
    (2,2) mesh with an explicit reshard — the final graduated store must be
    byte-identical to an unfailed straight-through run."""
    body = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config, reduce_for_smoke
    from repro.data import MarkovLM
    from repro.distributed import sharding as SH
    from repro.distributed.fault import reshard_state, surviving_mesh
    from repro.launch.mesh import make_mesh
    from repro.train import GraduationPolicy
    from repro.train.onboarding import build_onboarding_run

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    n_prof, slots = 4, 4
    ckpt = {str(tmp_path)!r}

    def build(mesh, ckpt_dir=None):
        data = MarkovLM(cfg.vocab_size, n_prof, seed=1)
        policy = GraduationPolicy(min_steps=3, max_steps=5, target_acc=2.0)
        trainer, _ = build_onboarding_run(
            cfg, data, range(n_prof), slots=slots, per_slot=2, seq_len=8,
            policy=policy, lr=5e-2, seed=0, rng=jax.random.key(1),
            log_every=2, mesh=mesh, ckpt_dir=ckpt_dir, ckpt_every=4,
            store_path=(os.path.join(ckpt_dir, "store.npz")
                        if ckpt_dir else None))
        return trainer

    # reference: unfailed straight-through run on the full mesh
    mesh8 = make_mesh((4, 2), ("data", "model"))
    ref = build(mesh8)
    ref.run_until_drained(max_steps=200)
    assert len(ref.scheduler.graduated) == n_prof

    # failed run: same mesh, checkpoint at step 4, die at step 6
    t1 = build(mesh8, ckpt_dir=os.path.join(ckpt, "ckpt"))
    t1.run(6)
    assert t1.mgr.latest_step() is not None

    # half the data axis is gone: resume on the surviving (2,2) mesh
    mesh4 = surviving_mesh(("data", "model"), (4, 2), "data", 2)
    t2 = build(mesh4, ckpt_dir=os.path.join(ckpt, "ckpt"))
    assert t2.try_resume()
    rsh = SH.to_shardings(
        SH.leading_axis_specs(t2.state["roster"], mesh4), mesh4)
    fsh = jax.tree.map(
        lambda _: NamedSharding(mesh4, PartitionSpec()),
        t2.state["frozen"])
    t2.state = {{
        "frozen": reshard_state(t2.state["frozen"], fsh),
        "roster": reshard_state(t2.state["roster"], rsh),
    }}
    t2.run_until_drained(max_steps=200)

    ref_store, new_store = ref.scheduler.store, t2.scheduler.store
    assert ref_store.profile_ids() == new_store.profile_ids() == \\
        list(range(n_prof))
    for pid in ref_store.profile_ids():
        ra, rb = ref_store._rec[pid], new_store._rec[pid]
        assert sorted(ra) == sorted(rb), pid
        for key in ra:
            assert ra[key].dtype == rb[key].dtype
            assert np.array_equal(ra[key], rb[key]), (pid, key)
    print("elastic resume ok")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, cwd=root, timeout=900)
    assert r.returncode == 0, \
        f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "elastic resume ok" in r.stdout
