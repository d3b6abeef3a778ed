"""Continuous-batching serving engine (paged KV + adapter-slot memory).

Contracts: per-request token ids BITWISE equal to the windowed engine on a
skewed-length workload (including under forced preempt/resume), strictly
less slot stranding, ONE decode trace across admissions/preemptions/
resumes, and the scheduler's age-promotion valve for exact-length buckets.
"""
import numpy as np
import jax
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import xpeft as XP
from repro.core.profiles import ProfileStore
from repro.models import init_lm
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request, Scheduler


def skewed(cfg, n, *, long_new=20, seed=0):
    from benchmarks.cb_smoke import skewed_requests
    return skewed_requests(cfg, n, seed=seed, long_new=long_new)


def _build(arch):
    cfg = reduce_for_smoke(get_config(arch))
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                         cfg.xpeft.bottleneck, "hard", cfg.xpeft.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(3):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    return cfg, params, store


@pytest.fixture(scope="module")
def setup():
    return _build("qwen1.5-0.5b")


def drain(setup, *, continuous, n=6, long_new=20, **kw):
    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      sync_every=4, continuous=continuous, page_size=16,
                      **kw)
    reqs = skewed(cfg, n, long_new=long_new)
    eng.run_until_drained(reqs)
    return eng, {r.uid: list(map(int, r.generated)) for r in reqs}


@pytest.fixture(scope="module")
def windowed_ref(setup):
    eng, toks = drain(setup, continuous=False)
    return {"tokens": toks, "stranded": eng.stranded_slot_steps,
            "device_steps": eng.slots.device_steps}


def test_cb_bitwise_parity_and_less_stranding(setup, windowed_ref):
    eng, toks = drain(setup, continuous=True)
    assert toks == windowed_ref["tokens"]          # bitwise token parity
    st = eng.serve_stats()
    assert st["step_traces"] == 1
    # the whole point: short requests stop waiting out the wave straggler
    assert eng.stranded_slot_steps < windowed_ref["stranded"]
    assert eng.slots.device_steps < windowed_ref["device_steps"]
    assert "stranded_slot_steps" in st
    eng.page_alloc.check()
    eng.mask_alloc.check()


def test_preempt_resume_bitwise(setup):
    """A starved page pool (5 pages < 4 + 2 a long plus a short request
    want) forces preempt-to-pending swaps; resumed requests must still
    produce bitwise the windowed tokens, through the SAME compiled step.
    long_new=50 pushes the long requests to ~4 pages of the 64-seq cache."""
    _, ref = drain(setup, continuous=False, n=6, long_new=50)
    eng, toks = drain(setup, continuous=True, n=6, long_new=50, max_pages=5)
    st = eng.serve_stats()
    assert st["preemptions"] > 0 and st["resumes"] > 0
    assert toks == ref
    assert st["step_traces"] == 1
    eng.page_alloc.check()


def test_recurrent_arch_continuous_parity():
    """Pure-recurrent archs have no paged leaves (O(1) state per slot):
    the continuous engine must still run — mid-stream admission + pooled
    mask entries — and match the windowed tokens bitwise."""
    cfg = reduce_for_smoke(get_config("rwkv6-7b"))
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                         cfg.xpeft.bottleneck, "hard", cfg.xpeft.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(3):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    out = {}
    for cont in (False, True):
        eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                          sync_every=4, continuous=cont, page_size=16)
        reqs = skewed(cfg, 4, long_new=10)
        eng.run_until_drained(reqs)
        out[cont] = {r.uid: list(map(int, r.generated)) for r in reqs}
        if cont:
            assert eng.serve_stats()["step_traces"] == 1
    assert out[True] == out[False]


# ------------------------------------------------------------------ scheduler
def _flood(n, length=5, base=100, max_new=2):
    rng = np.random.default_rng(0)
    return [Request(uid=base + i,
                    prompt=rng.integers(0, 50, size=length),
                    profile_id=0, max_new_tokens=max_new)
            for i in range(n)]


def test_exact_length_starvation_without_promotion():
    """Under efficiency policy + exact-length buckets (recurrent archs), a
    one-off prompt length is a bucket of size 1 that largest-first never
    picks while the common length keeps flowing."""
    sched = Scheduler("mamba", policy="efficiency", max_wait_waves=None)
    rare = Request(uid=0, prompt=np.arange(9), profile_id=0)
    sched.submit(_flood(2))
    sched.submit(rare)
    for wave in range(10):
        sched.submit(_flood(2, base=200 + 10 * wave))   # steady flood
        picked = sched.next_batch(2)
        assert rare not in picked
    assert rare.waits >= 10


def test_max_wait_waves_promotes_starved_bucket():
    """The same flood with max_wait_waves=2: the rare length leads a wave
    as soon as its age hits the valve — the anti-starvation guarantee the
    exact-length archs (rwkv/mamba/zamba) rely on."""
    sched = Scheduler("mamba", policy="efficiency", max_wait_waves=2)
    rare = Request(uid=0, prompt=np.arange(9), profile_id=0)
    sched.submit(_flood(2))
    sched.submit(rare)
    admitted_at = None
    for wave in range(10):
        sched.submit(_flood(2, base=200 + 10 * wave))
        if rare in sched.next_batch(2):
            admitted_at = wave
            break
    assert admitted_at is not None and admitted_at <= 3
    assert sched.stats()["promoted"] >= 1


def test_requeue_front_preserves_order():
    """Requests the page pool declined go back to the HEAD of the queue in
    their original order — a declined admission never loses its place."""
    sched = Scheduler("attn")
    reqs = _flood(6, length=5)
    sched.submit(reqs)
    first = sched.next_batch(2)
    assert [r.uid for r in first] == [reqs[0].uid, reqs[1].uid]
    sched.requeue_front(first)
    assert sched.stats()["requeued"] == 2
    again = sched.next_batch(2)
    assert [r.uid for r in again] == [r.uid for r in first]


@pytest.mark.parametrize("case,route", [("full", "paged"),
                                        ("spec", "dense_view"),
                                        ("sliding", "dense_view"),
                                        ("zamba", "dense_view"),
                                        ("windowed", "dense")])
def test_decode_route_follows_what_the_step_can_read(setup, case, route):
    """Full causal attention reads KV pages in place; speculation, a
    sliding-window mix and zamba's shared attention decode through the
    dense view of the pages; the windowed engine has a dense cache.
    serve_stats names the route the compiled step took and counts the
    steps it ran."""
    cfg, params, store = _build("zamba2-1.2b") if case == "zamba" else setup
    if case == "spec":
        cfg = cfg.with_(spec_enable=True, spec_gamma=2)
    if case == "sliding":
        cfg = cfg.with_(attn_type="sliding_mix", sliding_window=8,
                        global_every=2)
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      sync_every=4, continuous=case != "windowed",
                      page_size=16)
    eng.run_until_drained(skewed(cfg, 3, long_new=6))
    st = eng.serve_stats()
    assert st["decode_route"] == route
    assert st["steps_by_route"] == {route: st["device_steps"]}
    assert st["device_steps"] > 0
    eng.reset_stats()
    assert eng.serve_stats()["steps_by_route"] == {route: 0}
