"""The program's spans on the profiler's clock, the named scopes of the
paged decode step, the jitted program and kernel names the benchmark's
readers match, and the scheduler's queue-wait counters."""
import ast
import glob
import os
import time

import numpy as np
import jax
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import xpeft as XP
from repro.core.profiles import ProfileStore
from repro.models import init_lm
from repro.obs import trace as TR
from repro.serve.engine import Request, ServeEngine

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                         cfg.xpeft.bottleneck, "hard", cfg.xpeft.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(3):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    return cfg, params, store


def _requests(cfg, n, start=0, max_new=4):
    rng = np.random.default_rng(start)
    return [Request(uid=start + i,
                    prompt=rng.integers(0, cfg.vocab_size, 5 + i),
                    profile_id=i % 3, max_new_tokens=max_new)
            for i in range(n)]


def _start_trace(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _xspace(log_dir):
    return sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    path = _xspace(log_dir)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((ev.name, dict(ev.stats)) for ev in line.events
                           if ev.name.startswith(TR.PREFIXES))
    return out


def test_engine_spans_reach_the_profiler_without_a_bundle(setup, tmp_path):
    """An engine on NULL_OBS (``obs=None``) writes its spans into any
    profiler capture, with the admission's facts as args."""
    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      sync_every=4, continuous=True)
    eng.run_until_drained(_requests(cfg, 2, start=100))   # compile
    _start_trace(str(tmp_path))
    try:
        eng.run_until_drained(_requests(cfg, 3))
    finally:
        jax.profiler.stop_trace()
    assert eng.obs.tracer.events() == []         # no ring buffer on NULL_OBS
    spans = _host_spans(str(tmp_path))
    names = {n for n, _ in spans}
    assert {"serve.admit_wave", "serve.hydrate", "serve.prefill",
            "serve.sync", "serve.fetch", "serve.distribute",
            "serve.refresh_window", "serve.slot_admit"} <= names
    waves = [a for n, a in spans if n == "serve.admit_wave"]
    assert sum(a["admitted"] for a in waves) == 3
    first = waves[0]
    assert first["offered"] == 2 and first["admitted"] == 2
    # the compile pass cached pids 0 and 1: the first wave hit both
    assert {"missed", "hits", "path", "uids", "aggregated"} <= set(first)
    assert first["hits"] == 2 and first["missed"] == 0
    assert str(first["uids"]) == "0 1"
    prefill = [a for n, a in spans if n == "serve.prefill"]
    assert all({"bucket", "rows", "real"} <= set(a) for a in prefill)
    # the benchmark's reduction keeps its own spans only
    from bench import tracing
    assert tracing.extract(_xspace(str(tmp_path)))["host"] == []


def test_missed_profiles_are_distinct_and_unpadded(setup):
    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=4, max_seq=64,
                      continuous=True)
    reqs = _requests(cfg, 3)
    reqs[2].profile_id = 0                        # pids 0, 1, 0
    assert eng.admit_many(reqs) == 3
    adm = eng.last_admission
    assert adm["missed_profiles"] == 2 and adm["aggregated_profiles"] == 2
    assert adm["cache_misses"] == 3 and adm["cache_hits"] == 0


def _span_names():
    """Every span, instant and complete name the program's sources pass
    as a literal, with the file it is in."""
    out = []
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("span", "instant", "complete")
                    and len(node.args) >= 2):
                arg = node.args[1]
                assert isinstance(arg, ast.Constant), \
                    f"{path}:{node.lineno}: span name is not a literal"
                out.append((arg.value, os.path.relpath(path, SRC)))
    return out


def test_no_program_span_collides_with_the_harness():
    from bench import tracing

    names = _span_names()
    assert len({n for n, _ in names}) >= 20
    for name, where in names:
        assert name.startswith(TR.PREFIXES), (name, where)
        assert name not in tracing.SPANS, (name, where)


def test_decode_step_carries_the_paging_scopes(setup):
    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      continuous=True)
    eng.run_until_drained(_requests(cfg, 2))
    low = eng.slots._step.lower(eng.params, eng.cache, eng._masks_view,
                                eng.slots._arrays(), 0)
    assert low.as_text().startswith("module @jit_step_impl")
    hlo = low.compile().as_text()
    for scope in ("kv_dense_view", "kv_writeback", "kv_cache_update",
                  "adapter"):
        assert f"/{scope}/" in hlo, scope


def test_reader_module_and_kernel_names_are_pinned(setup):
    """The benchmark's readers find the decode step, the prefill and the
    gang step by their jitted module names, and the Pallas kernels by the
    jitted wrapper's name (``bench/readers.py``)."""
    from bench import readers
    from repro.kernels.fused_adapter_batched import fused_adapter_batched
    from repro.kernels.mask_aggregate import mask_aggregate_batched
    from repro.train.onboarding import build_onboarding_run

    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      continuous=True)
    low = eng.slots._step.lower(eng.params, eng.cache, eng._masks_view,
                                eng.slots._arrays(), 0)
    assert low.as_text().startswith(f"module @{readers.DECODE_MODULE} ")
    toks = np.zeros((2, 8), np.int32)
    low = eng._prefill.lower(eng.params, toks, None, np.ones(2, np.int32))
    assert low.as_text().startswith(f"module @{readers.PREFILL_MODULE} ")

    class Rows:
        def sample(self, step, n, seq_len, profile_ids=None):
            x = np.zeros((n, seq_len + 1), np.int32)
            return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    trainer, _ = build_onboarding_run(cfg, Rows(), range(4), slots=2,
                                      per_slot=1, seq_len=8, frozen=params)
    low = trainer.step_fn.lower(trainer.state, trainer.next_batch(),
                                jax.random.key(0))
    assert low.as_text().startswith(
        f"module @{readers.GANG_MODULE.rstrip('(')} ")
    assert (fused_adapter_batched.__name__,) == readers.FUSED_ADAPTER
    assert (mask_aggregate_batched.__name__,) == readers.MASK_AGGREGATE


def test_queue_wait_counts_and_resets(setup):
    cfg, params, store = setup
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      continuous=True)
    reqs = _requests(cfg, 4)
    eng.submit(reqs)
    time.sleep(0.05)
    eng.run_until_drained()
    sch = eng.serve_stats()["scheduler"]
    assert sch["waited"] == 4
    # every request waited out the sleep; the last two a wave besides
    assert sch["queue_wait_s"] >= 4 * 0.05
    unsubmitted = _requests(cfg, 1, start=50)
    eng.admit_many(unsubmitted)                   # never queued: not timed
    assert eng.serve_stats()["scheduler"]["waited"] == 4
    eng.run_until_drained()
    eng.reset_stats()
    sch = eng.serve_stats()["scheduler"]
    assert sch["waited"] == 0 and sch["queue_wait_s"] == 0.0


def test_onboarding_spans_reach_the_profiler(setup, tmp_path):
    from repro.train.onboarding import GraduationPolicy, build_onboarding_run

    cfg, params, _ = setup

    class Rows:
        def sample(self, step, n, seq_len, profile_ids=None):
            x = np.random.default_rng(step).integers(
                0, cfg.vocab_size, (n, seq_len + 1)).astype(np.int32)
            return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    trainer, _ = build_onboarding_run(
        cfg, Rows(), range(6), slots=2, per_slot=1, seq_len=8,
        frozen=params, policy=GraduationPolicy(min_steps=2, max_steps=2),
        log_every=2)
    trainer.run(2)                                # compile
    _start_trace(str(tmp_path))
    try:
        trainer.run(4)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [n for n, _ in spans]
    for name in ("train.flush", "train.poll", "train.metrics_fetch",
                 "train.graduate", "train.fill"):
        assert name in names, name
    grads = [a for n, a in spans if n == "train.graduate"]
    assert all({"profile", "slot", "steps"} <= set(a) for a in grads)
    assert len(grads) == len(trainer.scheduler.graduated) - 2
