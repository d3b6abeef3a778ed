"""Self-speculative decoding (ISSUE 8): the bare PLM (zero-adapter view)
drafts, the adapted model verifies in one batched step, the accepted
prefix commits. The contract under test is BITWISE: greedy speculative
output equals non-speculative greedy per request — through admission
churn, forced preemption/resume, and an 8-fake-device mesh — while the
decode step still traces exactly once and commits > 1 token per device
step."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import xpeft as XP
from repro.core.profiles import ProfileStore
from repro.models import init_lm
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request


def skewed(cfg, n, *, long_new=20, seed=0):
    from benchmarks.cb_smoke import skewed_requests
    return skewed_requests(cfg, n, seed=seed, long_new=long_new)


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


def drain(setup, *, gamma=0, n=6, long_new=20, quant="none", **kw):
    cfg, params, store = setup
    cfg = cfg.with_(spec_enable=gamma > 0, spec_gamma=max(gamma, 1))
    if quant != "none":
        cfg = cfg.with_xpeft(bank_quant=quant)
        store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                             cfg.xpeft.bottleneck, "hard", cfg.xpeft.k,
                             quant=quant)
        key = jax.random.key(0)
        table = XP.init_profile_table(key, cfg)
        for pid in range(3):
            store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      sync_every=4, continuous=True, page_size=16, **kw)
    reqs = skewed(cfg, n, long_new=long_new)
    eng.run_until_drained(reqs)
    assert all(r.done for r in reqs)
    return eng, {r.uid: list(map(int, r.generated)) for r in reqs}


@pytest.fixture(scope="module")
def plain_ref(setup):
    eng, toks = drain(setup, gamma=0)
    return {"tokens": toks, "device_steps": eng.slots.device_steps}


@pytest.mark.parametrize("gamma", [1, 3])
def test_spec_greedy_bitwise_parity(setup, plain_ref, gamma):
    eng, toks = drain(setup, gamma=gamma)
    assert toks == plain_ref["tokens"]            # bitwise per request
    st = eng.serve_stats()
    assert st["step_traces"] == 1                 # one compiled program
    # the perf claim: the same tokens in fewer device steps
    assert eng.slots.device_steps < plain_ref["device_steps"]
    assert st["committed_per_device_step"] > 1.0
    assert st["committed_tokens"] == st["decode_tokens"]
    spec = st["spec"]
    assert spec["gamma"] == gamma
    assert spec["drafted"] > 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    assert spec["per_request_acceptance"]          # per-slot/uid stats
    eng.page_alloc.check()


def test_spec_bitwise_through_preempt_resume(setup):
    """5-page pool + long budgets force preempt-to-pending swaps mid-
    generation; resumed requests must still commit bitwise the plain
    tokens (stale speculative KV beyond the commit point must never
    survive a swap), all through the one compiled step."""
    _, ref = drain(setup, gamma=0, n=6, long_new=50)
    eng, toks = drain(setup, gamma=3, n=6, long_new=50, max_pages=5)
    st = eng.serve_stats()
    assert st["preemptions"] > 0 and st["resumes"] > 0
    assert toks == ref
    assert st["step_traces"] == 1
    eng.page_alloc.check()


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_spec_parity_quantized_bank(setup, quant):
    """Speculation over the quantized adapter bank: drafts use the zero
    quantized record (dequantizes to the exact bare PLM), verify uses the
    slot's int8/int4 record — tokens still match that engine's own
    non-speculative greedy bitwise."""
    _, ref = drain(setup, gamma=0, quant=quant)
    eng, toks = drain(setup, gamma=2, quant=quant)
    assert toks == ref
    assert eng.serve_stats()["step_traces"] == 1


def test_spec_config_gates(setup):
    cfg, params, store = setup
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(cfg.with_(spec_enable=True, spec_gamma=2), params,
                    store, continuous=False)
    with pytest.raises(ValueError, match="exclusive"):
        ServeEngine(cfg.with_(spec_enable=True, spec_gamma=2,
                              decode_fused=True), params, store,
                    continuous=True)
    with pytest.raises(ValueError, match="spec_gamma"):
        ServeEngine(cfg.with_(spec_enable=True, spec_gamma=0), params,
                    store, continuous=True)


def test_spec_recurrent_arch_rejected():
    cfg = reduce_for_smoke(get_config("rwkv6-7b")).with_(
        spec_enable=True, spec_gamma=2)
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                         cfg.xpeft.bottleneck, "hard", cfg.xpeft.k)
    with pytest.raises(ValueError, match="attention"):
        ServeEngine(cfg, params, store, continuous=True)


def test_spec_mesh_bitwise_parity():
    """Speculative vs plain greedy on an 8-fake-device (4 data x 2 model)
    mesh: token ids bitwise equal, one trace each, tokens-per-step > 1.
    Subprocess: never set device-count flags in this process."""
    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax
    from repro.configs import get_config, reduce_for_smoke
    from repro.core import xpeft as XP
    from repro.core.profiles import ProfileStore
    from repro.launch.mesh import make_mesh
    from repro.models import init_lm
    from repro.serve.engine import ServeEngine
    from benchmarks.cb_smoke import skewed_requests

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                         cfg.xpeft.bottleneck, "hard", cfg.xpeft.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(3):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    mesh = make_mesh((4, 2), ("data", "model"))
    out = {}
    for gamma in (0, 3):
        c = cfg.with_(spec_enable=gamma > 0, spec_gamma=max(gamma, 1))
        eng = ServeEngine(c, params, store, max_slots=4, max_seq=64,
                          sync_every=4, continuous=True, page_size=16,
                          mesh=mesh)
        reqs = skewed_requests(c, 6, seed=0, long_new=20)
        eng.run_until_drained(reqs)
        out[gamma] = {r.uid: list(map(int, r.generated)) for r in reqs}
        st = eng.serve_stats()
        assert st["step_traces"] == 1, st["step_traces"]
        if gamma:
            assert st["committed_per_device_step"] > 1.0
    assert out[3] == out[0], "mesh spec tokens diverge"
    print("mesh spec parity ok")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=600)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "mesh spec parity ok" in r.stdout
