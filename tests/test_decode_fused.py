"""Decode megakernel (ISSUE 8): one Pallas program per layer applying
norm/attention/MLP AND the X-PEFT adapter at decode shapes (T=1). The
kernel body and the jnp oracle share `decode_block_row` verbatim, so
interpret-vs-ref parity is BITWISE on every adapter route; the engine
gate is exact token equality against the composed path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import xpeft as XP
from repro.core.profiles import ProfileStore
from repro.kernels import ops
from repro.models import init_lm
from repro.serve.engine import ServeEngine


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


def _kernel_inputs(setup, adapter):
    """Random decode-shaped inputs + layer-0 weights/adapter leaves."""
    cfg, params, _ = setup
    B, S = 4, 32
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    block = jax.tree.map(lambda t: t[0], params["blocks"])
    ks = jax.random.split(jax.random.key(7), 4)
    dt = jnp.dtype(cfg.dtype)
    x = jax.random.normal(ks[0], (B, 1, cfg.d_model), dt)
    kc = jax.random.normal(ks[1], (B, S, KV, hd), dt)
    vc = jax.random.normal(ks[2], (B, S, KV, hd), dt)
    pos = jnp.asarray([3, 0, 17, 9], jnp.int32)
    masks_l = {}
    if adapter != "none":
        table = XP.init_profile_table(ks[3], cfg)
        prof = XP.gather_profiles(table, jnp.arange(B))
        agg = jax.vmap(lambda p: XP.precompute_effective_adapters(
            params["xpeft_bank"], p, cfg.xpeft))(prof)
        lay = {k: v[:, 0] for k, v in agg.items()}     # layer-0 leaves
        if adapter == "bf16":
            masks_l = lay
        else:
            from repro.quant import schemes as QS
            qa = QS.quantize(lay["a_hat"], adapter,
                             group=cfg.xpeft.quant_group)
            qb = QS.quantize(lay["b_hat"], adapter,
                             group=cfg.xpeft.quant_group)
            masks_l = {"a_q": qa["q"], "a_scale": qa["scale"],
                       "b_q": qb["q"], "b_scale": qb["scale"],
                       "ln_scale": lay["ln_scale"],
                       "ln_bias": lay["ln_bias"]}
    kw = dict(norm=cfg.norm, qkv_bias=cfg.qkv_bias,
              use_rope=cfg.pos == "rope", theta=cfg.rope_theta,
              cap=cfg.logit_softcap, mlp_type=cfg.mlp_type,
              act_name=cfg.act, adapter=adapter,
              adapter_act=cfg.xpeft.adapter_activation)
    return (x, pos, block, kc, vc, masks_l), kw


@pytest.mark.parametrize("adapter", ["none", "bf16", "int8", "int4"])
def test_megakernel_interpret_ref_bitwise(setup, adapter):
    """The exact Pallas kernel body (interpret mode) vs the jnp oracle at
    decode shapes: y and the written K/V rows bitwise equal on every
    precision route."""
    args, kw = _kernel_inputs(setup, adapter)
    # jit both routes: the engine only ever runs them inside the jitted
    # decode step, and eager op-by-op dispatch fuses (FMA) differently
    ref = jax.jit(lambda *a: ops.decode_block_fused(
        *a, impl="ref", **kw))(*args)
    itp = jax.jit(lambda *a: ops.decode_block_fused(
        *a, impl="interpret", **kw))(*args)
    for r, i, name in zip(ref, itp, ("y", "k_rows", "v_rows")):
        assert r.dtype == i.dtype and r.shape == i.shape
        assert np.array_equal(np.asarray(r), np.asarray(i)), \
            f"{adapter}/{name} interpret != ref"


def _drain(setup, *, fused, quant="none", continuous=True, impl="auto"):
    from benchmarks.cb_smoke import skewed_requests
    cfg, params, store = setup
    cfg = cfg.with_(decode_fused=fused).with_xpeft(
        bank_quant=quant, kernel_impl=impl)
    if quant != "none":
        store = ProfileStore(cfg.num_layers, cfg.xpeft.num_adapters,
                             cfg.xpeft.bottleneck, "hard", cfg.xpeft.k,
                             quant=quant)
        table = XP.init_profile_table(jax.random.key(0), cfg)
        for pid in range(3):
            store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    eng = ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                      sync_every=4, continuous=continuous, page_size=16)
    reqs = skewed_requests(cfg, 6, seed=0, long_new=20)
    eng.run_until_drained(reqs)
    return eng, {r.uid: list(map(int, r.generated)) for r in reqs}


@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
def test_megakernel_engine_token_parity(setup, quant):
    """decode_fused=True serves bitwise the composed engine's tokens on
    the paged continuous path — bf16 and both quantized record routes —
    and the decode step still compiles exactly once."""
    _, ref = _drain(setup, fused=False, quant=quant)
    eng, toks = _drain(setup, fused=True, quant=quant)
    assert toks == ref
    assert eng.serve_stats()["step_traces"] == 1


def test_megakernel_engine_interpret_impl(setup):
    """kernel_impl only picks the backend inside the megakernel path —
    interpret mode (the exact kernel body) serves the same tokens."""
    _, ref = _drain(setup, fused=False)
    _, toks = _drain(setup, fused=True, impl="interpret")
    assert toks == ref


def test_megakernel_windowed_engine(setup):
    _, ref = _drain(setup, fused=False, continuous=False)
    _, toks = _drain(setup, fused=True, continuous=False)
    assert toks == ref


def test_megakernel_ineligible_shapes_compose(setup):
    """T>1 (prefill) and cacheless forwards must keep the composed path:
    the route resolver returns None for them."""
    from repro.models.model import _decode_fused_route
    cfg, _, _ = setup
    cfg = cfg.with_(decode_fused=True)
    masks = {"a_hat": None}
    assert _decode_fused_route(cfg, masks, True, 1) == "bf16"
    assert _decode_fused_route(cfg, masks, True, 4) is None   # prefill
    assert _decode_fused_route(cfg, masks, False, 1) is None  # no cache
    assert _decode_fused_route(cfg, None, True, 1) == "none"  # bare PLM
    off = cfg.with_(decode_fused=False)
    assert _decode_fused_route(off, masks, True, 1) is None


def test_megakernel_refused_with_compiled_pallas(setup):
    """The megakernel does not compile for the TPU at real widths, so an
    engine asked to serve it through compiled Pallas refuses, saying why,
    instead of falling back to the composed path in silence."""
    cfg, params, store = setup
    cfg = cfg.with_(decode_fused=True).with_xpeft(kernel_impl="pallas")
    with pytest.raises(ValueError, match="decode_fused .*Mosaic"):
        ServeEngine(cfg, params, store, max_slots=2, max_seq=64,
                    continuous=True, page_size=16)
