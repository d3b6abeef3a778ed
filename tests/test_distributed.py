"""Distribution layer: sharding rules, compressed collectives, pipeline,
small-mesh pjit — multi-device pieces run in a subprocess with 8 host
devices (never set device-count flags in this process)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (leading_axis_specs,
                                        sharded_bytes_per_device, spec_for)

MESH_AXES = {"data": 4, "model": 2}


def test_rules_attention_heads_tp():
    s = spec_for("blocks/attn/wq", (12, 64, 8, 16), MESH_AXES, fsdp=True)
    assert s == P(None, "data", "model", None)


def test_rules_mqa_kv_replicated():
    # kv_heads=1 not divisible by model=2 -> replicated, FSDP elsewhere
    s = spec_for("blocks/attn/wk", (12, 512, 1, 64), MESH_AXES, fsdp=True)
    assert s == P(None, "data", None, None)


def test_rules_small_tensors_skip_fsdp():
    s = spec_for("blocks/attn/wk", (12, 64, 1, 16), MESH_AXES, fsdp=True)
    assert s == P(None, None, None, None)  # < FSDP_MIN_SIZE


def test_rules_divisibility_guard():
    s = spec_for("blocks/mlp/wg", (10, 64, 31), MESH_AXES, fsdp=True)
    assert s[2] is None  # 31 % 2 != 0


def test_rules_bank_tp_and_fsdp():
    s = spec_for("xpeft_bank/bank_a", (12, 256, 64, 8), MESH_AXES, fsdp=True)
    assert s == P(None, "data", "model", None)


def test_rules_small_params_not_fsdp():
    s = spec_for("final_norm/scale", (64,), MESH_AXES, fsdp=True)
    assert s == P(None)


def test_rules_expert_pinned_fsdp():
    s = spec_for("blocks/moe/ew_g", (4, 8, 64, 32), MESH_AXES, fsdp=True)
    assert s == P(None, "model", None, "data")


def test_rules_mqa_kv_replicated_wide_model_axis():
    """kv_heads=1 stays replicated however wide the model axis gets (no
    FSDP axis in a TP-only mesh -> fully replicated)."""
    s = spec_for("blocks/attn/wk", (12, 512, 1, 64), {"model": 16},
                 fsdp=True)
    assert s == P(None, None, None, None)


def test_rules_bank_a_and_bank_b_tp_assignment():
    """Both banks TP-shard their d_model dim over "model": bank_a [L,N,d,b]
    on dim 2, bank_b [L,N,b,d] on dim 3; the N dim is never sharded (the
    k-sparse gather indexes it) and FSDP claims the largest leftover."""
    sa = spec_for("xpeft_bank/bank_a", (12, 256, 64, 8), MESH_AXES,
                  fsdp=False)
    assert sa == P(None, None, "model", None)
    sb = spec_for("xpeft_bank/bank_b", (12, 256, 8, 64), MESH_AXES,
                  fsdp=True)
    assert sb == P(None, "data", None, "model")


def test_rules_quantized_bank_leaves_follow_tp():
    """Quantized bank payloads keep the bf16 bank's d_model TP layout and
    the fp16 scale arrays ride along: int8 scales (ndim 3) drop the
    quantized axis, int4 group scales (ndim 4) keep a trailing group
    axis. Dims here are the full config's (L=24, N=256, d=1024, b=64)."""
    sa = spec_for("qbank/bank_a_q", (24, 256, 1024, 64), MESH_AXES,
                  fsdp=False)
    assert sa == P(None, None, "model", None)
    # int4 packs bank_b's LAST axis (d/2) — still TP-divisible
    sb = spec_for("qbank/bank_b_q", (24, 256, 64, 512), MESH_AXES,
                  fsdp=False)
    assert sb == P(None, None, None, "model")
    s8 = spec_for("qbank/bank_a_scale", (24, 256, 1024), MESH_AXES,
                  fsdp=False)
    assert s8 == P(None, None, "model")
    s4 = spec_for("qbank/bank_a_scale", (24, 256, 1024, 2), MESH_AXES,
                  fsdp=False)
    assert s4 == P(None, None, "model", None)
    sb8 = spec_for("qbank/bank_b_scale", (24, 256, 64), MESH_AXES,
                   fsdp=False)
    assert sb8 == P(None, None, None)
    sb4 = spec_for("qbank/bank_b_scale", (24, 256, 64, 32), MESH_AXES,
                   fsdp=False)
    assert sb4 == P(None, None, None, "model")


def test_quant_engine_qbank_and_buffers_shard():
    """A quantized ServeEngine under an 8-device mesh: qbank leaves and
    quantized slot buffers get valid specs, decode runs, and per-device
    bytes land strictly below the single-device footprint (subprocess:
    forces host devices)."""
    _run_sub("""
    from repro.configs import get_config, reduce_for_smoke
    from repro.core import xpeft as XP
    from repro.core.profiles import ProfileStore
    from repro.models import init_lm
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b")).with_xpeft(
        bank_quant="int8")
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    xp = cfg.xpeft
    store = ProfileStore(cfg.num_layers, xp.num_adapters, xp.bottleneck,
                         xp.mask_type, xp.k, quant="int8")
    table = XP.init_profile_table(key, cfg)
    for pid in range(4):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    mesh = make_mesh((4, 2), ("data", "model"))
    eng = ServeEngine(cfg, params, store, max_slots=8, max_seq=32,
                      mesh=mesh)
    reqs = [Request(uid=i, prompt=np.arange(5) % cfg.vocab_size,
                    profile_id=i % 4, max_new_tokens=6) for i in range(8)]
    eng.run_until_drained(reqs)
    assert all(len(r.generated) for r in reqs)
    per_dev = eng.resident_bytes_per_device()
    assert "qbank" in per_dev and per_dev["qbank"] > 0
    single = ServeEngine(cfg, init_lm(key, cfg), store, max_slots=8,
                         max_seq=32).resident_bytes_per_device()
    assert per_dev["total"] < single["total"], (per_dev, single)
    print("quant shard ok")
    """)


def test_rules_fsdp_largest_dim_tie_break():
    """Equal largest candidate dims: FSDP takes the LATER one (max over
    (dim, index) tuples) — pinned so resharding stays deterministic
    across processes."""
    s = spec_for("frozen/unmatched_w", (256, 256), {"data": 4}, fsdp=True)
    assert s == P(None, "data")


def test_overrides_pattern_matching():
    """A substring-matched override replaces the name rule (first match
    wins); non-matching patterns fall through to the built-in rule."""
    s = spec_for("blocks/attn/wq", (12, 64, 8, 16), MESH_AXES, fsdp=False,
                 overrides={"attn/wq": ("tp_d", None, None)})
    assert s == P(None, "model", None, None)
    s2 = spec_for("blocks/attn/wq", (12, 64, 8, 16), MESH_AXES, fsdp=False,
                  overrides={"mlp/wg": ("tp_d", None, None)})
    assert s2 == P(None, None, "model", None)  # built-in heads rule


# ------------------------------------------- per-device memory accounting

def _abs(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_sharded_bytes_per_device_counts_axes():
    tree = {"a": _abs((8, 64)), "b": _abs((3,))}
    specs = {"a": P("data", "model"), "b": P(None)}
    got = sharded_bytes_per_device(tree, specs, MESH_AXES)
    assert got == (8 * 64 * 4) // 8 + 3 * 4


def test_sharded_bytes_per_device_rejects_missing_spec():
    tree = {"a": _abs((8, 64)), "b": _abs((3,))}
    with pytest.raises(ValueError, match="exactly one spec"):
        sharded_bytes_per_device(tree, {"a": P("data", None)}, MESH_AXES)


def test_sharded_bytes_per_device_rejects_short_spec():
    with pytest.raises(ValueError, match="full rank"):
        sharded_bytes_per_device({"a": _abs((8, 64))}, {"a": P("data")},
                                 MESH_AXES)


def test_sharded_bytes_per_device_rejects_unknown_axis():
    with pytest.raises(ValueError, match="mesh axis"):
        sharded_bytes_per_device({"a": _abs((8, 64))},
                                 {"a": P("pod", None)}, MESH_AXES)


def test_leading_axis_specs_divisibility():
    class _Mesh:  # only .shape is consulted
        shape = MESH_AXES
    specs = leading_axis_specs(
        {"x": _abs((8, 3)), "odd": _abs((5,)), "s": _abs(())}, _Mesh())
    assert specs["x"] == P("data", None)
    assert specs["odd"] == P(None)      # 5 % 4 != 0 -> replicated
    assert specs["s"] == P()


_SUB_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def _run_sub(body: str):
    code = _SUB_PRELUDE + textwrap.dedent(body)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


def test_compressed_psum_numerics():
    _run_sub("""
    from functools import partial
    from repro.distributed.collectives import compressed_psum, compressed_psum_ef
    mesh = make_mesh((8,), ("d",))
    x = jax.random.normal(jax.random.key(0), (8, 64))

    @partial(jax.shard_map, mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
    def f(xl):
        return compressed_psum(xl, "d")
    got = f(x)[0]
    want = x.sum(0)
    err = float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())
    assert err < 0.05, err

    # error feedback: mean of quantized psums over repeated steps converges
    @partial(jax.shard_map, mesh=mesh, in_specs=(P("d", None), P("d", None)),
             out_specs=(P("d", None), P("d", None)))
    def g(xl, el):
        y, e = compressed_psum_ef(xl, el, "d")
        return y, e
    err_buf = jnp.zeros_like(x)
    acc = 0.0
    for i in range(20):
        y, err_buf = g(x, err_buf)
        acc = acc + y[0]
    rel = float(jnp.abs(acc / 20 - want).max()) / float(jnp.abs(want).max())
    assert rel < 0.01, rel
    print("compressed psum ok")
    """)


def test_pipeline_matches_single_device():
    _run_sub("""
    from repro.distributed.pipeline import pipeline_apply, stack_stages
    mesh = make_mesh((4, 2), ("pod", "model"))
    L, d = 8, 16
    ks = jax.random.split(jax.random.key(0), L)
    layers = jax.vmap(lambda k: {"w": jax.random.normal(k, (d, d)) / np.sqrt(d)})(ks)

    def stage_fn(stage_params, x):
        def body(c, lp):
            return jnp.tanh(c @ lp["w"]), None
        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    x_micro = jax.random.normal(jax.random.key(1), (6, 4, d))  # M=6 microbatches
    stacked = stack_stages(layers, 4)
    y_pipe = pipeline_apply(stage_fn, stacked, x_micro, mesh, axis="pod")

    # reference: run all layers sequentially
    def ref_one(x):
        def body(c, lp):
            return jnp.tanh(c @ lp["w"]), None
        y, _ = jax.lax.scan(body, x, layers)
        return y
    y_ref = jax.vmap(ref_one)(x_micro)
    err = float(jnp.abs(y_pipe - y_ref).max())
    assert err < 1e-4, err
    print("pipeline ok", err)
    """)


def test_small_mesh_train_step_and_moe_parity():
    """pjit xpeft train step on a 4x2 mesh == single-device result; also
    checks the shard_map MoE path against the local path."""
    _run_sub("""
    from repro.configs import get_config, reduce_for_smoke
    from repro.train.steps import init_train_state, make_train_step
    from repro.distributed import ctx
    from repro.distributed.sharding import param_specs, batch_specs, to_shardings
    from repro.models.moe import init_moe, moe_apply

    cfg = reduce_for_smoke(get_config("qwen3-moe-30b-a3b")).with_(
        num_experts=8, top_k=2, capacity_factor=8.0)
    key = jax.random.key(0)
    state = init_train_state(key, cfg, "xpeft")
    B, T = 8, 16
    batch = {"tokens": jax.random.randint(key, (B, T), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, T), 0, cfg.vocab_size),
             "profile_ids": jnp.arange(B) % 4}
    step = make_train_step(cfg, "xpeft", lr=1e-3)
    s1, m1 = jax.jit(step)(state, batch, jax.random.key(7))

    mesh = make_mesh((4, 2), ("data", "model"))
    with ctx.mesh_context(mesh):
        st_sh = to_shardings(param_specs(state, mesh, fsdp=True), mesh)
        b_sh = to_shardings(batch_specs(batch, mesh, B), mesh)
        stepd = jax.jit(step, in_shardings=(st_sh, b_sh, None),
                        out_shardings=(st_sh, None))
        s2, m2 = stepd(state, batch, jax.random.key(7))
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert abs(l1 - l2) / max(abs(l1), 1e-6) < 2e-2, (l1, l2)

    # MoE parity: shard_map path vs local path on identical inputs
    p = init_moe(jax.random.key(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (B, T, cfg.d_model))
    y_local, _ = moe_apply(p, x, cfg)          # no mesh ctx -> local
    with ctx.mesh_context(mesh):
        xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, P(("data",), None, None)))
        y_dist, _ = jax.jit(lambda pp, xx: moe_apply(pp, xx, cfg))(p, xs)
    err = float(jnp.abs(y_local - y_dist).max())
    assert err < 1e-3, err
    print("mesh train + moe parity ok", l1, l2, err)
    """)


def test_eight_device_serve_onboard_bitwise_parity():
    """End-to-end tentpole gate: the 8-fake-device mesh onboards and serves
    BIT-identically to the 1-device path — graduated store bytes, admission
    Â/B̂ cache entries, and decoded token ids all equal, with the gang step
    tracing exactly once on both paths. Runs benchmarks/sharded_smoke.py
    (the same vehicle as `make multidev-smoke`) through its shared
    subprocess entry point."""
    from benchmarks.sharded_smoke import run_subprocess

    rec = run_subprocess(check=True)
    assert rec["onboard_store_bitwise_equal"]
    assert rec["serve_entries_bitwise_equal"]
    assert rec["decode_tokens_equal"]
    assert rec["gang_traces"] == {"single": 1, "sharded": 1}
    single = rec["single"]["resident_bytes_per_device"]["total"]
    sharded = rec["sharded"]["resident_bytes_per_device"]["total"]
    assert 0 < sharded < single  # the mesh actually shards device state


def test_mesh_engine_kernels_run_under_shard_map():
    """GSPMD cannot partition a Mosaic kernel: on a mesh the engine traces
    its steps under ops.kernel_mesh, so each kernel call (here the exact
    kernel bodies, interpret mode) runs as a shard_map — rows over "data",
    the bank's d_model over "model". Decoded tokens and admission
    aggregates must equal the single-device engine's."""
    _run_sub("""
    from repro.configs import get_config, reduce_for_smoke
    from repro.launch.serve import build_engine
    from repro.serve.engine import Request
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b")).with_xpeft(
        kernel_impl="interpret")
    mesh = make_mesh((4, 2), ("data", "model"))

    def serve(mesh_):
        eng = build_engine(cfg, profiles=3, slots=4, max_seq=64, mesh=mesh_,
                           continuous=True, page_size=16)
        reqs = [Request(uid=i, prompt=np.arange(3 + 2 * i) % cfg.vocab_size,
                        profile_id=i % 3, max_new_tokens=5)
                for i in range(6)]
        eng.run_until_drained(reqs)
        assert eng.serve_stats()["step_traces"] == 1
        return ([list(r.generated) for r in reqs],
                np.asarray(eng.profile_cache.peek(0)["a_hat"]))

    toks1, a1 = serve(None)
    toks8, a8 = serve(mesh)
    assert toks1 == toks8, (toks1, toks8)
    np.testing.assert_allclose(a8, a1, rtol=0, atol=1e-6)
    """)


def test_elastic_reshard_smaller_mesh():
    _run_sub("""
    from repro.distributed.fault import reshard_state, surviving_mesh
    from jax.sharding import NamedSharding
    mesh8 = make_mesh((8,), ("data",))
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh8, P("data", None)))
    mesh4 = surviving_mesh(("data",), (8,), "data", 4)
    y = reshard_state({"x": x}, {"x": NamedSharding(mesh4, P("data", None))})
    np.testing.assert_array_equal(np.asarray(y["x"]), np.asarray(x))
    print("elastic ok")
    """)
