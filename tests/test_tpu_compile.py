"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, at qwen1.5-0.5b widths (d 1024, b 64, L 24, N 256, k 50, 8 slots;
the paged decode attention also at the serving cells' pools and at
deepseek-7b widths).

Interpret mode runs the kernel body on the CPU but cannot see the chip
compiler's refusals (block tiling, fp16 loads, vector reshapes, VMEM);
these compiles can, with no chip attached. Each asserts that the program
really holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. The fixture skips where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.fused_adapter_batched import fused_adapter_batched
from repro.kernels.fused_adapter_quant import fused_adapter_quant_batched
from repro.kernels.ia3_apply import ia3_apply_batched
from repro.kernels.mask_aggregate import mask_aggregate_batched
from repro.kernels.mask_aggregate_quant import mask_aggregate_quant_batched
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.quant.schemes import quant_spec

L, N, D, B_, K, SLOTS = 24, 256, 1024, 64, 50, 8
P = SLOTS * L                      # admission rows: every slot's layers
PREFILL_BUCKETS = (64, 128, 256)   # pow2 buckets of 64-256-token prompts
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh22(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("side", ["a", "b"])
def test_mask_aggregate_batched(one_chip, side):
    """Admission: the k-sparse aggregate over the layer-folded bank."""
    row = (D, B_) if side == "a" else (B_, D)
    txt = _compiled_text(mask_aggregate_batched, one_chip,
                         ((L * N,) + row, BF), ((P, K), I32), ((P, K), F32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("T", (1,) + PREFILL_BUCKETS)
def test_fused_adapter_batched(one_chip, T):
    """Every decode (T=1) and prefill step: per-slot Â/B̂ applied."""
    txt = _compiled_text(fused_adapter_batched, one_chip,
                         ((SLOTS, T, D), BF), ((SLOTS, D, B_), BF),
                         ((SLOTS, B_, D), BF), ((SLOTS, B_), F32),
                         ((SLOTS, B_), F32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("shared", [False, True])
def test_ia3_apply_batched(one_chip, shared):
    s = ((D,), BF) if shared else ((SLOTS, D), BF)
    txt = _compiled_text(ia3_apply_batched, one_chip,
                         ((SLOTS, 1, D), BF), s)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("scheme", ["int8", "int4"])
@pytest.mark.parametrize("side", ["a", "b"])
def test_mask_aggregate_quant_batched(one_chip, scheme, side):
    row = (D, B_) if side == "a" else (B_, D)
    q, qdt, sc = quant_spec((L * N,) + row, scheme)
    txt = _compiled_text(mask_aggregate_quant_batched, one_chip,
                         (q, qdt), (sc, jnp.float16), ((P, K), I32),
                         ((P, K), F32), scheme=scheme)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("scheme", ["int8", "int4"])
@pytest.mark.parametrize("T", [1, PREFILL_BUCKETS[-1]])
def test_fused_adapter_quant(one_chip, scheme, T):
    a, qdt, a_s = quant_spec((SLOTS, D, B_), scheme)
    b, _, b_s = quant_spec((SLOTS, B_, D), scheme)
    txt = _compiled_text(fused_adapter_quant_batched, one_chip,
                         ((SLOTS, T, D), BF), (a, qdt), (a_s, jnp.float16),
                         (b, qdt), (b_s, jnp.float16), ((SLOTS, B_), F32),
                         ((SLOTS, B_), F32), scheme=scheme)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kernel", ["fused_adapter", "aggregate_a",
                                    "aggregate_b"])
def test_kernels_on_2x2_mesh(mesh22, kernel):
    """GSPMD refuses to partition a Mosaic kernel; under ops.kernel_mesh
    each call is a shard_map (rows over "data", the bank's d_model over
    "model") and compiles, with no all-gather of the bank."""
    from jax.sharding import NamedSharding, PartitionSpec

    def S(shape, dt, *spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh22, PartitionSpec(*spec)))

    if kernel == "fused_adapter":
        def fn(*a):
            with ops.kernel_mesh(mesh22):
                return ops.fused_adapter(*a, impl="pallas")
        args = (S((SLOTS, 1, D), BF, "data"), S((SLOTS, D, B_), BF, "data"),
                S((SLOTS, B_, D), BF, "data"), S((SLOTS, B_), F32, "data"),
                S((SLOTS, B_), F32, "data"))
    else:
        tp_dim = 1 if kernel == "aggregate_a" else 2
        row = (D, B_) if tp_dim == 1 else (B_, D)
        spec = [None, None, None]
        spec[tp_dim] = "model"

        def fn(*a):
            with ops.kernel_mesh(mesh22):
                return ops.mask_aggregate_batched(*a, impl="pallas",
                                                  tp_dim=tp_dim)
        args = (S((L * N,) + row, BF, *spec), S((P, K), I32),
                S((P, K), F32))
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    if kernel != "fused_adapter":
        assert "all-gather" not in txt


@pytest.mark.parametrize("widths", [
    (24, 16, 16, 64, 1024),       # qwen1.5-0.5b: L, slots, KV, hd, max_seq
    (4, 8, 32, 128, 4096),        # deepseek-7b, 4 layers
])
def test_paged_decode_attention(one_chip, widths):
    """Every continuous decode step, every layer: attention straight from
    the stacked lane-dense page pool (page 16), which stays in HBM."""
    Lp, slots, kv, hd, max_seq = widths
    page = 16
    mp = max_seq // page
    pool = ((Lp, slots * mp, page, kv * hd), BF)
    txt = _compiled_text(paged_decode_attention, one_chip,
                         ((slots, kv, hd), BF), ((slots, kv, hd), BF),
                         ((slots, kv, hd), BF), pool, pool, ((), I32),
                         ((slots, mp), I32), ((slots,), I32))
    assert "tpu_custom_call" in txt


def test_continuous_step_reads_pages_in_place():
    """The compiled continuous decode step (here on the CPU, which takes
    the kernel's jnp twin) holds no op shaped like a dense view of the
    pool, [L, slots, max_seq, ...], and its page pools are donated: the
    step's one row write lands in place."""
    import re

    import numpy as np
    from repro.configs import get_config, reduce_for_smoke
    from repro.launch.serve import build_engine
    from repro.serve.engine import Request

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    slots, max_seq = 3, 80          # no width of the model is 3 or 80
    eng = build_engine(cfg, profiles=3, slots=slots, max_seq=max_seq,
                       continuous=True, page_size=16)
    eng.run_until_drained([
        Request(uid=i, prompt=np.arange(3 + 5 * i) % cfg.vocab_size,
                profile_id=i % 3, max_new_tokens=4) for i in range(4)])
    assert eng.serve_stats()["decode_route"] == "paged"
    low = eng.slots._step.lower(eng.params, eng.cache, eng._masks_view,
                                eng.slots._arrays(), 0)
    hlo = low.compile().as_text()
    dense = re.compile(r"\[%d,%d,%d[,\]]" % (cfg.num_layers, slots, max_seq))
    assert not dense.search(hlo), dense.search(hlo).group(0)
    pool = eng.cache["data"]["k"].shape
    pool_t = "tensor<%sxf32>" % "x".join(map(str, pool))
    donated = re.findall(re.escape(pool_t) + r" \{[^}]*tf\.aliasing_output",
                         low.as_text())
    assert len(donated) == 2, low.as_text()[:2000]     # k and v pools
    assert "input_output_alias" in hlo.splitlines()[0]


def _record_relayouts(hlo, d, b):
    """Copies and transposes in a compiled TPU module that swap the two
    minor (physical) dims of a ``[.., d, b]`` or ``[.., b, d]`` array: a
    relayout of the adapter records. A copy that only moves major dims
    (the scan's ``[B, L, ..] -> [L, B, ..]``) keeps the minor two."""
    import re
    instr = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]"
                       r"\{([\d,]*)[:}]")
    move = re.compile(r"= \w+\[[\d,]*\]\{[^}]*\} (copy|transpose)"
                      r"\(%([\w.\-]+)\)")
    defs = {}
    for line in hlo.splitlines():
        m = instr.match(line)
        if m:
            defs[m.group(1)] = (
                [int(x) for x in m.group(2).split(",") if x],
                [int(x) for x in m.group(3).split(",") if x])
    bad = []
    for line in hlo.splitlines():
        m, op = instr.match(line), move.search(line)
        if not (m and op):
            continue
        dims, minor_to_major = defs[m.group(1)]
        if len(dims) < 3 or sorted(dims[-2:]) != sorted((d, b)):
            continue
        src = defs.get(op.group(2))
        if (op.group(1) == "transpose" or src is None
                or src[1][:2] != minor_to_major[:2]):
            bad.append(line.strip()[:200])
    return bad


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_adapter_records_reach_the_kernel_without_relayout(one_chip, step):
    """The slot records Â [.., d, b] and B̂ [.., b, d] (b = 64, below the
    128 lanes) reach ``fused_adapter_batched`` in the continuous decode
    step and in a prefill with no copy or transpose that swaps their two
    minor dims: the TPU stores a [.., d, 64] array with d minor, which is
    the [b, d] rows the kernel reads Â as. qwen1.5-0.5b widths, 2 layers,
    8 slots, a 128-token prefill."""
    from repro.configs import get_config
    from repro.models import attention as ATT
    from repro.models import model as MDL
    from repro.serve import pages as PG

    cfg = (get_config("qwen1.5-0.5b")
           .with_(num_layers=2, vocab_size=4096, remat="none")
           .with_xpeft(kernel_impl="pallas"))
    Lc, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    d, b = cfg.d_model, cfg.xpeft.bottleneck
    S, page = 256, 16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.eval_shape(lambda: MDL.init_lm(jax.random.key(0), cfg))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          {k: v for k, v in params.items()
                           if k != "xpeft_bank"})
    masks = {"a_hat": sds((SLOTS, Lc, d, b), BF),
             "b_hat": sds((SLOTS, Lc, b, d), BF),
             "ln_scale": sds((SLOTS, Lc, b), F32),
             "ln_bias": sds((SLOTS, Lc, b), F32)}
    if step == "decode":
        mp = S // page
        pool = sds((Lc, SLOTS * mp, page, KV * hd), BF)
        data = {"k": pool, "v": pool}
        assert MDL.paged_decode_route(cfg, masks, 1, data)

        def fn(params, data, table, tok, lengths, masks, active):
            kv = ATT.PagedKV(data["k"], data["v"], table,
                             jnp.where(active, lengths, 0))
            h, rows, _ = MDL.forward(params, tok[:, None], cfg,
                                     profile_masks=masks, cache=kv,
                                     cache_pos=lengths)
            return (MDL.lm_logits(params, h, cfg),
                    PG.writeback(data, rows, table, lengths, active, page))

        args = (params, data, sds((SLOTS, mp), I32), sds((SLOTS,), I32),
                sds((SLOTS,), I32), masks, sds((SLOTS,), jnp.bool_))
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(*args)
    else:
        def fn(params, tokens, masks):
            mini = MDL.init_cache(cfg, SLOTS, S)
            h, mini, _ = MDL.forward(params, tokens, cfg,
                                     profile_masks=masks, cache=mini,
                                     cache_pos=0)
            return h[:, -1], mini

        lowered = jax.jit(fn).lower(
            params, sds((SLOTS, PREFILL_BUCKETS[1]), I32), masks)
    hlo = lowered.compile().as_text()
    assert "fused_adapter_batched" in hlo
    assert _record_relayouts(hlo, d, b) == []
