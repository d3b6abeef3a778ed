"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, at qwen1.5-0.5b widths (d 1024, b 64, L 24, N 256, k 50, 8 slots).

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
