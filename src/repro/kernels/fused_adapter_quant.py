"""Pallas TPU kernel: batched fused adapter with in-register dequant.

The decode hot path applies per-slot aggregated Â/B̂ every layer; with
`bank_quant` enabled those records live in HBM as int8 / packed int4 +
fp16 scales (profile cache entries and the per-slot mask buffers). This
kernel is `fused_adapter_batched` with a dequant prologue: the quantized
projection rows stream HBM->VMEM at their quantized width and widen to
fp32 registers right before the MXU dots, so the adapter's HBM traffic
shrinks by the storage factor with ZERO extra latency from a separate
dequantize pass (no fp32/bf16 Â/B̂ ever materializes in HBM).

Grid (B, T/block_t), per-row records only (every slot serves its own
profile); compute is fp32 end-to-end (dequant output is fp32), matching
`ref.fused_adapter_quant_batched_ref` bit-for-bit — both call
`quant.schemes.dequant_block` and run the same LN/activation op sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.schemes import check_scheme, dequant_block


def _kernel(x_ref, aq_ref, as_ref, bq_ref, bs_ref, ls_ref, lb_ref, o_ref, *,
            scheme, activation, eps):
    x = x_ref[0].astype(jnp.float32)                        # [block_t, d]
    a = dequant_block(aq_ref[0], as_ref[0], scheme)         # [d, b] f32
    h = jnp.dot(x, a, preferred_element_type=jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    h = h * ls_ref[0].astype(jnp.float32) + lb_ref[0].astype(jnp.float32)
    if activation == "gelu":
        h = jax.nn.gelu(h)
    b_hat = dequant_block(bq_ref[0], bs_ref[0], scheme)     # [b, d] f32
    y = jnp.dot(h, b_hat, preferred_element_type=jnp.float32)
    o_ref[0] = (x + y).astype(o_ref.dtype)


def _pick_block_t(T: int, block_t: int) -> int:
    block_t = min(block_t, T)
    while T % block_t:
        block_t -= 1
    return block_t


@functools.partial(jax.jit, static_argnames=("scheme", "activation",
                                             "block_t", "interpret"))
def fused_adapter_quant_batched(x, a_q, a_scale, b_q, b_scale, ln_scale,
                                ln_bias, *, scheme: str,
                                activation: str = "gelu", block_t: int = 256,
                                interpret: bool = False):
    """x [B, T, d]; a_q [B, d, b] int8 (or [B, d, b/2] packed int4) with
    a_scale [B, d] / [B, d, b/g]; b_q [B, b, d] (or [B, b, d/2]) with
    b_scale [B, b] / [B, b, d/g]; ln_* [B, b] -> [B, T, d]."""
    check_scheme(scheme)
    B, T, d = x.shape
    b = b_q.shape[1]
    block_t = _pick_block_t(T, block_t)

    def row3(bi, ti):
        return (bi, 0, 0)

    # Mosaic cannot load fp16 on the TPU: widen the scales to f32 here
    # (exact, and dequant_block's first op anyway). Per-row vectors gain a
    # unit axis: (1, d) / (1, b) blocks of [B, d] / [B, b] arrays break
    # the TPU's (8, 128) tiling rule.
    a_scale = a_scale.astype(jnp.float32)
    b_scale = b_scale.astype(jnp.float32)
    if scheme == "int8":
        a_scale, b_scale = a_scale[..., None], b_scale[..., None]
    ln_scale = ln_scale.reshape(B, 1, b)
    ln_bias = ln_bias.reshape(B, 1, b)
    kernel = functools.partial(_kernel, scheme=scheme, activation=activation,
                               eps=1e-6)
    return pl.pallas_call(
        kernel,
        grid=(B, T // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, d, a_q.shape[-1]), row3),
            pl.BlockSpec((1, d, a_scale.shape[-1]), row3),
            pl.BlockSpec((1, b, b_q.shape[-1]), row3),
            pl.BlockSpec((1, b, b_scale.shape[-1]), row3),
            pl.BlockSpec((1, 1, b), row3),
            pl.BlockSpec((1, 1, b), row3),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), x.dtype),
        interpret=interpret,
    )(x, a_q, a_scale, b_q, b_scale, ln_scale, ln_bias)
