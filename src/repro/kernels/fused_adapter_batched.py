"""Pallas TPU kernel: batched fused bottleneck-adapter application.

The serve decode step and per-example-profile training both present the
adapter with a BATCH of activations and a batch of (already aggregated)
projection pairs: ``x [B, T, d]``, ``Â [B, d, b]``, ``B̂ [B, b, d]`` — a
grouped matmul (one adapter per batch row). The unbatched kernel in
``fused_adapter.py`` covers one row; vmapping it launches B independent
pallas_calls and loses the chance to pipeline Â/B̂ fetches across rows.

This kernel is ONE ``pallas_call`` with grid ``(B, T // block_t)``: the
per-row projections are fetched once per row (the t-minor grid order keeps
them resident in VMEM across the row's T/block_t steps) and the activation
tile streams HBM->VMEM exactly once:

    HBM traffic: read x once + write y once (2·B·T·d)
                 + the projections once     (2·B·d·b)
    vs unfused ≥ 4·B·T·d plus the [B, T, b] intermediate round-trip.

Shared-adapter broadcast: when every row uses the SAME Â/B̂ (e.g. an
admission-time aggregated single profile applied to a whole batch), pass
2-D ``a_hat [d, b]`` / ``b_hat [b, d]`` — the index map pins the fetch to
block 0 and no [B, d, b] materialization happens.

VMEM budget at decode defaults (block_t<=256, d=8192, b=128, bf16):
x tile 4 MiB + Â 2 MiB + B̂ 2 MiB + out 4 MiB ≈ 12 MiB < 16 MiB v5e VMEM.
As with the unbatched kernel, pad b to the 128 lane width on real TPUs
(LN then masks the padded columns — see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, a_ref, b_ref, ls_ref, lb_ref, o_ref, *, activation, eps,
            use_ln):
    x = x_ref[0]                                            # [block_t, d]
    h = jnp.dot(x, a_ref[0], preferred_element_type=jnp.float32)
    if use_ln:
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + eps)
        h = h * ls_ref[0].astype(jnp.float32) + \
            lb_ref[0].astype(jnp.float32)
    if activation == "gelu":
        h = jax.nn.gelu(h)
    y = jnp.dot(h.astype(x.dtype), b_ref[0],
                preferred_element_type=jnp.float32)
    o_ref[0] = x + y.astype(x.dtype)


def _pick_block_t(T: int, block_t: int) -> int:
    block_t = min(block_t, T)
    while T % block_t:          # fall back to a divisor (decode T is 1 or pow2)
        block_t -= 1
    return block_t


@functools.partial(jax.jit,
                   static_argnames=("activation", "block_t", "interpret",
                                    "use_ln"))
def fused_adapter_batched(x, a_hat, b_hat, ln_scale, ln_bias, *,
                          activation: str = "gelu", block_t: int = 256,
                          interpret: bool = False, use_ln: bool = True):
    """x [B, T, d]; a_hat [B, d, b] or [d, b] (shared); b_hat [B, b, d] or
    [b, d]; ln_* [B, b] or [b] -> [B, T, d]. ``use_ln=False`` skips the
    LN-after-down-proj (the LoRA route: identity activation + no LN turns
    the bottleneck kernel into x + B̂Âx)."""
    B, T, d = x.shape
    b = a_hat.shape[-1]
    block_t = _pick_block_t(T, block_t)

    shared_proj = a_hat.ndim == 2
    shared_ln = ln_scale.ndim == 1
    if shared_proj:
        a_hat, b_hat = a_hat[None], b_hat[None]
    # ln rows as [B, 1, b]: a (1, b) block of a [B, b] array breaks the
    # TPU's (8, 128) tiling rule; a unit second-minor axis does not
    ln_scale = ln_scale.reshape(-1, 1, b)
    ln_bias = ln_bias.reshape(-1, 1, b)
    row_p = (lambda bi, ti: (0, 0, 0)) if shared_proj else \
        (lambda bi, ti: (bi, 0, 0))
    row_l = (lambda bi, ti: (0, 0, 0)) if shared_ln else \
        (lambda bi, ti: (bi, 0, 0))

    kernel = functools.partial(_kernel, activation=activation, eps=1e-6,
                               use_ln=use_ln)
    return pl.pallas_call(
        kernel,
        grid=(B, T // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, d, b), row_p),
            pl.BlockSpec((1, b, d), row_p),
            pl.BlockSpec((1, 1, b), row_l),
            pl.BlockSpec((1, 1, b), row_l),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), x.dtype),
        interpret=interpret,
    )(x, a_hat, b_hat, ln_scale, ln_bias)
