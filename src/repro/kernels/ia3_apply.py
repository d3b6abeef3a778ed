"""Pallas TPU kernel: batched fused IA3 scaling.

IA3 rescales the residual stream elementwise: ``y = x * (1 + s)`` with
``s [B, d]`` the mask-weighted sum of the profile's selected scale DELTAS
(one aggregate vector per batch row, hydrated at admission exactly like
Â/B̂). The op is pure VPU work and trivially HBM-bound — the kernel's only
job is to stream the ``[block_t, d]`` activation tile through VMEM once
with the row's scale vector held resident, instead of letting XLA
materialize the broadcast ``[B, T, d]`` scale:

    HBM traffic: read x once + write y once (2·B·T·d) + s once (B·d).

``s == 0`` (empty selection / degraded serving) multiplies by exactly 1.0,
so the zero entry stays bitwise the bare PLM — the same identity contract
the bottleneck/LoRA zero aggregates satisfy additively.

Shared broadcast: pass 1-D ``s [d]`` to apply one profile's scale to the
whole batch (index map pins the fetch to row 0, mirroring the
fused-adapter kernels' shared-Â/B̂ path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref):
    x = x_ref[0]                                            # [block_t, d]
    s = s_ref[0].astype(jnp.float32)                        # [1, d]
    y = x.astype(jnp.float32) * (1.0 + s)
    o_ref[0] = y.astype(x.dtype)


def _pick_block_t(T: int, block_t: int) -> int:
    block_t = min(block_t, T)
    while T % block_t:
        block_t -= 1
    return block_t


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ia3_apply_batched(x, s, *, block_t: int = 256, interpret: bool = False):
    """x [B, T, d]; s [B, d] or [d] (shared) -> x * (1 + s)."""
    B, T, d = x.shape
    block_t = _pick_block_t(T, block_t)

    shared = s.ndim == 1
    # s as [B, 1, d]: a (1, d) block of a [B, d] array breaks the TPU's
    # (8, 128) tiling rule; a unit second-minor axis does not
    s = s.reshape(-1, 1, d)
    row = (lambda bi, ti: (0, 0, 0)) if shared else \
        (lambda bi, ti: (bi, 0, 0))

    return pl.pallas_call(
        _kernel,
        grid=(B, T // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, 1, d), row),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), x.dtype),
        interpret=interpret,
    )(x, s)
