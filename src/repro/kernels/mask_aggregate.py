"""Pallas TPU kernel: k-sparse adapter-bank aggregation.

Hard X-PEFT masks select k of N adapters; aggregating via the dense
mask-bank einsum reads the WHOLE bank from HBM (N·d·b bytes) and spends
N·d·b MACs. This kernel streams only the k selected slices HBM->VMEM using
scalar-prefetched indices (the mask's k-hot index list and its weights
live in SMEM before the grid starts, so the DMA pipeline knows which bank
rows to fetch), and accumulates in fp32 VMEM:

    bytes:  k·d·b   (N/k fewer, = 5.1x at N=256, k=50)
    flops:  k·d·b   MACs

Grid: (d/block_d, k) — the output tile stays resident in VMEM across the
minor k steps (revisiting accumulation), one bank tile per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, bank_ref, out_ref):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += w_ref[ki] * bank_ref[0].astype(jnp.float32)


def _kernel_batched(idx_ref, w_ref, bank_ref, out_ref):
    pi, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += w_ref[pi, ki] * bank_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mask_aggregate(bank, idx, w, *, block_d: int = 256,
                   interpret: bool = False):
    """bank [N, d, b], idx [k] int32, w [k] f32 -> [d, b] f32."""
    N, d, b = bank.shape
    k = idx.shape[0]
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // block_d, k),
        in_specs=[
            pl.BlockSpec((1, block_d, b),
                         lambda di, ki, idx_ref, w_ref: (idx_ref[ki], di, 0)),
        ],
        out_specs=pl.BlockSpec((block_d, b),
                               lambda di, ki, idx_ref, w_ref: (di, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, b), jnp.float32),
        interpret=interpret,
    )(idx, w, bank)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mask_aggregate_batched(bank, idx, w, *, block_d: int = 256,
                           interpret: bool = False):
    """bank [N, d, b], idx [P, k] int32, w [P, k] f32 -> [P, d, b] f32.

    One pallas_call for P profiles (serve admission batches the per-layer
    aggregations of every admitted request into one P = R·L launch; the
    layer axis is folded into the bank's N axis by the caller, see
    core.xpeft.precompute_effective_adapters_sparse). Grid (P, d/block_d, k):
    the output tile stays VMEM-resident across the minor k steps
    (revisiting accumulation) while scalar-prefetched indices steer the
    bank-row DMAs — HBM reads stay P·k·d·b, never N·d·b. The weights ride
    in SMEM beside the indices: a ``(1, k)`` VMEM block of ``w`` breaks the
    TPU's (8, 128) tiling rule.
    """
    N, d, b = bank.shape
    P, k = idx.shape
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, d // block_d, k),
        in_specs=[
            pl.BlockSpec((1, block_d, b),
                         lambda pi, di, ki, idx_ref, w_ref:
                         (idx_ref[pi, ki], di, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d, b),
                               lambda pi, di, ki, idx_ref, w_ref: (pi, di, 0)),
    )
    return pl.pallas_call(
        _kernel_batched,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, d, b), jnp.float32),
        interpret=interpret,
    )(idx, w, bank)
