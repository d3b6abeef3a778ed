"""Pallas TPU kernel: k-sparse aggregation over a QUANTIZED adapter bank.

Same revisiting-accumulation structure as `mask_aggregate_batched`
(grid (P, d/block_d, k), scalar-prefetched indices steering the bank-row
DMAs), but the bank rows arrive int8 (or packed int4) with fp16 scales and
are dequantized IN-REGISTER — the HBM traffic per aggregated profile drops
from 2·k·L·d·b bank-dtype bytes to the quantized row bytes:

    int8:  k·d·b bytes + k·d fp16 scales    (~2x under bf16, 4x under fp32)
    int4:  k·d·b/2 bytes + group scales     (~3.6x under bf16)

The dequant epilogue is `quant.schemes.dequant_block` — the SAME function
the jnp reference backend uses, so each dequantized term is BIT-identical
across compiled / interpret / ref (asserted in tests/test_kernels_quant.py
with one-hot weights). The k-term fp32 accumulation runs in the same
k-minor order in all three backends, but its final bits can differ by a
few ulps between backends: XLA contracts `w*deq + acc` into an FMA at
LLVM codegen inside whatever fusion each program structure produces, and
no HLO-level construct (optimization_barrier, bitcast round-trips) pins
that choice. Parity tests therefore assert terms bitwise and reductions
at <= 5e-7 absolute — quantization steps are ~1e-3, four orders larger.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.schemes import check_scheme, dequant_block


def _kernel(idx_ref, w_ref, q_ref, s_ref, out_ref, *, scheme):
    pi, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    deq = dequant_block(q_ref[0], s_ref[0], scheme)     # [block_d, b] f32
    out_ref[...] += w_ref[pi, ki] * deq


@functools.partial(jax.jit,
                   static_argnames=("scheme", "block_d", "interpret"))
def mask_aggregate_quant_batched(q, scale, idx, w, *, scheme: str,
                                 block_d: int = 256,
                                 interpret: bool = False):
    """Quantized bank rows q [N, d, b] int8 (or [N, d, b/2] uint8 packed
    int4) + scale [N, d] / [N, d, b/g] fp16, idx [P, k] int32, w [P, k]
    f32 -> [P, d, b] f32 (single batched launch, layer axis pre-folded into
    N by the caller exactly as in the unquantized path)."""
    check_scheme(scheme)
    N, d = q.shape[:2]
    b = q.shape[2] * (2 if scheme == "int4" else 1)
    P, k = idx.shape
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)

    # Mosaic cannot load fp16 on the TPU: widen the scales to f32 here
    # (exact, and dequant_block's first op anyway). int8's per-row scales
    # go to [N, d, 1]: a (1, block_d) block of [N, d] breaks the (8, 128)
    # tiling rule.
    scale = scale.astype(jnp.float32)
    if scheme == "int8":
        scale = scale[..., None]

    def bank_row(pi, di, ki, idx_ref, w_ref):
        return idx_ref[pi, ki], di, 0

    # the weights ride in SMEM beside the indices (as in
    # mask_aggregate_batched)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, d // block_d, k),
        in_specs=[
            pl.BlockSpec((1, block_d, q.shape[-1]), bank_row),
            pl.BlockSpec((1, block_d, scale.shape[-1]), bank_row),
        ],
        out_specs=pl.BlockSpec((1, block_d, b),
                               lambda pi, di, ki, idx_ref, w_ref: (pi, di, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, scheme=scheme),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, d, b), jnp.float32),
        interpret=interpret,
    )(idx, w, q, scale)
