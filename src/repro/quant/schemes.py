"""Quantization schemes for the adapter bank and stored Â/B̂ rows.

At production scale the BANK bounds everything: every k-sparse admission
reads k·L·d·b bank bytes and every device holds the full bank plus the
aggregated Â/B̂ records the profile cache serves from. Two schemes shrink
both, selected by ``XPeftConfig.bank_quant``:

- ``int8`` — symmetric per-row (last axis) int8, one fp16 scale per row:
  ``q = clip(round(x / s), ±127)`` with ``s = absmax/127``. 2x fewer bytes
  than bf16 (4x vs fp32) at ~0.4% relative error on adapter-scale values.
- ``int4`` — group-wise packed int4: the last axis is split into groups of
  ``group_for(n, group)`` values sharing one fp16 scale (``s = absmax/7``),
  two values per byte. ~3.6x fewer bytes than bf16.

Packing is PLANAR, not interleaved: byte ``i`` carries element ``i`` in its
low nibble and element ``i + n/2`` in its high nibble, so in-register
unpacking is two shifts + one concatenate — no lane interleave, which keeps
the dequant epilogue cheap inside the Pallas kernels (they import
``dequant_block`` so kernel, interpret, and jnp-ref backends share the
EXACT op sequence and stay bit-identical).

Everything here is pure jnp (host numpy arrays welcome) and jit-safe; the
quantize side runs at engine construction / admission / graduation, never
on the decode hot path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SCHEMES = ("none", "int8", "int4")
INT4_BIAS = 8  # nibbles store q + 8 in [1, 15]; 0 encodes a zero-scale group


def check_scheme(scheme: str) -> str:
    if scheme not in SCHEMES:
        raise ValueError(f"bank_quant {scheme!r}; expected one of {SCHEMES}")
    return scheme


def group_for(n: int, group: int = 32) -> int:
    """Largest divisor of ``n`` that is <= ``group`` (int4 group size).

    The configured group is an upper bound: reduced smoke configs have
    b=4-wide rows where a 32-wide group cannot fit. n itself must be even
    (two nibbles per byte); groups may be any divisor — packing is planar
    over the whole axis and independent of the grouping."""
    if n % 2:
        raise ValueError(f"int4 needs an even last axis, got {n}")
    g = min(group, n)
    while n % g:
        g -= 1
    return max(g, 2)


# ----------------------------------------------------------------------------
# int8: symmetric per-row, fp16 scale
# ----------------------------------------------------------------------------

def quantize_int8(x) -> dict:
    """x [..., n] float -> {"q": int8 [..., n], "scale": fp16 [...]}.

    The scale is rounded to fp16 BEFORE quantizing, so dequantization is
    the exact inverse of the grid actually used (roundtrip error stays
    <= scale/2 + the clip tail, never the fp16 rounding of the scale)."""
    x = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = (absmax / 127.0).astype(jnp.float16)
    s32 = scale.astype(jnp.float32)[..., None]
    q = jnp.where(s32 > 0, jnp.round(x / jnp.where(s32 > 0, s32, 1.0)), 0.0)
    return {"q": jnp.clip(q, -127, 127).astype(jnp.int8), "scale": scale}


# ----------------------------------------------------------------------------
# int4: group-wise along the last axis, planar-packed two values per byte
# ----------------------------------------------------------------------------

def pack_int4(q):
    """int [..., n] in [-8, 7] -> uint8 [..., n/2] (planar: low nibble =
    first half of the axis, high nibble = second half, biased +8)."""
    n = q.shape[-1]
    b = (q + INT4_BIAS).astype(jnp.uint8)
    return b[..., : n // 2] | (b[..., n // 2:] << 4)


def unpack_int4(packed):
    """uint8 [..., n/2] -> int32 [..., n] in [-8, 7] (planar layout).
    Widens before shifting: Mosaic has no 8-bit shift on the TPU."""
    p = packed.astype(jnp.int32)
    lo = (p & 0xF) - INT4_BIAS
    hi = (p >> 4) - INT4_BIAS
    return jnp.concatenate([lo, hi], axis=-1)


def quantize_int4(x, *, group: int = 32) -> dict:
    """x [..., n] float -> {"q": uint8 [..., n/2], "scale": fp16 [..., n/g]}
    with g = group_for(n, group); values in [-7, 7] (symmetric)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[-1]
    g = group_for(n, group)
    xg = x.reshape(x.shape[:-1] + (n // g, g))
    scale = (jnp.max(jnp.abs(xg), axis=-1) / 7.0).astype(jnp.float16)
    s32 = scale.astype(jnp.float32)[..., None]
    q = jnp.where(s32 > 0, jnp.round(xg / jnp.where(s32 > 0, s32, 1.0)), 0.0)
    q = jnp.clip(q, -7, 7).astype(jnp.int32).reshape(x.shape)
    return {"q": pack_int4(q), "scale": scale}


# ----------------------------------------------------------------------------
# shared dequant epilogue (kernels + refs import THIS, never reimplement)
# ----------------------------------------------------------------------------

def dequant_block(q, scale, scheme: str):
    """Dequantize one block to fp32. int8: q [..., n] with scale [...] (or
    [..., 1], the form the TPU kernels' VMEM tiling wants); int4: packed
    q [..., n/2] with scale [..., n/g]. The op sequence here is the single
    source of truth for every backend (Pallas compiled, Pallas interpret,
    jnp ref), which is what makes them bit-identical."""
    if scheme == "int8":
        s = scale.astype(jnp.float32)
        if s.ndim < q.ndim:
            s = s[..., None]
        return q.astype(jnp.float32) * s
    if scheme == "int4":
        vals = unpack_int4(q).astype(jnp.float32)
        s = scale.astype(jnp.float32)
        groups = s.shape[-1]
        g = vals.shape[-1] // groups
        # widen the group scales to one per element by selects, not by a
        # [..., groups, g] reshape (which Mosaic cannot lay out); each
        # element still takes exactly one f32 product with its scale
        gid = jax.lax.broadcasted_iota(jnp.int32, vals.shape,
                                       vals.ndim - 1) // g
        wide = jnp.broadcast_to(s[..., :1], vals.shape)
        for j in range(1, groups):
            wide = jnp.where(gid == j, s[..., j:j + 1], wide)
        return vals * wide
    raise ValueError(f"dequant_block: scheme {scheme!r}")


def quantize(x, scheme: str, *, group: int = 32) -> dict:
    check_scheme(scheme)
    if scheme == "int8":
        return quantize_int8(x)
    if scheme == "int4":
        return quantize_int4(x, group=group)
    raise ValueError("quantize: scheme 'none' has no quantized form")


def dequantize(rec: dict, scheme: str):
    return dequant_block(rec["q"], rec["scale"], scheme)


def quant_spec(shape, scheme: str, *, group: int = 32):
    """(q_shape, q_dtype, scale_shape) for a float tensor of ``shape``
    quantized along its last axis — how the engine sizes its per-slot
    quantized mask buffers without materializing a dummy row."""
    check_scheme(scheme)
    n = shape[-1]
    if scheme == "int8":
        return shape, jnp.int8, shape[:-1]
    g = group_for(n, group)
    return shape[:-1] + (n // 2,), jnp.uint8, shape[:-1] + (n // g,)


# ----------------------------------------------------------------------------
# bank-level helpers
# ----------------------------------------------------------------------------

def quantize_bank(bank: dict, scheme: str, *, group: int = 32) -> dict:
    """{"bank_a": [L,N,d,b], "bank_b": [L,N,b,d]} -> flat quantized tree
    {"bank_a_q", "bank_a_scale", "bank_b_q", "bank_b_scale"}.

    Flat names (not nested dicts) so the GSPMD sharding rules can address
    each leaf: bank_*_q keep the bf16 bank's d_model TP sharding, scales
    ride along (distributed/sharding.py)."""
    check_scheme(scheme)
    qa = quantize(bank["bank_a"], scheme, group=group)
    qb = quantize(bank["bank_b"], scheme, group=group)
    return {"bank_a_q": qa["q"], "bank_a_scale": qa["scale"],
            "bank_b_q": qb["q"], "bank_b_scale": qb["scale"]}


def quantize_bank_hetero(bank: dict, scheme: str, *, group: int = 32) -> dict:
    """Heterogeneous-bank quantization: matmul-family segments (bottleneck
    bank_a/bank_b, LoRA lora_a/lora_b) get the full int8/int4 treatment —
    their error is averaged away inside a d-wide contraction. IA3 scale
    deltas and prefix KV rows are stored fp16 instead: both are consumed
    ELEMENTWISE (a multiplicative gate / raw attention rows), so per-entry
    quantization noise lands directly on activations with nothing to
    average over — and at [L, cnt, d] / [L, cnt, P, kv] they are a
    rounding error of the bank's footprint anyway."""
    check_scheme(scheme)
    out = {}
    for name in ("bank_a", "bank_b", "lora_a", "lora_b"):
        if name in bank:
            q = quantize(bank[name], scheme, group=group)
            out[f"{name}_q"] = q["q"]
            out[f"{name}_scale"] = q["scale"]
    for name in ("ia3_v", "prefix_k", "prefix_v"):
        if name in bank:
            out[name] = bank[name].astype(jnp.float16)
    return out
