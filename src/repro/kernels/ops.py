"""Kernel dispatch layer: jit'd public wrappers for the Pallas kernels.

Every model/serve hot path that applies or aggregates adapters routes
through this module (models/model.py `_xpeft_apply`, core/xpeft.py
`apply_precomputed_layer`, serve/engine.py admission). Callers pass
``impl`` — normally ``cfg.xpeft.kernel_impl`` — and the wrapper picks the
execution backend:

- ``auto``      — compiled Pallas on TPU; jnp reference elsewhere (this CPU
                  container). The reference is the fast path off-TPU: Pallas
                  interpret mode executes the kernel body op-by-op in the
                  scheduler and is strictly a semantics check.
- ``pallas``    — force the compiled Pallas kernel (TPU).
- ``interpret`` — force Pallas interpret mode (CI/parity testing: the exact
                  kernel body, runnable on CPU).
- ``ref``       — force the jnp oracle in kernels/ref.py.

Batched (ndim-3) inputs dispatch to the single-launch batched kernels
(`fused_adapter_batched.py`, `mask_aggregate.mask_aggregate_batched`)
rather than a vmap-of-kernel: one grid `(B, ...)` launch pipelines the
per-row Â/B̂ fetches instead of serializing B independent pallas_calls.

The quantized-bank routes (`mask_aggregate_quant_batched`,
`fused_adapter_quant` — XPeftConfig.bank_quant) take int8 / packed-int4
payloads + fp16 scales and dequantize in-register inside the kernels
(`mask_aggregate_quant.py`, `fused_adapter_quant.py`); the jnp refs share
the exact dequant op sequence (`quant.schemes.dequant_block`).

Meshes: GSPMD cannot partition a Mosaic kernel. Code traced under
``kernel_mesh(mesh)`` (the serve engine's jitted steps, when it has a
mesh) runs each kernel call as a ``shard_map``: per-row kernels split
their rows over "data", the bank aggregation splits the bank's d_model
axis over "model" (the layout ``distributed/sharding.py`` gives the bank),
everything else is replicated inside the map.

TPU deployment note: `bottleneck` b of 48/64 is below the 128 lane width;
for peak MXU utilization pad Â/B̂'s b dim to 128 — LN must then mask the
padded columns (ops here keep the unpadded semantics; the pad is a
launch-config choice).
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.fused_adapter import fused_adapter as _fused_pallas
from repro.kernels.fused_adapter_batched import (
    fused_adapter_batched as _fused_pallas_batched)
from repro.kernels.fused_adapter_quant import (
    fused_adapter_quant_batched as _fused_pallas_quant)
from repro.kernels.mask_aggregate import mask_aggregate as _agg_pallas
from repro.kernels.mask_aggregate import (
    mask_aggregate_batched as _agg_pallas_batched)
from repro.kernels.mask_aggregate_quant import (
    mask_aggregate_quant_batched as _agg_pallas_quant)
from repro.kernels.paged_decode_attention import (
    paged_decode_attention as _paged_pallas)
from repro.quant.schemes import check_scheme

IMPLS = ("auto", "pallas", "interpret", "ref")

_MESH: ContextVar = ContextVar("kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace the kernel calls inside under ``mesh`` (None: no mesh)."""
    tok = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(tok)


def _split(mesh, axis: str, size: int):
    """``axis`` if the mesh has it and it divides ``size``, else None."""
    n = dict(mesh.shape).get(axis)
    return axis if n and size % n == 0 else None


def _shard_map(kernel, args, in_specs, out_specs):
    return jax.shard_map(kernel, mesh=_MESH.get(), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _per_row(kernel, args, per_row):
    """Run a per-row kernel (args[0] is x [B, ...]; ``per_row[i]`` says
    whether args[i] carries the row axis): rows over "data" on a mesh."""
    mesh = _MESH.get()
    if mesh is None:
        return kernel(*args)
    lead = _split(mesh, "data", args[0].shape[0])
    specs = tuple(P(lead) if row else P() for row in per_row)
    return _shard_map(kernel, args, specs, P(lead))


def _aggregate(kernel, banks, idx, w, tp_dim):
    """Run a bank aggregation kernel(*banks, idx, w): on a mesh, bank row
    axis ``tp_dim`` (the d_model axis, or None) over "model"; indices and
    weights replicated. Rows aggregate independently, so each shard's
    result is exact."""
    mesh = _MESH.get()
    if mesh is None:
        return kernel(*banks, idx, w)
    spec = [None] * banks[0].ndim
    if tp_dim is not None:
        spec[tp_dim] = _split(mesh, "model", banks[0].shape[tp_dim])
    return _shard_map(kernel, (*banks, idx, w),
                      tuple(P(*spec[:b.ndim]) for b in banks) + (P(), P()),
                      P(*spec))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU, 'ref' elsewhere; others pass through."""
    if impl not in IMPLS:
        raise ValueError(f"kernel_impl {impl!r}; expected one of {IMPLS}")
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def mask_aggregate(bank, idx, w, *, impl: str = "auto"):
    """k-sparse bank aggregation. bank [N,d,b], idx [k], w [k] -> [d,b]."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.mask_aggregate_ref(bank, idx, w)
    return _agg_pallas(bank, idx, w, interpret=impl == "interpret")


def mask_aggregate_batched(bank, idx, w, *, impl: str = "auto",
                           tp_dim=None):
    """bank [N,d,b], idx [P,k], w [P,k] -> [P,d,b] (single batched launch).
    ``tp_dim``: the bank row axis (1 or 2) that holds d_model, if any."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.mask_aggregate_batched_ref(bank, idx, w)
    return _aggregate(
        lambda *a: _agg_pallas_batched(*a, interpret=impl == "interpret"),
        (bank,), idx, w, tp_dim)


def fused_adapter(x, a_hat, b_hat, ln_scale, ln_bias, *,
                  activation: str = "gelu", impl: str = "auto",
                  use_ln: bool = True):
    """Fused bottleneck adapter: y = x + B̂(act(LN(Â x))).

    x [T,d] with a_hat [d,b], or x [B,T,d] with per-row a_hat [B,d,b]
    (b_hat/ln_* likewise; 2-D adapter args broadcast across the batch).
    ``use_ln=False`` + ``activation="identity"`` is the LoRA route
    (y = x + B̂Âx) — same kernels, the LN block compiled out.
    """
    impl = resolve_impl(impl)
    if x.ndim == 3:
        if impl == "ref":
            return ref.fused_adapter_batched_ref(
                x, a_hat, b_hat, ln_scale, ln_bias, activation=activation,
                use_ln=use_ln)
        return _per_row(
            lambda *a: _fused_pallas_batched(
                *a, activation=activation, use_ln=use_ln,
                interpret=impl == "interpret"),
            (x, a_hat, b_hat, ln_scale, ln_bias),
            (True, a_hat.ndim == 3, b_hat.ndim == 3, ln_scale.ndim == 2,
             ln_bias.ndim == 2))
    if impl == "ref":
        return ref.fused_adapter_ref(x, a_hat, b_hat, ln_scale, ln_bias,
                                     activation=activation, use_ln=use_ln)
    return _fused_pallas(x, a_hat, b_hat, ln_scale, ln_bias,
                         activation=activation, use_ln=use_ln,
                         interpret=impl == "interpret")


def lora_adapter(x, a_hat, b_hat, *, impl: str = "auto"):
    """LoRA route: y = x + B̂Âx — the fused bottleneck kernels with the
    LN skipped and identity activation. Â/B̂ share the bottleneck
    aggregate shapes (rank r = b), so aggregation AND application reuse
    the same kernels row-for-row. ln args are dummies the kernel never
    reads (shapes must still tile)."""
    b = a_hat.shape[-1]
    lead = a_hat.shape[:-2]
    ones = jnp.ones(lead + (b,), x.dtype)
    zeros = jnp.zeros(lead + (b,), x.dtype)
    return fused_adapter(x, a_hat, b_hat, ones, zeros,
                         activation="identity", impl=impl, use_ln=False)


def ia3_apply(x, s, *, impl: str = "auto"):
    """IA3 fused scaling: y = x * (1 + s), s the aggregated scale-delta
    vector ([d] shared or [B, d] per-row); x [B,T,d] or [T,d]."""
    from repro.kernels.ia3_apply import ia3_apply_batched as _ia3_pallas

    impl = resolve_impl(impl)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    if impl == "ref":
        out = ref.ia3_apply_batched_ref(x, s)
    else:
        out = _per_row(
            lambda *a: _ia3_pallas(*a, interpret=impl == "interpret"),
            (x, s), (True, s.ndim == 2))
    return out[0] if squeeze else out


def decode_block_fused(x, pos, block, k_cache, v_cache, masks_l, *,
                       norm: str, qkv_bias: bool, use_rope: bool,
                       theta: float, cap: float, mlp_type: str,
                       act_name: str, adapter: str, adapter_act: str,
                       impl: str = "auto"):
    """Decode megakernel (ModelConfig.decode_fused): one program per layer
    applying norm/attention/MLP AND the X-PEFT adapter over the resident
    [B, 1, d] activations. `adapter` picks the fused route ("none", "bf16",
    "int8", "int4"); returns (y, k_rows, v_rows) — the caller scatters the
    K/V rows into the cache (paged writeback stays outside the kernel)."""
    from repro.kernels.decode_fused import decode_block_pallas

    impl = resolve_impl(impl)
    kw = dict(norm=norm, qkv_bias=qkv_bias, use_rope=use_rope, theta=theta,
              cap=cap, mlp_type=mlp_type, act_name=act_name, adapter=adapter,
              adapter_act=adapter_act)
    if impl == "ref":
        return ref.decode_block_ref(x, pos, block, k_cache, v_cache,
                                    masks_l, **kw)
    return decode_block_pallas(x, pos, block, k_cache, v_cache, masks_l,
                               interpret=impl == "interpret", **kw)


def paged_decode_supported(impl: str, page: int, row_elems: int,
                           dtype) -> bool:
    """Whether ``paged_decode_attention`` can serve a pool of ``page``-row
    pages of ``row_elems`` lanes here. Never under a kernel mesh: the
    pool's pages shard over "data" while a slot's pages may sit on any
    shard, so those steps keep the dense view. Compiled, a page must be
    whole packed tiles of a lane-dense row."""
    if _MESH.get() is not None:
        return False
    if resolve_impl(impl) != "pallas":
        return True
    rows_per_tile = 32 // jnp.dtype(dtype).itemsize
    return row_elems % 128 == 0 and page % rows_per_tile == 0


def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, layer, table,
                           lengths, *, impl: str = "auto"):
    """T=1 decode attention straight from the stacked page pool: q [B,H,hd]
    and the new token's k/v rows [B,KV,hd] against pools
    [L, n_pages, page, KV*hd] at ``layer`` through ``table`` [B, mp];
    slot b attends its ``lengths[b]`` cached positions plus the new row.
    -> [B, H, hd]."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.paged_decode_attention_ref(q, k_new, v_new, k_pool,
                                              v_pool, layer, table, lengths)
    return _paged_pallas(q, k_new, v_new, k_pool, v_pool, layer, table,
                         lengths, interpret=impl == "interpret")


# ----------------------------------------------------------------------------
# Quantized-bank routes (XPeftConfig.bank_quant != "none"). Pure additions:
# with bank_quant "none" nothing below is reachable and the unquantized
# dispatch above stays bitwise-identical.
# ----------------------------------------------------------------------------

def mask_aggregate_quant_batched(q, scale, idx, w, *, scheme: str,
                                 impl: str = "auto"):
    """k-sparse aggregation over a quantized bank: q [N,d,b|b/2] int8/uint8,
    scale [N,d|d,b/g] fp16, idx [P,k], w [P,k] -> [P,d,b] f32 (dequantized
    in-register; HBM reads stay at the quantized row width)."""
    check_scheme(scheme)
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.mask_aggregate_quant_batched_ref(q, scale, idx, w,
                                                    scheme=scheme)
    # on a mesh the quantized rows replicate inside the map: int4 packs
    # the B side's d_model axis planar, so a shard of it is not a slice
    return _aggregate(
        lambda *a: _agg_pallas_quant(*a, scheme=scheme,
                                     interpret=impl == "interpret"),
        (q, scale), idx, w, None)


def fused_adapter_quant(x, a_q, a_scale, b_q, b_scale, ln_scale, ln_bias, *,
                        scheme: str, activation: str = "gelu",
                        impl: str = "auto"):
    """Dequant-fused bottleneck adapter (decode/prefill hot path): x
    [B,T,d] with per-row quantized Â/B̂ records. Batched-only — quantized
    records always arrive per-slot from the profile cache / mask buffers."""
    check_scheme(scheme)
    if x.ndim != 3:
        raise ValueError("fused_adapter_quant is batched-only: x must be "
                         f"[B, T, d], got ndim={x.ndim}")
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref.fused_adapter_quant_batched_ref(
            x, a_q, a_scale, b_q, b_scale, ln_scale, ln_bias,
            scheme=scheme, activation=activation)
    return _per_row(
        lambda *a: _fused_pallas_quant(*a, scheme=scheme,
                                       activation=activation,
                                       interpret=impl == "interpret"),
        (x, a_q, a_scale, b_q, b_scale, ln_scale, ln_bias), (True,) * 7)
