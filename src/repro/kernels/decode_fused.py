"""Pallas TPU megakernel: one whole transformer block per layer at decode
shapes (T=1, B slots) — norm1, QKV projection, RoPE, cached attention,
output projection, norm2, MLP AND the X-PEFT adapter (bf16 Â/B̂ or the
int8/int4 dequant routes) in a SINGLE ``pallas_call``.

The composed decode path launches attention, the MLP and the fused-adapter
kernel as separate programs per layer; at T=1 every one of those re-reads
the [1, d] residual from HBM. Here the residual stream lives in registers
for the whole block: grid ``(B,)``, one program per slot, and the only HBM
traffic is the weights (read once per slot), the slot's KV rows and the
[1, d] input/output.

The kernel does NOT scatter into the KV cache — it returns the new K/V
rows (already in cache dtype) and ``models/model.py`` scatters them at the
slot's position outside the kernel, so the paged continuous-batching
engine keeps its sentinel-drop writeback semantics unchanged.

``decode_block_row`` is the per-slot math, shared verbatim between the
kernel body (reading Refs) and ``ref.decode_block_ref`` (a python loop
over slots) — interpret-vs-ref parity is therefore bitwise by
construction on all three adapter routes, the same contract the quant
kernels make via ``dequant_block``.

VMEM note: the per-slot blocks load the full [S, KV, hd] cache rows and
the full weight set; at smoke/CI shapes that is KBs, at real decode
shapes (32k context) the S axis must be tiled with an online softmax —
a launch-config evolution, not a semantics change (the row math is the
oracle either way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.schemes import dequant_block

NEG_INF = -2.0e38

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "sqrelu": lambda t: jnp.square(jax.nn.relu(t)),
    "identity": lambda t: t,
}

# adapter route -> the masks_l leaves the kernel streams per slot
ADAPTER_LEAVES = {
    "none": (),
    "bf16": ("a_hat", "b_hat", "ln_scale", "ln_bias"),
    "int8": ("a_q", "a_scale", "b_q", "b_scale", "ln_scale", "ln_bias"),
    "int4": ("a_q", "a_scale", "b_q", "b_scale", "ln_scale", "ln_bias"),
}


def _norm_row(t, scale, bias, kind: str, eps: float = 1e-6):
    """Row twin of models.common.norm_apply (same op order -> bitwise)."""
    t32 = t.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(t32 * t32, axis=-1, keepdims=True)
        y = t32 * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(t.dtype)
    mu = jnp.mean(t32, axis=-1, keepdims=True)
    var = jnp.var(t32, axis=-1, keepdims=True)
    y = (t32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(t.dtype)


def _mm(a, b):
    """a @ b in a's dtype, accumulated in f32 (the MXU's form; Mosaic
    refuses a narrower accumulator)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def decode_block_row(x, pos, n1, n2, attn, mlp, kc, vc, ad, *, norm: str,
                     qkv_bias: bool, use_rope: bool, theta: float,
                     cap: float, mlp_type: str, act_name: str,
                     adapter: str, adapter_act: str):
    """One slot's whole decode block: x [1, d], pos scalar int32,
    kc/vc [S, KV, hd] cache rows, ad the slot's adapter leaves (or {}).

    Returns (y [1, d], k_row [KV, hd], v_row [KV, hd]) with the K/V rows
    already in cache dtype. Pure jnp on plain arrays — the Pallas kernel
    body and the ref oracle both call THIS, so their parity is bitwise.
    """
    dt = x.dtype
    d = x.shape[-1]
    S, KV, hd = kc.shape
    H = attn["wq"].shape[1]
    G = H // KV
    act = _ACTS[act_name]

    # --- norm1 + QKV (mirrors attention.attention at T=1) ----------------
    h = _norm_row(x, n1["scale"], n1.get("bias"), norm)
    q = _mm(h, attn["wq"].reshape(d, H * hd)).reshape(1, H, hd)
    k = _mm(h, attn["wk"].reshape(d, KV * hd)).reshape(1, KV, hd)
    v = _mm(h, attn["wv"].reshape(d, KV * hd)).reshape(1, KV, hd)
    if qkv_bias:
        q = q + attn["bq"].astype(q.dtype)
        k = k + attn["bk"].astype(k.dtype)
        v = v + attn["bv"].astype(v.dtype)
    if use_rope:
        half = hd // 2
        # == models.common.rope_freqs: iota*2.0 is exactly arange(0,hd,2)
        freqs = 1.0 / (theta ** (jax.lax.broadcasted_iota(
            jnp.int32, (1, half), 1).astype(jnp.float32) * 2.0 / hd))
        ang = pos.astype(jnp.float32) * freqs            # [1, hd/2]
        cos = jnp.cos(ang)[:, None, :]
        sin = jnp.sin(ang)[:, None, :]

        def rope(t):
            t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
            return jnp.concatenate(
                [t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                axis=-1).astype(t.dtype)

        q, k = rope(q), rope(k)

    # --- cached attention ------------------------------------------------
    # the composed path writes K/V into the cache and reads them BACK
    # (quantized caches round-trip through cache dtype); mirror that by
    # substituting the round-tripped new row at position `pos`
    k_row = k[0].astype(kc.dtype)                        # [KV, hd]
    v_row = v[0].astype(vc.dtype)
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (S, 1, 1), 0)
    keys = jnp.where(s_iota == pos, k_row.astype(dt)[None], kc.astype(dt))
    vals = jnp.where(s_iota == pos, v_row.astype(dt)[None], vc.astype(dt))
    keys = keys.transpose(1, 0, 2)                       # [KV, S, hd]
    vals = vals.transpose(1, 0, 2)
    qg = q.reshape(1, KV, G, hd).transpose(1, 2, 0, 3)   # [KV, G, 1, hd]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("kgth,ksh->kgts", qg, keys,
                        preferred_element_type=jnp.float32) * scale
    if cap and cap > 0:
        logits = jnp.tanh(logits / cap) * cap
    kp = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, S), 3)
    # causal+valid at T=1 collapse to k_pos <= pos
    logits = jnp.where(kp <= pos, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("kgts,ksh->kgth", w.astype(dt), vals,
                   preferred_element_type=jnp.float32).astype(dt)
    o = o.transpose(2, 0, 1, 3).reshape(1, H * hd)
    x = x + _mm(o, attn["wo"].reshape(H * hd, d))

    # --- norm2 + MLP ------------------------------------------------------
    h = _norm_row(x, n2["scale"], n2.get("bias"), norm)
    if mlp_type == "glu":
        g = _mm(h, mlp["wg"])
        u = _mm(h, mlp["wu"])
        x = x + _mm(act(g) * u, mlp["wd"])
    else:
        m = act(_mm(h, mlp["w1"]) + mlp["b1"].astype(h.dtype))
        x = x + (_mm(m, mlp["w2"]) + mlp["b2"].astype(h.dtype))

    # --- X-PEFT adapter (same op order as the fused-adapter kernels) -----
    if adapter == "bf16":
        hh = jnp.dot(x, ad["a_hat"], preferred_element_type=jnp.float32)
        mu = jnp.mean(hh, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(hh - mu), axis=-1, keepdims=True)
        hh = (hh - mu) * jax.lax.rsqrt(var + 1e-6)
        hh = hh * ad["ln_scale"].astype(jnp.float32) \
            + ad["ln_bias"].astype(jnp.float32)
        if adapter_act == "gelu":
            hh = jax.nn.gelu(hh)
        y = jnp.dot(hh.astype(dt), ad["b_hat"],
                    preferred_element_type=jnp.float32)
        x = x + y.astype(dt)
    elif adapter in ("int8", "int4"):
        x32 = x.astype(jnp.float32)
        a = dequant_block(ad["a_q"], ad["a_scale"], adapter)
        hh = jnp.dot(x32, a, preferred_element_type=jnp.float32)
        mu = jnp.mean(hh, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(hh - mu), axis=-1, keepdims=True)
        hh = (hh - mu) * jax.lax.rsqrt(var + 1e-6)
        hh = hh * ad["ln_scale"].astype(jnp.float32) \
            + ad["ln_bias"].astype(jnp.float32)
        if adapter_act == "gelu":
            hh = jax.nn.gelu(hh)
        y = jnp.dot(hh, dequant_block(ad["b_q"], ad["b_scale"], adapter),
                    preferred_element_type=jnp.float32)
        x = (x32 + y).astype(dt)

    return x, k_row, v_row


def _weight_names(norm: str, qkv_bias: bool, mlp_type: str):
    names = ["n1.scale"]
    if norm == "layernorm":
        names.append("n1.bias")
    names += ["attn.wq", "attn.wk", "attn.wv", "attn.wo"]
    if qkv_bias:
        names += ["attn.bq", "attn.bk", "attn.bv"]
    names.append("n2.scale")
    if norm == "layernorm":
        names.append("n2.bias")
    if mlp_type == "glu":
        names += ["mlp.wg", "mlp.wu", "mlp.wd"]
    else:
        names += ["mlp.w1", "mlp.b1", "mlp.w2", "mlp.b2"]
    return names


def _lookup(block, path):
    o = block
    for p in path.split("."):
        o = o[p]
    return o


def _regroup(names, values):
    """names like 'attn.wq' / 'ad.a_hat' -> {"n1": {...}, "attn": {...}, ...}"""
    out = {}
    for nm, v in zip(names, values):
        grp, leaf = nm.split(".")
        out.setdefault(grp, {})[leaf] = v
    return out


@functools.partial(jax.jit, static_argnames=(
    "norm", "qkv_bias", "use_rope", "theta", "cap", "mlp_type", "act_name",
    "adapter", "adapter_act", "interpret"))
def decode_block_pallas(x, pos, block, k_cache, v_cache, masks_l, *,
                        norm: str, qkv_bias: bool, use_rope: bool,
                        theta: float, cap: float, mlp_type: str,
                        act_name: str, adapter: str, adapter_act: str,
                        interpret: bool = False):
    """x [B, 1, d], pos [B] int32, block the layer's param dict, k/v_cache
    [B, S, KV, hd], masks_l the per-slot adapter leaves (route `adapter`).

    -> (y [B, 1, d], k_rows [B, KV, hd], v_rows [B, KV, hd]).
    """
    B, T, d = x.shape
    assert T == 1, "decode megakernel is a T=1 path"
    S, KV, hd = k_cache.shape[1:]
    cdt = k_cache.dtype

    def full(arr):
        nd = arr.ndim
        return pl.BlockSpec(arr.shape, lambda bi, pos_ref, _n=nd: (0,) * _n)

    def row(arr):
        nd = arr.ndim
        return pl.BlockSpec((1,) + arr.shape[1:],
                            lambda bi, pos_ref, _n=nd:
                            (bi,) + (0,) * (_n - 1))

    # (name, array, spec, leading-slot-dim?) in the kernel's fixed order;
    # pos rides in SMEM (scalar prefetch): a (1,) block of [B] is refused
    ins = [("x", x, row(x), True)]
    for nm in _weight_names(norm, qkv_bias, mlp_type):
        arr = _lookup(block, nm)
        ins.append((nm, arr, full(arr), False))
    ins.append(("kc", k_cache, row(k_cache), True))
    ins.append(("vc", v_cache, row(v_cache), True))
    for nm in ADAPTER_LEAVES[adapter]:
        arr = masks_l[nm]
        if nm.endswith("_scale") and nm != "ln_scale":
            arr = arr.astype(jnp.float32)     # Mosaic cannot load fp16
        if arr.ndim == 2:
            # per-slot vectors gain a unit axis ((1, n) blocks of [B, n]
            # break the (8, 128) tiling rule): ln rows as [B, 1, b],
            # int8 per-row scales as [B, n, 1] (dequant_block takes both)
            arr = arr[..., None] if nm.endswith("_scale") and \
                nm != "ln_scale" else arr[:, None, :]
        ins.append(("ad." + nm, arr, row(arr), True))

    names = tuple(nm for nm, _, _, _ in ins)
    rowset = tuple(is_row for _, _, _, is_row in ins)

    def kernel(pos_ref, *refs):
        o_ref, k_ref, v_ref = refs[-3:]
        vals = {}
        for nm, is_row, ref in zip(names, rowset, refs[:-3]):
            vals[nm] = ref[0] if is_row else ref[...]
        w = _regroup([n for n in names if "." in n],
                     [vals[n] for n in names if "." in n])
        y, k_row, v_row = decode_block_row(
            vals["x"], pos_ref[pl.program_id(0)], w["n1"], w["n2"],
            w["attn"], w["mlp"],
            vals["kc"], vals["vc"], w.get("ad", {}), norm=norm,
            qkv_bias=qkv_bias, use_rope=use_rope, theta=theta, cap=cap,
            mlp_type=mlp_type, act_name=act_name, adapter=adapter,
            adapter_act=adapter_act)
        o_ref[0] = y
        k_ref[0] = k_row
        v_ref[0] = v_row

    def row3(bi, pos_ref):
        return bi, 0, 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[spec for _, _, spec, _ in ins],
            out_specs=[
                pl.BlockSpec((1, 1, d), row3),
                pl.BlockSpec((1, KV, hd), row3),
                pl.BlockSpec((1, KV, hd), row3),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, d), x.dtype),
            jax.ShapeDtypeStruct((B, KV, hd), cdt),
            jax.ShapeDtypeStruct((B, KV, hd), cdt),
        ],
        interpret=interpret,
    )(pos.astype(jnp.int32), *[arr for _, arr, _, _ in ins])
