"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mask_aggregate_ref(bank, idx, w):
    """bank [N, d, b], idx [k] int32, w [k] -> [d, b] fp32.

    The k-sparse hard-mask aggregation: Â = Σ_j w_j · bank[idx_j].
    """
    g = jnp.take(bank, idx, axis=0).astype(jnp.float32)      # [k, d, b]
    return jnp.einsum("k,kdb->db", w.astype(jnp.float32), g)


def fused_adapter_ref(x, a_hat, b_hat, ln_scale, ln_bias, *,
                      activation: str = "gelu", eps: float = 1e-6,
                      use_ln: bool = True):
    """x [T, d], a_hat [d, b], b_hat [b, d] -> [T, d].

    y = x + B̂(act(LN(Â x)))  — the X-PEFT bottleneck with the paper's
    LN-after-down-proj, fp32 internals. ``use_ln=False`` + identity
    activation is the LoRA route: y = x + B̂Âx.
    """
    h = jnp.dot(x.astype(jnp.float32), a_hat.astype(jnp.float32))
    if use_ln:
        mu = h.mean(-1, keepdims=True)
        var = h.var(-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + eps)
        h = h * ln_scale.astype(jnp.float32) + ln_bias.astype(jnp.float32)
    if activation == "gelu":
        h = jax.nn.gelu(h)
    y = jnp.dot(h, b_hat.astype(jnp.float32))
    return (x.astype(jnp.float32) + y).astype(x.dtype)


def ia3_apply_batched_ref(x, s):
    """x [B, T, d]; s [B, d] or [d] (shared) -> x * (1 + s), fp32 compute
    — the oracle twin of kernels/ia3_apply.py. s == 0 is bitwise x."""
    if s.ndim == 2:
        s = s[:, None, :]
    y = x.astype(jnp.float32) * (1.0 + s.astype(jnp.float32))
    return y.astype(x.dtype)


def mask_aggregate_quant_batched_ref(q, scale, idx, w, *, scheme: str):
    """Quantized twin of mask_aggregate_batched_ref, BIT-identical to the
    Pallas kernel: dequant via the shared quant.schemes.dequant_block and
    fp32 accumulation in the kernel's k-minor order (a python loop over the
    static k, not an einsum — einsum reduction order is XLA's choice)."""
    from repro.quant.schemes import dequant_block

    P, k = idx.shape
    rows_q = jnp.take(q, idx.reshape(-1), axis=0)
    rows_q = rows_q.reshape((P, k) + rows_q.shape[1:])
    rows_s = jnp.take(scale, idx.reshape(-1), axis=0)
    rows_s = rows_s.reshape((P, k) + rows_s.shape[1:])
    out = None
    for ki in range(k):
        term = w[:, ki, None, None].astype(jnp.float32) * \
            dequant_block(rows_q[:, ki], rows_s[:, ki], scheme)
        out = term if out is None else out + term
    return out


def fused_adapter_quant_batched_ref(x, a_q, a_scale, b_q, b_scale, ln_scale,
                                    ln_bias, *, scheme: str,
                                    activation: str = "gelu",
                                    eps: float = 1e-6):
    """Quantized twin of fused_adapter_batched_ref, mirroring the Pallas
    kernel's exact op sequence (fp32 x, dequant_block, mean/rsqrt LN) so
    interpret-mode parity is bitwise, not allclose."""
    from repro.quant.schemes import dequant_block

    B = x.shape[0]
    rows = []
    for i in range(B):
        xi = x[i].astype(jnp.float32)
        a = dequant_block(a_q[i], a_scale[i], scheme)
        h = jnp.dot(xi, a, preferred_element_type=jnp.float32)
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + eps)
        h = h * ln_scale[i].astype(jnp.float32) + \
            ln_bias[i].astype(jnp.float32)
        if activation == "gelu":
            h = jax.nn.gelu(h)
        y = jnp.dot(h, dequant_block(b_q[i], b_scale[i], scheme),
                    preferred_element_type=jnp.float32)
        rows.append((xi + y).astype(x.dtype))
    return jnp.stack(rows)


def decode_block_ref(x, pos, block, k_cache, v_cache, masks_l, *, norm: str,
                     qkv_bias: bool, use_rope: bool, theta: float,
                     cap: float, mlp_type: str, act_name: str,
                     adapter: str, adapter_act: str):
    """Oracle twin of the decode megakernel: a python loop over slots
    calling the SAME per-row math (`decode_fused.decode_block_row`) the
    kernel body runs — interpret-vs-ref parity is bitwise by construction
    on all three adapter routes (none/bf16, int8, int4)."""
    from repro.kernels.decode_fused import ADAPTER_LEAVES, decode_block_row

    B = x.shape[0]
    leaves = ADAPTER_LEAVES[adapter]
    ys, krs, vrs = [], [], []
    for i in range(B):
        ad_i = {nm: masks_l[nm][i] for nm in leaves}
        y, kr, vr = decode_block_row(
            x[i], pos[i], block["n1"], block["n2"], block["attn"],
            block["mlp"], k_cache[i], v_cache[i], ad_i, norm=norm,
            qkv_bias=qkv_bias, use_rope=use_rope, theta=theta, cap=cap,
            mlp_type=mlp_type, act_name=act_name, adapter=adapter,
            adapter_act=adapter_act)
        ys.append(y)
        krs.append(kr)
        vrs.append(vr)
    return jnp.stack(ys), jnp.stack(krs), jnp.stack(vrs)


def mask_aggregate_batched_ref(bank, idx, w):
    """bank [N, d, b], idx [P, k], w [P, k] -> [P, d, b] fp32."""
    g = jnp.take(bank, idx, axis=0).astype(jnp.float32)      # [P, k, d, b]
    return jnp.einsum("pk,pkdb->pdb", w.astype(jnp.float32), g)


def fused_adapter_batched_ref(x, a_hat, b_hat, ln_scale, ln_bias, *,
                              activation: str = "gelu", eps: float = 1e-6,
                              use_ln: bool = True):
    """x [B, T, d]; a_hat [B, d, b] or [d, b] (shared across the batch);
    ln_* [B, b] or [b] -> [B, T, d]. Batched twin of fused_adapter_ref;
    ``use_ln=False`` is the LoRA route."""
    x32 = x.astype(jnp.float32)
    a32 = a_hat.astype(jnp.float32)
    b32 = b_hat.astype(jnp.float32)
    if a_hat.ndim == 2:
        h = x32 @ a32
    else:
        h = jnp.einsum("btd,bdc->btc", x32, a32)
    if use_ln:
        mu = h.mean(-1, keepdims=True)
        var = h.var(-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + eps)
        ls = ln_scale.astype(jnp.float32)
        lb = ln_bias.astype(jnp.float32)
        if ls.ndim == 2:
            ls, lb = ls[:, None, :], lb[:, None, :]
        h = h * ls + lb
    if activation == "gelu":
        h = jax.nn.gelu(h)
    if b_hat.ndim == 2:
        y = h @ b32
    else:
        y = jnp.einsum("btc,bcd->btd", h, b32)
    return (x32 + y).astype(x.dtype)


def paged_decode_attention_ref(q, k_new, v_new, k_pool, v_pool, layer,
                               table, lengths):
    """jnp twin of kernels/paged_decode_attention.py: slot b's query
    attends, in float32, the cached positions below ``lengths[b]`` that
    sit on real pages of ``layer`` (sentinel entries are never read),
    then its own new ``k``/``v`` row. q [B,H,hd], rows [B,KV,hd], pools
    [L, n_pages, page, KV*hd], table [B, mp] -> [B, H, hd]."""
    B, H, hd = q.shape
    KV = k_new.shape[1]
    n_pages, page = k_pool.shape[1:3]

    def layer_pages(pool):                                  # [B, S, KV, hd]
        pages = jnp.take(pool[layer], table, axis=0, mode="clip")
        return pages.reshape(B, -1, KV, hd).astype(jnp.float32)

    keys = jnp.concatenate([layer_pages(k_pool),
                            k_new[:, None].astype(jnp.float32)], axis=1)
    vals = jnp.concatenate([layer_pages(v_pool),
                            v_new[:, None].astype(jnp.float32)], axis=1)
    S = keys.shape[1] - 1
    pos = jnp.arange(S)
    live = (pos[None] < lengths[:, None]) & jnp.repeat(table < n_pages,
                                                       page, axis=1)
    live = jnp.concatenate([live, jnp.ones((B, 1), bool)], axis=1)
    qg = q.astype(jnp.float32).reshape(B, KV, H // KV, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, keys) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, vals)
    return out.reshape(B, H, hd).astype(q.dtype)
