"""X-PEFT layer application + the multi-profile mask table.

The framework keeps per-profile trainables as a TABLE (leading dim =
max_profiles) so that hundreds of profiles train simultaneously in one batch:
each example gathers its profile's row, and gradient scatter-add back into the
table happens automatically through the gather transpose (DESIGN.md §3.4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import adapters as A
from repro.core import masks as M


def init_xpeft_state(key, cfg) -> dict:
    """Frozen bank + per-profile trainable table for a ModelConfig."""
    xp = cfg.xpeft
    kb, kp = jax.random.split(key)
    if xp.is_hetero:
        bank = A.init_hetero_bank(kb, cfg.num_layers, xp, cfg.d_model,
                                  cfg.kv_dim, dtype=jnp.dtype(cfg.dtype))
    else:
        bank = A.init_adapter_bank(kb, cfg.num_layers, xp.num_adapters,
                                   cfg.d_model, xp.bottleneck,
                                   dtype=jnp.dtype(cfg.dtype))
    table = init_profile_table(kp, cfg)
    return {"bank": bank, "profiles": table}


# Entry keys each adapter family contributes to a hydrated (aggregated)
# profile entry — the typed generalization of the {a_hat, b_hat, ln_*}
# record. The unified mask still selects over ONE [0, N) index space;
# these are the per-type AGGREGATES the selection produces.
HETERO_ENTRY_KEYS = {
    "bottleneck": ("a_hat", "b_hat", "ln_scale", "ln_bias"),
    "lora": ("lora_a", "lora_b"),
    "ia3": ("ia3_s",),
    "prefix": ("prefix_k", "prefix_v"),
}


def hetero_entry_keys(xp):
    """Ordered entry keys for the families present in ``xp.bank_spec``."""
    out = []
    for t, _, _ in xp.segments():
        for k in HETERO_ENTRY_KEYS[t]:
            if k not in out:
                out.append(k)
    return tuple(out)


def _segment_slice(w, off, cnt):
    """Static slice of the unified-N weight axis for one segment."""
    return w[..., off:off + cnt]


def _safe_inv(wsum):
    """0/0-safe renorm factor: 1/wsum where wsum > 0, else 0.

    Double-where, not ``1/maximum(wsum, eps)``: the derivative of that
    form at wsum = 0 is -1/eps^2, which overflows float32 to inf, and the
    zero cotangent the unselected where-branch receives turns 0·inf into
    NaN — poisoning the whole mask-logit gradient row whenever a training
    example's masks select no prefix slot at some layer."""
    safe = jnp.where(wsum > 0, wsum, 1.0)
    return jnp.where(wsum > 0, 1.0 / safe, 0.0)


def hetero_aggregate_dense_layer(bank_l: dict, w_a_l, w_b_l, xp):
    """One layer's per-type aggregates from DENSE unified-space weights.

    bank_l holds the layer-l slices of the typed bank leaves; w_*_l are
    [..., N] over the unified index space. Per family:

    - bottleneck/lora: Â from the A-mask, B̂ from the B-mask (the paper's
      two-sided selection, per side).
    - ia3: BOTH masks contribute — s = Σ (w_a + w_b)[i] · v[i] (a scale
      delta has no A/B sidedness).
    - prefix: renormalized convex mixture — rows = Σ (w_a+w_b)[i]·rows[i]
      / Σ (w_a+w_b)[i], 0/0 -> zero rows (KV rows are not residual
      deltas; an unnormalized sum would shrink every key toward zero).

    Returns {type: aggregate(s)} for the segments present.
    """
    out = {}
    for t, off, cnt in xp.segments():
        wa = _segment_slice(w_a_l, off, cnt).astype(jnp.float32)
        wb = _segment_slice(w_b_l, off, cnt).astype(jnp.float32)
        if t == "bottleneck":
            a_hat, b_hat = A.aggregate_dense(
                {"bank_a": bank_l["bank_a"], "bank_b": bank_l["bank_b"]},
                wa, wb)
            out["bottleneck"] = (a_hat, b_hat)
        elif t == "lora":
            a_hat, b_hat = A.aggregate_dense(
                {"bank_a": bank_l["lora_a"], "bank_b": bank_l["lora_b"]},
                wa, wb)
            out["lora"] = (a_hat, b_hat)
        elif t == "ia3":
            v = bank_l["ia3_v"].astype(jnp.float32)
            out["ia3"] = jnp.einsum("...n,nd->...d", wa + wb, v)
        elif t == "prefix":
            pk = bank_l["prefix_k"].astype(jnp.float32)
            pv = bank_l["prefix_v"].astype(jnp.float32)
            wab = wa + wb
            num_k = jnp.einsum("...n,npq->...pq", wab, pk)
            num_v = jnp.einsum("...n,npq->...pq", wab, pv)
            wsum = wab.sum(-1)
            inv = _safe_inv(wsum)[..., None, None]
            out["prefix"] = (num_k * inv, num_v * inv)
    return out


def precompute_effective_adapters_hetero(bank: dict, profile_params: dict,
                                         xp):
    """Dense admission-time aggregation for a heterogeneous bank (single
    profile): the typed twin of ``precompute_effective_adapters``.
    Returns the ``hetero_entry_keys(xp)`` dict with [L, ...] leaves."""
    w_a, w_b = profile_mask_weights(profile_params, xp, training=False)
    out = {}
    for t, off, cnt in xp.segments():
        wa = w_a[..., off:off + cnt].astype(jnp.float32)
        wb = w_b[..., off:off + cnt].astype(jnp.float32)
        if t == "bottleneck":
            a32 = bank["bank_a"].astype(jnp.float32)
            b32 = bank["bank_b"].astype(jnp.float32)
            out["a_hat"] = jnp.einsum("ln,lndb->ldb", wa, a32).astype(
                bank["bank_a"].dtype)
            out["b_hat"] = jnp.einsum("ln,lnbd->lbd", wb, b32).astype(
                bank["bank_b"].dtype)
            out["ln_scale"] = profile_params["ln_scale"]
            out["ln_bias"] = profile_params["ln_bias"]
        elif t == "lora":
            a32 = bank["lora_a"].astype(jnp.float32)
            b32 = bank["lora_b"].astype(jnp.float32)
            out["lora_a"] = jnp.einsum("ln,lndb->ldb", wa, a32).astype(
                bank["lora_a"].dtype)
            out["lora_b"] = jnp.einsum("ln,lnbd->lbd", wb, b32).astype(
                bank["lora_b"].dtype)
        elif t == "ia3":
            v32 = bank["ia3_v"].astype(jnp.float32)
            out["ia3_s"] = jnp.einsum("ln,lnd->ld", wa + wb, v32).astype(
                bank["ia3_v"].dtype)
        elif t == "prefix":
            pk = bank["prefix_k"].astype(jnp.float32)
            pv = bank["prefix_v"].astype(jnp.float32)
            wab = wa + wb
            num_k = jnp.einsum("ln,lnpq->lpq", wab, pk)
            num_v = jnp.einsum("ln,lnpq->lpq", wab, pv)
            wsum = wab.sum(-1)
            inv = _safe_inv(wsum)[:, None, None]
            out["prefix_k"] = (num_k * inv).astype(bank["prefix_k"].dtype)
            out["prefix_v"] = (num_v * inv).astype(bank["prefix_v"].dtype)
    return out


def init_profile_table(key, cfg) -> dict:
    xp = cfg.xpeft
    keys = jax.random.split(key, xp.max_profiles)
    return jax.vmap(
        lambda k: M.init_profile_params(k, cfg.num_layers, xp.num_adapters,
                                        xp.bottleneck)
    )(keys)


def gather_profiles(table: dict, profile_ids) -> dict:
    """Select rows of the profile table for a batch: [B, L, N] / [B, L, b]."""
    return jax.tree.map(lambda t: jnp.take(t, profile_ids, axis=0), table)


def profile_mask_weights(profile_params: dict, xp, *, key=None,
                         training: bool = True):
    """Logits -> (w_a, w_b) mask weights, shape [..., L, N]."""
    if key is not None:
        ka, kb = jax.random.split(key)
    else:
        ka = kb = None
    w_a = M.mask_weights(profile_params["mA"], xp, key=ka, training=training)
    w_b = M.mask_weights(profile_params["mB"], xp, key=kb, training=training)
    return w_a, w_b


def apply_xpeft_layer(x, bank_l: dict, w_a_l, w_b_l, ln_scale_l, ln_bias_l,
                      xp):
    """Apply the layer-l X-PEFT adapter to activations x [..., T, d].

    w_*_l: [N] (single profile) or [B, N] (per-example profiles).
    bank_l: {"bank_a": [N, d, b], "bank_b": [N, b, d]} — the slice the
    scan-over-layers feeds in.
    """
    a_hat, b_hat = A.aggregate_dense(bank_l, w_a_l, w_b_l)
    return A.apply_adapter(x, a_hat, b_hat, ln_scale_l, ln_bias_l,
                           activation=xp.adapter_activation)


def apply_xpeft_layer_sparse(x, bank_l: dict, idx_a_l, w_a_l, idx_b_l, w_b_l,
                             ln_scale_l, ln_bias_l, xp):
    """Hard-mask serving path: k-sparse gather aggregation (N/k cheaper)."""
    a_hat, b_hat = A.aggregate_sparse(bank_l, idx_a_l, w_a_l, idx_b_l, w_b_l)
    return A.apply_adapter(x, a_hat, b_hat, ln_scale_l, ln_bias_l,
                           activation=xp.adapter_activation)


def apply_xpeft_layer_hetero(x, bank_l: dict, w_a_l, w_b_l, ln_scale_l,
                             ln_bias_l, xp):
    """Dense heterogeneous layer application (training / soft masks):
    aggregate each typed segment from the unified-space weights and apply
    in the fixed order bottleneck -> LoRA -> IA3. Prefix rows are NOT
    applied here — they are KV rows, threaded into attention by the model
    body (``prefix_rows_dense_layer``)."""
    agg = hetero_aggregate_dense_layer(bank_l, w_a_l, w_b_l, xp)
    if "bottleneck" in agg:
        a_hat, b_hat = agg["bottleneck"]
        x = A.apply_adapter(x, a_hat, b_hat, ln_scale_l, ln_bias_l,
                            activation=xp.adapter_activation)
    if "lora" in agg:
        la, lb = agg["lora"]
        x = A.apply_lora(x, la.astype(x.dtype), lb.astype(x.dtype))
    if "ia3" in agg:
        x = A.apply_ia3(x, agg["ia3"])
    return x


def prefix_rows_dense_layer(bank_l: dict, w_a_l, w_b_l, xp, kv_heads: int,
                            head_dim: int):
    """One layer's per-example prefix KV rows from dense unified-space
    weights: returns ``(pk [B, P, KV, hd], pv, pvalid [B])`` for
    attention's ``extra_kv``, or None when the spec has no prefix
    segment. pvalid is False where the example's masks select no prefix
    slot at this layer — attention then masks the rows out entirely, so
    a no-prefix selection stays bitwise the bare sequence."""
    seg = next(((off, cnt) for t, off, cnt in xp.segments()
                if t == "prefix"), None)
    if seg is None:
        return None
    off, cnt = seg
    wa = w_a_l[..., off:off + cnt].astype(jnp.float32)
    wb = w_b_l[..., off:off + cnt].astype(jnp.float32)
    wab = wa + wb                                       # [B, cnt]
    pk = bank_l["prefix_k"].astype(jnp.float32)         # [cnt, P, kv]
    pv = bank_l["prefix_v"].astype(jnp.float32)
    num_k = jnp.einsum("...n,npq->...pq", wab, pk)
    num_v = jnp.einsum("...n,npq->...pq", wab, pv)
    wsum = wab.sum(-1)                                  # [B]
    inv = _safe_inv(wsum)[..., None, None]
    shape = num_k.shape[:-1] + (kv_heads, head_dim)
    return ((num_k * inv).reshape(shape), (num_v * inv).reshape(shape),
            wsum > 0)


def precompute_effective_adapters(bank: dict, profile_params: dict, xp):
    """Admission-time aggregation (beyond-paper serving optimization).

    Aggregates a profile's masks against the whole bank ONCE, producing dense
    Â/B̂ stacks [L, d, b] / [L, b, d] that the decode hot loop applies with
    two tiny matmuls — removes the per-step aggregation from the critical
    path (DESIGN.md §3, serve cache).
    """
    w_a, w_b = profile_mask_weights(profile_params, xp, training=False)
    a_hat = jnp.einsum("ln,lndb->ldb", w_a, bank["bank_a"].astype(jnp.float32))
    b_hat = jnp.einsum("ln,lnbd->lbd", w_b, bank["bank_b"].astype(jnp.float32))
    return {"a_hat": a_hat.astype(bank["bank_a"].dtype),
            "b_hat": b_hat.astype(bank["bank_b"].dtype),
            "ln_scale": profile_params["ln_scale"],
            "ln_bias": profile_params["ln_bias"]}


def precompute_effective_adapters_dense_batched(bank: dict, w_a, w_b):
    """Dense admission aggregation for a batch of profiles (soft masks).

    w_*: [R, L, N] -> (Â [R, L, d, b], B̂ [R, L, b, d]) in bank dtype. The
    R=1 case is precompute_effective_adapters' einsum with a request axis;
    soft masks are dense by construction so there is no sparse shortcut.
    """
    a_hat = jnp.einsum("rln,lndb->rldb", w_a.astype(jnp.float32),
                       bank["bank_a"].astype(jnp.float32))
    b_hat = jnp.einsum("rln,lnbd->rlbd", w_b.astype(jnp.float32),
                       bank["bank_b"].astype(jnp.float32))
    return (a_hat.astype(bank["bank_a"].dtype),
            b_hat.astype(bank["bank_b"].dtype))


def precompute_effective_adapters_sparse(bank: dict, idx_a, w_a, idx_b, w_b,
                                         xp):
    """k-sparse admission aggregation through the kernel dispatch layer.

    idx_*/w_*: [..., L, k] (a single profile's top-k mask indices, or a
    leading request batch R for multi-request admission). Reads only
    k·L·d·b bank bytes (N/k less than the dense einsum in
    precompute_effective_adapters) by folding the layer axis into the
    bank's N axis and issuing ONE batched aggregation of P = R·L rows.
    """
    from repro.kernels import ops

    L, N = bank["bank_a"].shape[:2]
    d, b = bank["bank_a"].shape[2], bank["bank_a"].shape[3]
    batch = idx_a.shape[:-2]
    # fold layers into the bank slot axis: row (l, n) -> l*N + n
    flat_a = bank["bank_a"].reshape(L * N, d, b)
    flat_b = bank["bank_b"].reshape(L * N, b, d)
    off = (jnp.arange(L, dtype=jnp.int32) * N)[:, None]     # [L, 1]

    def flatten(idx, w):
        k = idx.shape[-1]
        fi = (idx.astype(jnp.int32) + off).reshape(-1, k)
        return fi, w.astype(jnp.float32).reshape(-1, k)

    fia, fwa = flatten(idx_a, w_a)
    fib, fwb = flatten(idx_b, w_b)
    a_hat = ops.mask_aggregate_batched(flat_a, fia, fwa, impl=xp.kernel_impl,
                                       tp_dim=1)
    b_hat = ops.mask_aggregate_batched(flat_b, fib, fwb, impl=xp.kernel_impl,
                                       tp_dim=2)
    dt = bank["bank_a"].dtype
    return (a_hat.reshape(*batch, L, d, b).astype(dt),
            b_hat.reshape(*batch, L, b, d).astype(dt))


def _sparse_fold(leaf, idx, w, xp):
    """Layer-folded k-sparse aggregation of one typed leaf.

    leaf [L, C, p, q]; idx/w [..., L, k] with idx LOCAL to the segment
    (weights of out-of-segment selections already zeroed) -> [..., L, p, q]
    fp32, via ONE batched mask_aggregate launch of R·L rows — the same
    layer-folding trick as precompute_effective_adapters_sparse."""
    from repro.kernels import ops

    L, C, p, q = leaf.shape
    batch = idx.shape[:-2]
    k = idx.shape[-1]
    flat = leaf.reshape(L * C, p, q)
    off = (jnp.arange(L, dtype=jnp.int32) * C)[:, None]
    fi = (idx.astype(jnp.int32) + off).reshape(-1, k)
    fw = w.astype(jnp.float32).reshape(-1, k)
    out = ops.mask_aggregate_batched(flat, fi, fw, impl=xp.kernel_impl)
    return out.reshape(*batch, L, p, q)


def _segment_bucket(idx, w, off, cnt):
    """Fixed-shape bucketing of unified-space indices into one segment:
    indices outside [off, off+cnt) clamp to a valid local row and their
    weights zero out (0 * finite row == exact 0 in the fp32 accumulator),
    so every segment runs at the full static k width — one trace, no
    data-dependent shapes."""
    in_seg = (idx >= off) & (idx < off + cnt)
    local = jnp.clip(idx - off, 0, cnt - 1).astype(jnp.int32)
    return local, w.astype(jnp.float32) * in_seg


def precompute_effective_adapters_sparse_hetero(bank: dict, idx_a, w_a,
                                                idx_b, w_b, xp):
    """k-sparse admission aggregation for a heterogeneous bank.

    idx_*/w_*: [..., L, k] over the UNIFIED index space. Each typed
    segment buckets the k selections with ``_segment_bucket`` and runs the
    SAME batched aggregation kernels at full k width, so a mixed-type
    k-sparse aggregation is exactly the sum of per-type dense
    aggregations (the property the fuzz test pins down). Returns the
    per-type aggregates (no ln affines — the caller attaches the
    profile's own); bottleneck/LoRA sides follow their masks, IA3 and
    prefix take contributions from BOTH masks, prefix renormalized to a
    convex mixture (0/0 -> zero rows)."""
    out = {}
    for t, off, cnt in xp.segments():
        la, wa = _segment_bucket(idx_a, w_a, off, cnt)
        lb, wb = _segment_bucket(idx_b, w_b, off, cnt)
        if t in ("bottleneck", "lora"):
            names = ("bank_a", "bank_b") if t == "bottleneck" else \
                ("lora_a", "lora_b")
            sub = {"bank_a": bank[names[0]], "bank_b": bank[names[1]]}
            a_hat, b_hat = precompute_effective_adapters_sparse(
                sub, la, wa, lb, wb, xp)
            if t == "bottleneck":
                out["a_hat"], out["b_hat"] = a_hat, b_hat
            else:
                out["lora_a"], out["lora_b"] = a_hat, b_hat
        elif t == "ia3":
            v = bank["ia3_v"][..., None]                   # [L, C, d, 1]
            s = _sparse_fold(v, la, wa, xp) + _sparse_fold(v, lb, wb, xp)
            out["ia3_s"] = s[..., 0].astype(bank["ia3_v"].dtype)
        elif t == "prefix":
            num_k = _sparse_fold(bank["prefix_k"], la, wa, xp) + \
                _sparse_fold(bank["prefix_k"], lb, wb, xp)
            num_v = _sparse_fold(bank["prefix_v"], la, wa, xp) + \
                _sparse_fold(bank["prefix_v"], lb, wb, xp)
            wsum = wa.sum(-1) + wb.sum(-1)                 # [..., L]
            inv = _safe_inv(wsum)[..., None, None]
            dt = bank["prefix_k"].dtype
            out["prefix_k"] = (num_k * inv).astype(dt)
            out["prefix_v"] = (num_v * inv).astype(dt)
    return out


def precompute_effective_adapters_sparse_quant(qbank: dict, idx_a, w_a,
                                               idx_b, w_b, xp):
    """k-sparse admission aggregation over a QUANTIZED bank.

    qbank: {"bank_a_q","bank_a_scale","bank_b_q","bank_b_scale"} with
    leading [L, N] dims (quant.schemes.quantize_bank). Same layer-folding
    trick as precompute_effective_adapters_sparse — ONE batched launch of
    P = R·L rows — but HBM reads are the quantized row width and the
    dequant happens in-register (kernels/mask_aggregate_quant.py).
    Returns fp32 (Â [..., L, d, b], B̂ [..., L, b, d]); the engine
    re-quantizes per row for its cache entries / slot buffers.
    """
    from repro.kernels import ops

    L, N = qbank["bank_a_q"].shape[:2]
    d = qbank["bank_a_q"].shape[2]
    b = qbank["bank_b_q"].shape[2]
    batch = idx_a.shape[:-2]
    flat = {k: v.reshape((L * N,) + v.shape[2:]) for k, v in qbank.items()}
    off = (jnp.arange(L, dtype=jnp.int32) * N)[:, None]     # [L, 1]

    def flatten(idx, w):
        k = idx.shape[-1]
        fi = (idx.astype(jnp.int32) + off).reshape(-1, k)
        return fi, w.astype(jnp.float32).reshape(-1, k)

    fia, fwa = flatten(idx_a, w_a)
    fib, fwb = flatten(idx_b, w_b)
    a_hat = ops.mask_aggregate_quant_batched(
        flat["bank_a_q"], flat["bank_a_scale"], fia, fwa,
        scheme=xp.bank_quant, impl=xp.kernel_impl)
    b_hat = ops.mask_aggregate_quant_batched(
        flat["bank_b_q"], flat["bank_b_scale"], fib, fwb,
        scheme=xp.bank_quant, impl=xp.kernel_impl)
    return (a_hat.reshape(*batch, L, d, a_hat.shape[-1]),
            b_hat.reshape(*batch, L, b, b_hat.shape[-1]))


def apply_precomputed_layer(x, eff_l: dict, xp):
    """Apply an admission-time-aggregated adapter slice (per layer)."""
    from repro.kernels import ops

    return ops.fused_adapter(x, eff_l["a_hat"], eff_l["b_hat"],
                             eff_l["ln_scale"], eff_l["ln_bias"],
                             activation=xp.adapter_activation,
                             impl=xp.kernel_impl)
