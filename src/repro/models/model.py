"""The unified LM: scan-over-layers transformer substrate for every assigned
arch (dense / MoE / RWKV6 / Mamba2 / Zamba2-hybrid / encoder) with X-PEFT
adapter-bank hooks on every block's residual stream.

Params are plain dict pytrees; layers are stacked on a leading L axis and run
under jax.lax.scan (compact HLO => compilable 132B-param dry-runs on CPU).
Abstract init for the dry-run comes from jax.eval_shape(init_lm, ...).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import xpeft as XP
from repro.core.adapters import init_adapter_bank, init_hetero_bank
from repro.distributed import ctx
from repro.models import attention as ATT
from repro.models import mamba as MB
from repro.models import mlp as MLP
from repro.models import moe as MOE
from repro.models import rwkv as RK
from repro.models.common import init_norm, norm_apply, dense_init, softcap


# ----------------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------------

def _init_stack(key, n, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


def _init_attn_block(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    block = {
        "attn": ATT.init_attention(k1, cfg, dtype),
        "n1": init_norm(cfg.norm, cfg.d_model),
        "n2": init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.moe:
        block["moe"] = MOE.init_moe(k2, cfg, dtype)
    else:
        block["mlp"] = MLP.init_mlp(k2, cfg, dtype)
    return block


def _init_block(key, cfg, dtype):
    if cfg.block_pattern == "rwkv":
        return {"rwkv": RK.init_rwkv_block(key, cfg, dtype),
                "n1": init_norm("rmsnorm", cfg.d_model),
                "n2": init_norm("rmsnorm", cfg.d_model)}
    if cfg.block_pattern in ("mamba", "zamba"):
        return {"mamba": MB.init_mamba_block(key, cfg, dtype),
                "n1": init_norm("rmsnorm", cfg.d_model)}
    return _init_attn_block(key, cfg, dtype)


def init_lm(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                            cfg.d_model, dtype),
        "blocks": _init_stack(keys[1], cfg.num_layers,
                              lambda k: _init_block(k, cfg, dtype)),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.pos == "learned":
        params["pos_embed"] = dense_init(keys[2], (cfg.max_seq_len, cfg.d_model),
                                         cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[3], (cfg.d_model, cfg.vocab_size),
                                       cfg.d_model, dtype)
    if cfg.block_pattern == "zamba":
        shared_cfg = cfg.with_(attn_type="full")
        params["shared_attn"] = _init_attn_block(keys[4], shared_cfg, dtype)
    if cfg.num_labels:
        params["cls"] = {
            "pool_w": dense_init(keys[5], (cfg.d_model, cfg.d_model),
                                 cfg.d_model, jnp.float32),
            "pool_b": jnp.zeros((cfg.d_model,), jnp.float32),
            "head_w": dense_init(keys[6], (cfg.d_model, cfg.num_labels),
                                 cfg.d_model, jnp.float32),
            "head_b": jnp.zeros((cfg.num_labels,), jnp.float32),
        }
    if cfg.xpeft.enabled:
        if cfg.xpeft.is_hetero:
            params["xpeft_bank"] = init_hetero_bank(
                keys[7], cfg.num_layers, cfg.xpeft, cfg.d_model, cfg.kv_dim,
                dtype)
        else:
            params["xpeft_bank"] = init_adapter_bank(
                keys[7], cfg.num_layers, cfg.xpeft.num_adapters, cfg.d_model,
                cfg.xpeft.bottleneck, dtype)
    return params


def layer_meta(cfg) -> np.ndarray:
    """Static per-layer flags: is_global (gemma3 5:1 local:global)."""
    if cfg.attn_type == "sliding_mix":
        return np.array([(l % cfg.global_every) == cfg.global_every - 1
                         for l in range(cfg.num_layers)])
    return np.ones((cfg.num_layers,), bool)


# ----------------------------------------------------------------------------
# KV / recurrent cache
# ----------------------------------------------------------------------------

def init_cache(cfg, batch: int, seq: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.cache_dtype or cfg.dtype)
    L = cfg.num_layers
    if cfg.block_pattern == "rwkv":
        st = RK.init_rwkv_state(batch, cfg, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), st)
    if cfg.block_pattern == "mamba":
        st = MB.init_mamba_state(batch, cfg, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), st)
    if cfg.block_pattern == "zamba":
        st = MB.init_mamba_state(batch, cfg, dtype)
        cache = jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), st)
        n_inv = cfg.num_layers // cfg.shared_attn_every
        cache = dict(cache)
        cache["attn_k"] = jnp.zeros(
            (n_inv, batch, seq, cfg.num_kv_heads, cfg.head_dim), dtype)
        cache["attn_v"] = jnp.zeros_like(cache["attn_k"])
        return cache
    return {
        "k": jnp.zeros((L, batch, seq, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((L, batch, seq, cfg.num_kv_heads, cfg.head_dim), dtype),
    }


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------

def _xpeft_apply(x, bank_l, masks_l, cfg):
    if masks_l is None or not cfg.xpeft.enabled:
        return x
    if "a_q" in masks_l:
        # QUANTIZED aggregated adapters (bank_quant serving): per-example
        # int8 / packed-int4 Â/B̂ + fp16 scales, dequantized in-register by
        # the dequant-fused kernel — the record never widens in HBM.
        from repro.kernels import ops
        return ops.fused_adapter_quant(
            x, masks_l["a_q"], masks_l["a_scale"],
            masks_l["b_q"], masks_l["b_scale"],
            masks_l["ln_scale"], masks_l["ln_bias"],
            scheme=cfg.xpeft.bank_quant,
            activation=cfg.xpeft.adapter_activation,
            impl=cfg.xpeft.kernel_impl)
    if "a_hat" in masks_l or "lora_a" in masks_l or "ia3_s" in masks_l:
        # admission-time aggregated adapters (serving fast path): per-example
        # Â [B,d,b] / B̂ [B,b,d] already contracted against the bank. Routed
        # through the kernel dispatch layer — on TPU one batched Pallas
        # launch keeps the [T,b] intermediate in VMEM (no HBM round-trip).
        # Heterogeneous entries compose in the fixed per-layer order
        # bottleneck -> LoRA -> IA3 (prefix rows live in the KV cache, not
        # here); a type-pure entry carries only a_hat/b_hat and this is
        # exactly the historical single fused_adapter call.
        from repro.kernels import ops
        if "a_hat" in masks_l:
            x = ops.fused_adapter(x, masks_l["a_hat"], masks_l["b_hat"],
                                  masks_l["ln_scale"], masks_l["ln_bias"],
                                  activation=cfg.xpeft.adapter_activation,
                                  impl=cfg.xpeft.kernel_impl)
        if "lora_a" in masks_l:
            x = ops.lora_adapter(x, masks_l["lora_a"], masks_l["lora_b"],
                                 impl=cfg.xpeft.kernel_impl)
        if "ia3_s" in masks_l:
            x = ops.ia3_apply(x, masks_l["ia3_s"],
                              impl=cfg.xpeft.kernel_impl)
        return x
    if "w_a" not in masks_l:
        # serving entries with no residual-path leaves (e.g. a prefix-only
        # bank_spec: prefix_skip rides to attention, nothing applies here)
        return x
    if "idx_a" in masks_l:
        # k-sparse hard-mask aggregation: gather only the k selected
        # adapters (N/k cheaper than the dense contraction; the jnp twin of
        # kernels/mask_aggregate.py)
        return XP.apply_xpeft_layer_sparse(
            x, bank_l, masks_l["idx_a"], masks_l["w_a"],
            masks_l["idx_b"], masks_l["w_b"],
            masks_l["ln_scale"][..., None, :],
            masks_l["ln_bias"][..., None, :], cfg.xpeft)
    if cfg.xpeft.is_hetero:
        # dense unified-space weights over a typed bank (training / soft
        # masks): per-segment aggregation + bottleneck -> LoRA -> IA3
        # composition; prefix KV rows were threaded into attention by the
        # scan body before this point.
        return XP.apply_xpeft_layer_hetero(
            x, bank_l, masks_l["w_a"], masks_l["w_b"],
            masks_l["ln_scale"][..., None, :],
            masks_l["ln_bias"][..., None, :], cfg.xpeft)
    return XP.apply_xpeft_layer(x, bank_l, masks_l["w_a"], masks_l["w_b"],
                                masks_l["ln_scale"][..., None, :],
                                masks_l["ln_bias"][..., None, :], cfg.xpeft)


def _decode_fused_route(cfg, masks, use_cache: bool, Tt: int):
    """Static eligibility of the decode megakernel: returns the adapter
    route ("none" | "bf16" | "int8" | "int4") or None for the composed
    path. Only the T=1 cached full-attention decode step qualifies; the
    on-the-fly mask routes (w_a / idx_a) keep the composed path — the
    megakernel fuses admission-time aggregated records only."""
    if not (cfg.decode_fused and use_cache and Tt == 1
            and cfg.block_pattern == "attn" and not cfg.moe
            and cfg.attn_type == "full" and cfg.causal):
        return None
    if masks is None or not cfg.xpeft.enabled:
        return "none"
    if any(key in masks for key in ("lora_a", "lora_b", "ia3_s",
                                    "prefix_skip")):
        return None  # heterogeneous entries take the composed per-type path
    if "a_q" in masks:
        return cfg.xpeft.bank_quant \
            if cfg.xpeft.bank_quant in ("int8", "int4") else None
    if "a_hat" in masks:
        return "bf16"
    return None


def _decode_fused_apply(block, x, masks_l, cfg, *, positions, cache_l,
                        cache_pos, route):
    """Megakernel step: one program for norm/attn/MLP/adapter, then the
    K/V row scatter OUTSIDE the kernel (same semantics as attention.py's
    cache update, so paged sentinel-drop writeback is unchanged)."""
    from repro.kernels import ops
    B = x.shape[0]
    y, k_rows, v_rows = ops.decode_block_fused(
        x, positions[:, 0], block, cache_l["k"], cache_l["v"], masks_l,
        norm=cfg.norm, qkv_bias=cfg.qkv_bias, use_rope=cfg.pos == "rope",
        theta=cfg.rope_theta, cap=cfg.logit_softcap, mlp_type=cfg.mlp_type,
        act_name=cfg.act, adapter=route,
        adapter_act=cfg.xpeft.adapter_activation,
        impl=cfg.xpeft.kernel_impl)
    with jax.named_scope("kv_cache_update"):
        if jnp.ndim(cache_pos) == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache_l["k"], k_rows[:, None], cache_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache_l["v"], v_rows[:, None], cache_pos, axis=1)
        else:
            ck = cache_l["k"].at[jnp.arange(B), cache_pos].set(
                k_rows, mode="drop")
            cv = cache_l["v"].at[jnp.arange(B), cache_pos].set(
                v_rows, mode="drop")
    return y, {"k": ck, "v": cv}


def _attn_block_apply(block, x, cfg, *, positions, cache_l, cache_pos,
                      is_global, extra_kv=None, front_skip=None, paged=None):
    h = norm_apply(x, block["n1"], cfg.norm)
    h, new_cache = ATT.attention(block["attn"], h, positions=positions,
                                 cfg=cfg, cache=cache_l, cache_pos=cache_pos,
                                 is_global=is_global, extra_kv=extra_kv,
                                 front_skip=front_skip, paged=paged)
    x = x + h
    h = norm_apply(x, block["n2"], cfg.norm)
    if cfg.moe:
        h, aux = MOE.moe_apply(block["moe"], h, cfg)
    else:
        h, aux = MLP.mlp_apply(block["mlp"], h, cfg), jnp.float32(0)
    x = x + h
    return x, new_cache, aux


def _make_body(cfg, positions, cache_pos, use_cache, fused_route=None,
               paged=None, paged_masks=None):
    """Scan body over stacked layers for uniform-block archs. With
    ``paged`` (an ``ATT.PagedKV``) the scanned cache leaf is the layer
    index: attention reads the layer's KV pages in place and emits its new
    K/V rows, and the layer's slot records are read straight from their
    ``[B, L, ...]`` view ``paged_masks`` (no per-step layer-major copy of
    them)."""

    def body(x, xs):
        block, bank_l, masks_l, is_global, cache_l = xs
        layer_paged = None
        if paged is not None:
            layer_paged, cache_l = (paged, cache_l), None
            if paged_masks is not None:
                masks_l = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, layer_paged[1], axis=1, keepdims=False),
                    paged_masks)
        elif not use_cache:
            cache_l = None
        if fused_route is not None:
            # decode megakernel: attention/MLP AND the adapter in one
            # program per layer (adapter already applied — skip
            # _xpeft_apply below)
            x, new_cache = _decode_fused_apply(
                block, x, masks_l, cfg, positions=positions,
                cache_l=cache_l, cache_pos=cache_pos, route=fused_route)
            x = ctx.hint(x, "batch", "seq", "embed")
            return x, (new_cache, jnp.float32(0))
        if cfg.block_pattern == "rwkv":
            x, new_cache = RK.rwkv_block(
                block["rwkv"], x, cfg,
                {"n1": block["n1"], "n2": block["n2"]}, cache_l)
            aux = jnp.float32(0)
        elif cfg.block_pattern in ("mamba", "zamba"):
            x, new_cache = MB.mamba_block(block["mamba"], x, cfg,
                                          {"n1": block["n1"]}, cache_l)
            aux = jnp.float32(0)
        else:
            extra_kv = None
            front_skip = None
            if (masks_l is not None and cfg.xpeft.enabled
                    and cfg.xpeft.is_hetero and not use_cache
                    and "w_a" in masks_l):
                # dense training path over a prefix-bearing bank: this
                # layer's per-example prefix KV rows ride into attention
                # as un-rotated front rows (None when the spec has no
                # prefix segment). The cached/serving path instead
                # hydrates prefix rows into the KV cache at admission.
                extra_kv = XP.prefix_rows_dense_layer(
                    bank_l, masks_l["w_a"], masks_l["w_b"], cfg.xpeft,
                    cfg.num_kv_heads, cfg.head_dim)
            if (use_cache and masks_l is not None
                    and "prefix_skip" in masks_l):
                # serving over hydrated prefix KV rows: per-example,
                # per-layer gate — a layer whose masks selected no prefix
                # slot holds zero rows at [0, P) and must not attend them
                # (matches the training path's extra_kv pvalid gating)
                front_skip = masks_l["prefix_skip"]
            x, new_cache, aux = _attn_block_apply(
                block, x, cfg, positions=positions, cache_l=cache_l,
                cache_pos=cache_pos, is_global=is_global, extra_kv=extra_kv,
                front_skip=front_skip, paged=layer_paged)
        with jax.named_scope("adapter"):
            x = _xpeft_apply(x, bank_l, masks_l, cfg)
        # re-pin the residual stream each layer (Megatron-SP: under
        # act_rules {"seq": "model"} the scan carry — and therefore the
        # remat-saved layer inputs — stay sequence-sharded over TP)
        x = ctx.hint(x, "batch", "seq", "embed")
        if new_cache is None:
            new_cache = jnp.float32(0)
        return x, (new_cache, aux)

    return body


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def paged_decode_route(cfg, profile_masks, T: int, data) -> bool:
    """Whether a decode step over the paged cache's leaves ``data`` can
    attend the pages in place (``forward`` with an ``ATT.PagedKV``): T=1
    full causal attention with no softcap, no decode megakernel, no
    hydrated prefix rows to gate (``prefix_skip``), a K pool
    ``[L, n_pages, page, KV*hd]`` held at the compute dtype, and a kernel
    that can serve that pool here. Everything else decodes through the
    dense view of the pages."""
    if not (T == 1 and cfg.block_pattern == "attn"
            and cfg.attn_type == "full" and cfg.causal
            and not cfg.logit_softcap and not cfg.decode_fused
            and not (profile_masks is not None
                     and "prefix_skip" in profile_masks)):
        return False
    from repro.kernels import ops
    pool = data["k"]
    return (jnp.dtype(pool.dtype) == jnp.dtype(cfg.dtype)
            and ops.paged_decode_supported(cfg.xpeft.kernel_impl,
                                           pool.shape[2], pool.shape[3],
                                           pool.dtype))


def forward(params, tokens, cfg, *, prefix_embeds=None, profile_masks=None,
            cache=None, cache_pos=0, positions=None):
    """tokens [B,T] -> (hidden [B,T',d], new_cache, aux_loss).

    profile_masks: {"w_a","w_b": [B,L,N], "ln_scale","ln_bias": [B,L,b]}
    (per-example hydrated mask weights), or None.
    cache: stacked cache pytree from init_cache; cache_pos: write offset.
    An ``ATT.PagedKV`` cache (where ``paged_decode_route`` holds) is read
    in place, and new_cache is then the step's K/V rows
    ``{"k","v": [L, B, KV, hd]}`` for the caller to write to the pools.
    """
    B, T = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    Tt = x.shape[1]
    if positions is None:
        if jnp.ndim(cache_pos) == 0:
            positions = cache_pos + jnp.arange(Tt, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (B, Tt))
        else:  # per-slot decode positions
            positions = cache_pos[:, None] + jnp.arange(Tt, dtype=jnp.int32)
        if (cache is None and profile_masks is not None
                and cfg.xpeft.enabled and cfg.xpeft.has_prefix
                and "w_a" in profile_masks):
            # prefix-bearing dense training path: prefix KV rows occupy
            # positions [0, P), so the prompt's RoPE phase starts at P —
            # matching serving, where prefill writes the prompt at
            # cache_pos = P behind the hydrated prefix rows. Per-example:
            # a profile whose masks never touch the prefix segment keeps
            # bare positions (RoPE is only *relatively* shift-invariant,
            # so a blanket offset would break bitwise zero-mask == bare).
            wsum = jnp.zeros((B,), jnp.float32)
            for typ, off, cnt in cfg.xpeft.segments():
                if typ != "prefix":
                    continue
                seg_a = profile_masks["w_a"][:, :, off:off + cnt]
                seg_b = profile_masks["w_b"][:, :, off:off + cnt]
                wsum = wsum + seg_a.sum((1, 2)) + seg_b.sum((1, 2))
            offs = jnp.where(wsum > 0, jnp.int32(cfg.xpeft.prefix_tokens), 0)
            positions = positions + offs[:, None]
    if cfg.pos == "learned":
        if jnp.ndim(cache_pos) == 0:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], cache_pos, Tt, axis=0)[None]
        else:
            x = x + jnp.take(params["pos_embed"], positions, axis=0)
    x = ctx.hint(x, "batch", "seq", "embed")

    use_cache = cache is not None
    bank = params.get("xpeft_bank")
    if bank is None:
        bank = jnp.zeros((cfg.num_layers,), jnp.float32)  # dummy scanned leaf
    meta = jnp.asarray(layer_meta(cfg))

    if isinstance(cache, ATT.PagedKV):
        # the caller checked paged_decode_route; the slot records stay in
        # their [B, L, ...] layout and each layer reads its own slice
        assert Tt == 1, "paged decode attends one new token per slot"
        body = _remat(_make_body(cfg, positions, cache_pos, True,
                                 paged=cache, paged_masks=profile_masks),
                      cfg)
        xs = (params["blocks"], bank, None, meta,
              jnp.arange(cfg.num_layers, dtype=jnp.int32))
        x, (rows, auxs) = jax.lax.scan(body, x, xs)
        x = norm_apply(x, params["final_norm"], cfg.norm)
        return x, rows, jnp.mean(auxs)

    masks = None
    if profile_masks is not None:
        # [B, L, ...] -> [L, B, ...] for scan
        masks = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), profile_masks)

    if cfg.block_pattern == "zamba":
        return _forward_zamba(params, x, cfg, positions, cache, cache_pos,
                              bank, masks, meta)

    fused_route = _decode_fused_route(cfg, masks, use_cache, Tt)
    body = _remat(_make_body(cfg, positions, cache_pos, use_cache,
                             fused_route), cfg)
    dummy_cache = cache if use_cache else jnp.zeros((cfg.num_layers,), jnp.float32)
    xs = (params["blocks"], bank, masks, meta, dummy_cache)
    x, (new_cache, auxs) = jax.lax.scan(body, x, xs)
    x = norm_apply(x, params["final_norm"], cfg.norm)
    return x, (new_cache if use_cache else None), jnp.mean(auxs)


def _forward_zamba(params, x, cfg, positions, cache, cache_pos, bank, masks,
                   meta):
    """Zamba2: groups of mamba layers with a SHARED attention block between.

    38 layers, shared_attn_every=6 -> 6 shared-block invocations, each with
    its own KV cache slice (cache["attn_k"][g]).
    """
    use_cache = cache is not None
    E = cfg.shared_attn_every
    n_inv = cfg.num_layers // E
    body = _remat(_make_body(cfg, positions, cache_pos, use_cache), cfg)

    def slice_tree(tree, lo, n):
        return jax.tree.map(lambda a: a[lo:lo + n], tree)

    mamba_cache = None
    if use_cache:
        mamba_cache = {k: v for k, v in cache.items()
                       if k not in ("attn_k", "attn_v")}
    new_mamba, new_ak, new_av, auxs = [], [], [], []
    shared_cfg = cfg.with_(attn_type="full", moe=False)
    bounds = [(g * E, E) for g in range(n_inv)]
    rem = cfg.num_layers - n_inv * E
    if rem:
        bounds.append((n_inv * E, rem))
    for gi, (lo, n) in enumerate(bounds):
        xs = (slice_tree(params["blocks"], lo, n), slice_tree(bank, lo, n),
              slice_tree(masks, lo, n) if masks is not None else None,
              meta[lo:lo + n],
              slice_tree(mamba_cache, lo, n) if use_cache else
              jnp.zeros((n,), jnp.float32))
        x, (nc, aux) = jax.lax.scan(body, x, xs)
        if use_cache:
            new_mamba.append(nc)
        auxs.append(aux)
        if gi < n_inv:
            attn_cache_l = None
            if use_cache:
                attn_cache_l = {"k": cache["attn_k"][gi],
                                "v": cache["attn_v"][gi]}
            x, ac, _ = _attn_block_apply(
                params["shared_attn"], x, shared_cfg, positions=positions,
                cache_l=attn_cache_l, cache_pos=cache_pos, is_global=True)
            if use_cache:
                new_ak.append(ac["k"])
                new_av.append(ac["v"])
    new_cache = None
    if use_cache:
        new_cache = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *new_mamba)
        new_cache["attn_k"] = jnp.stack(new_ak)
        new_cache["attn_v"] = jnp.stack(new_av)
    x = norm_apply(x, params["final_norm"], cfg.norm)
    return x, new_cache, jnp.mean(jnp.concatenate(
        [jnp.atleast_1d(a) for a in auxs]))


# ----------------------------------------------------------------------------
# Heads
# ----------------------------------------------------------------------------

def lm_logits(params, hidden, cfg):
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", hidden, params["embed"])
    else:
        logits = jnp.einsum("btd,dv->btv", hidden, params["lm_head"])
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return ctx.hint(logits, "batch", "seq", "vocab")


def cls_logits(params, hidden, cfg, head_override=None):
    """Encoder classification: pooled [CLS] -> labels. head_override lets
    per-profile heads (X-PEFT trainables) replace the shared head."""
    pooled = jnp.tanh(hidden[:, 0, :].astype(jnp.float32)
                      @ params["cls"]["pool_w"] + params["cls"]["pool_b"])
    head = head_override if head_override is not None else params["cls"]
    if head is params["cls"]:
        return pooled @ head["head_w"] + head["head_b"]
    # per-example heads: [B, d, C] / [B, C]
    return jnp.einsum("bd,bdc->bc", pooled, head["head_w"]) + head["head_b"]
