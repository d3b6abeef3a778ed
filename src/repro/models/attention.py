"""Attention: MHA/GQA/MQA with RoPE, sliding-window/global mix, KV cache.

Long sequences use a chunked online-softmax (flash-style) path in pure JAX —
lax.scan over query chunks with an inner scan over key chunks — so [T,S]
logits never materialize. Causal chunk pairs above the diagonal are computed
masked (rectangle); the §Perf log treats removing that waste as a hillclimb.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.kernels import ops
from repro.models.common import apply_rope, dense_init, softcap

NEG_INF = -2.0e38


class PagedKV(NamedTuple):
    """Read-only view of the continuous engine's stacked page pools for
    T=1 decode: each layer attends its slots' pages in place
    (``ops.paged_decode_attention``; under the ``ref`` impl, a gather of
    the layer's pages and the dense cached path) and returns its new K/V
    rows instead of an updated cache."""
    k: jax.Array          # [L, n_pages, page, KV*hd]
    v: jax.Array
    table: jax.Array      # [B, max_pages] int32, n_pages = sentinel
    lengths: jax.Array    # [B] cached positions each slot attends


def init_attention(key, cfg, dtype) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), d, dtype),
        "wk": dense_init(ks[1], (d, KV, hd), d, dtype),
        "wv": dense_init(ks[2], (d, KV, hd), d, dtype),
        "wo": dense_init(ks[3], (H, hd, d), H * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), jnp.float32)
        p["bk"] = jnp.zeros((KV, hd), jnp.float32)
        p["bv"] = jnp.zeros((KV, hd), jnp.float32)
    return p


def _mask(q_pos, k_pos, *, causal, window, kv_valid, front_skip=None,
          k_idx=None):
    """q_pos [B,Tq], k_pos [S] or [B,S], kv_valid [B] -> bool [B,Tq,S].

    ``front_skip [B]`` masks the first ``front_skip[b]`` key BUFFER slots —
    per-example gating of learned prefix KV rows concatenated at the
    front (an example whose profile selects no prefix slot must attend
    EXACTLY the bare sequence, not P zero rows diluting the softmax).
    When k_pos is per-example [B,S] (prefix path: positions differ per
    example), ``k_idx [S]`` carries the buffer-slot index that kv_valid
    and front_skip gate on; positional masks use k_pos."""
    qp = q_pos[:, :, None]
    kp = k_pos[None, None, :] if k_pos.ndim == 1 else k_pos[:, None, :]
    ki = kp if k_idx is None else k_idx[None, None, :]
    m = ki < jnp.reshape(kv_valid, (-1, 1, 1))
    if front_skip is not None:
        m = m & (ki >= jnp.reshape(front_skip, (-1, 1, 1)))
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        m = m & (qp - kp < window)
    return m


def _sdpa_dense(q, k, v, mask, scale, cap):
    """q [B,KV,G,Tq,hd], k/v [B,KV,S,hd], mask [B,Tq,S]."""
    logits = jnp.einsum("bkgth,bksh->bkgts", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cap)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bksh->bkgth", w.astype(v.dtype), v)
    return out


def _sdpa_chunked(q, k, v, q_pos, k_pos, *, causal, window, kv_valid, scale,
                  cap, q_chunk, k_chunk):
    """Flash-style online softmax over key chunks, scanned over query chunks."""
    B, KV, G, Tq, hd = q.shape
    S = k.shape[2]
    nq, nk = Tq // q_chunk, S // k_chunk
    dv = v.shape[-1]

    qs = q.reshape(B, KV, G, nq, q_chunk, hd).transpose(3, 0, 1, 2, 4, 5)
    # NOTE: re-pinning q-seq CP on the chunk dim here was measured WORSE
    # (dbrx cp_qseq 40.9 -> 45.4s; §Perf it.7 refuted) — GSPMD handles the
    # [T]->[nq,qc] reshape better than an explicit re-constraint.
    qps = q_pos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
    ks = k.reshape(B, KV, nk, k_chunk, hd).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, KV, nk, k_chunk, dv).transpose(2, 0, 1, 3, 4)
    ks = ctx.hint(ks, None, "batch", "kv_heads", "kv_seq", None)
    vs = ctx.hint(vs, None, "batch", "kv_heads", "kv_seq", None)
    kps = k_pos.reshape(nk, k_chunk)

    def q_step(_, qc):
        qi, qpi = qc

        def k_step(carry, kc):
            m_run, l_run, acc = carry
            ki, vi, kpi = kc
            logits = jnp.einsum("bkgth,bksh->bkgts", qi, ki,
                                preferred_element_type=jnp.float32) * scale
            logits = softcap(logits, cap)
            msk = _mask(qpi, kpi, causal=causal, window=window,
                        kv_valid=kv_valid)
            logits = jnp.where(msk[:, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgts,bksh->bkgth", p.astype(vi.dtype), vi).astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, q_chunk), jnp.float32),
                jnp.zeros((B, KV, G, q_chunk, dv), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(k_step, init, (ks, vs, kps))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return None, out.astype(v.dtype)

    _, outs = jax.lax.scan(q_step, None, (qs, qps))
    # outs: [nq, B, KV, G, q_chunk, hd] -> [B, KV, G, Tq, hd]
    return outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, KV, G, Tq, dv)


@jax.named_scope("kv_cache_update")
def _cache_update(cache, k, v, cache_pos):
    """Write the new positions' K/V rows into the dense cache at
    ``cache_pos`` (a scalar offset, or one per row)."""
    if jnp.ndim(cache_pos) == 0:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), cache_pos, axis=1)
        return ck, cv
    # per-slot positions (continuous batching / speculative verify):
    # scatter T consecutive steps at each slot's own offset; writes past S
    # fall off the end and are dropped (the engine masks those slots via
    # kv_valid and never commits their tokens)
    B, T = k.shape[:2]
    idx = cache_pos[:, None] + jnp.arange(T)
    ck = cache["k"].at[jnp.arange(B)[:, None], idx].set(
        k.astype(cache["k"].dtype), mode="drop")
    cv = cache["v"].at[jnp.arange(B)[:, None], idx].set(
        v.astype(cache["v"].dtype), mode="drop")
    return ck, cv


def attention(params, x, *, positions, cfg, cache=None, cache_pos=None,
              is_global=True, q_chunk=512, k_chunk=1024, extra_kv=None,
              front_skip=None, paged=None):
    """x [B,T,d] -> (y [B,T,d], new_cache).

    cache: {"k","v": [B, S, KV, hd]} functional KV cache; cache_pos: scalar
    write offset. Without a cache, keys=queries (self-attention).

    paged: ``(PagedKV, layer)`` — T=1 decode straight from the page pools
    (full causal attention only); new_cache is then this token's rows
    ``{"k","v": [B, KV, hd]}``, written to the pools after the layer scan.

    front_skip: optional [B] int32 — mask the first ``front_skip[b]`` KEY
    buffer slots in the cached path (serving over hydrated prefix KV rows:
    a layer whose profile selected no prefix slot holds zero rows at
    [0, P) that must not dilute the softmax). The no-cache prefix path
    sets this internally from ``extra_kv``'s pvalid.

    extra_kv: optional ``(pk [B,P,KV,hd], pv [B,P,KV,hd], pvalid [B])`` —
    learned PREFIX KV rows (stored post-RoPE; concatenated un-rotated at
    the front of the no-cache key/value sequence). The caller passes
    ``positions`` already offset by P so prefix rows sit at positions
    [0, P) and the prompt starts at P; ``pvalid=False`` examples mask the
    prefix region out entirely (bitwise the bare sequence). Serving never
    uses this — the engine hydrates prefix rows straight into the KV
    cache before prefill, so cached decode stays one compiled program.
    """
    B, T, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # TP arbitration: head-shard Q only when the KV heads shard too —
    # otherwise Q-heads and KV-seq would claim the model axis differently
    # and GSPMD bounces activations every layer (dbrx/qwen3-moe: 7.5x
    # collective blowup, see EXPERIMENTS.md §Perf it.4). With
    # non-divisible KV, context-parallel K/V carries the TP instead.
    msize = ctx.axis_size("model")
    if msize <= 1 or KV % msize == 0:
        q = ctx.hint(q, "batch", None, "heads", None)
    else:
        # non-divisible KV: q-seq CP if the launcher enabled the "q_seq"
        # rule (no-op otherwise; K/V-seq CP carries the TP by default)
        q = ctx.hint(q, "batch", "q_seq", None, None)

    rows = None
    if paged is not None:
        pkv, layer = paged
        rows = {"k": k[:, 0], "v": v[:, 0]}
        if ops.resolve_impl(cfg.xpeft.kernel_impl) != "ref":
            with jax.named_scope("kv_dense_view"):
                out = ops.paged_decode_attention(
                    q[:, 0], k[:, 0], v[:, 0], pkv.k, pkv.v, layer,
                    pkv.table, pkv.lengths, impl=cfg.xpeft.kernel_impl)
            y = jnp.einsum("bthk,hkd->btd", out[:, None], params["wo"])
            return y, rows
        # the jnp route: gather this layer's pages to the dense layout and
        # attend through the cached path below, so the tokens stay bitwise
        # those of the dense cache (junk pages sit past kv_valid)
        with jax.named_scope("kv_dense_view"):
            cache = {n: jnp.take(pool[layer], pkv.table, axis=0, mode="clip")
                     .reshape(B, -1, KV, hd)
                     for n, pool in (("k", pkv.k), ("v", pkv.v))}
        cache_pos = pkv.lengths

    k_idx = None
    if cache is not None:
        ck, cv = _cache_update(cache, k, v, cache_pos)
        new_cache = {"k": ck, "v": cv}
        # quantized caches (e.g. f8) cast back to compute dtype on read
        keys, vals = ck.astype(k.dtype), cv.astype(v.dtype)
        S = ck.shape[1]
        kv_valid = jnp.broadcast_to(cache_pos + T, (B,))
        k_pos = jnp.arange(S)
    elif extra_kv is not None:
        pk, pv, pvalid = extra_kv
        P = pk.shape[1]
        new_cache = None
        keys = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        vals = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        S = P + T
        kv_valid = jnp.full((B,), P + T, jnp.int32)
        # per-example key positions: prefix rows at [0, P), self keys at
        # the example's own (possibly unshifted) query positions
        k_pos = jnp.concatenate([
            jnp.broadcast_to(
                jnp.arange(P, dtype=positions.dtype)[None], (B, P)),
            positions], axis=1)
        k_idx = jnp.arange(P + T)
        front_skip = jnp.where(pvalid, 0, P).astype(jnp.int32)
    else:
        new_cache = None
        keys, vals = k, v
        S = T
        kv_valid = jnp.full((B,), T, jnp.int32)
        k_pos = jnp.arange(T)

    keys = keys.transpose(0, 2, 1, 3)   # [B, KV, S, hd]
    vals = vals.transpose(0, 2, 1, 3)
    # TP arbitration: kv_heads claims the model axis when divisible, else
    # the sequence dim does (context-parallel attention; ctx rule "kv_seq")
    keys = ctx.hint(keys, "batch", "kv_heads", "kv_seq", None)
    vals = ctx.hint(vals, "batch", "kv_heads", "kv_seq", None)
    qg = q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,hd]

    window = None
    if cfg.attn_type == "sliding_mix":
        # traced per-layer flag: global layers get an "infinite" window
        window = jnp.where(is_global, jnp.int32(2**30),
                           jnp.int32(cfg.sliding_window))

    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    use_chunked = (T > q_chunk) and (T % q_chunk == 0) and (S % k_chunk == 0)
    if use_chunked and front_skip is None:
        out = _sdpa_chunked(qg, keys, vals, positions, k_pos,
                            causal=cfg.causal, window=window,
                            kv_valid=kv_valid, scale=scale,
                            cap=cfg.logit_softcap,
                            q_chunk=q_chunk, k_chunk=k_chunk)
    else:
        msk = _mask(positions, k_pos, causal=cfg.causal, window=window,
                    kv_valid=kv_valid, front_skip=front_skip, k_idx=k_idx)
        out = _sdpa_dense(qg, keys, vals, msk, scale, cfg.logit_softcap)

    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, (new_cache if rows is None else rows)
