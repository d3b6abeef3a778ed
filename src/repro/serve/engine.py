"""Serving engine orchestrator: scheduler + slot state + profile cache.

The engine wires four layers (DESIGN.md §2 Serve, restructured):

- `serve/scheduler.py` — request queue + admission policy: FIFO waves,
  bucket-grouped so same-length prompts share one prefill launch.
- `serve/profile_cache.py` — byte-capacity LRU of admission-time
  aggregated Â/B̂ keyed by profile_id: a hit admits with ZERO bank reads
  (the dominant case when R requests share P ≪ R profiles).
- `serve/slots.py` — device-resident decode state (`last_tok`/`lengths`/
  `active`) advanced by ONE jitted step that also decides termination on
  device; the host syncs every `sync_every` steps, not every token.
- this module — hydration + batched bucketed prefill + the public API
  (`admit_many`, `step`, `sync`, `run_until_drained`).

Admission of a wave:
1. hydrate masks: per-request profile-cache lookup; only MISSING profiles
   are aggregated against the bank — k-sparse (top-k rows only) for hard
   masks, dense einsum for soft — in one jitted call padded to a pow2
   profile-count bucket; results are cached and the wave's rows gathered.
2. ONE scatter of the stacked rows into the per-slot mask buffers.
3. batched bucketed prefill: every same-length-bucket group goes through
   ONE jitted prefill call (stacked [B, pad] batch, per-request last-token
   argmax on device), then one batched KV-cache scatter per group.
   Attention archs pad prompts to pow2 buckets; recurrent-state archs
   (rwkv/mamba/zamba) prefill at exact length (pad tokens cannot be
   masked out of a recurrent state).

The engine never touches `ProfileStore` internals — hydration goes through
the store's vectorized public API (`batch_sparse_indices`, `ln_affines`,
`batch_mask_weights`). It DOES subscribe to the store's change
notifications: re-graduating a profile (`add_profile`/`merge_from`)
invalidates its cached aggregate, so serving never pins a re-trained
profile to stale Â/B̂.

Multi-device: pass `mesh=` (see `launch/mesh.py`) and the same engine runs
under GSPMD — params via the repo sharding rules (bank d_model / heads /
vocab TP over "model"), KV cache and slot/mask buffers with their slot
axis over "data", all jitted hot-path functions pinned to those shardings.
No contraction is split along the slot axis, so admission aggregates and
per-slot decode are bit-identical to the single-device path (validated on
CPU with XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import time
import weakref
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs as OBS
from repro.obs import trace as TR
from repro.core import xpeft as XP
from repro.core.profiles import ProfileStore
from repro.kernels import ops
from repro.models import model as MDL
from repro.resilience import (InjectedHydrationError, RecordIntegrityError,
                              RetryPolicy, retry_with_backoff)
from repro.serve import pages as PG
from repro.serve.profile_cache import ProfileCache
from repro.serve.scheduler import Request, Scheduler
from repro.serve.slots import SlotState
from repro.serve.steps import greedy_next
from repro.utils import pow2_count


def _rate(num, den, nd: int = 4) -> float:
    """Rate field for serve_stats(): 0.0 — not num/max(den,1) — when the
    denominator never ticked. A zero-decode engine must report 0 syncs per
    token, not `host_syncs` of them."""
    return round(num / den, nd) if den else 0.0


class ServeEngine:
    def __init__(self, cfg, params, store: ProfileStore, *, max_slots: int = 4,
                 max_seq: int = 256, precompute: bool = True,
                 sync_every: int = 8, cache_bytes: Optional[int] = 64 << 20,
                 mesh=None, fault_plan=None,
                 retry_policy: Optional[RetryPolicy] = None,
                 continuous: bool = False, page_size: int = 16,
                 max_pages: Optional[int] = None,
                 mask_pages: Optional[int] = None,
                 max_wait_waves: Optional[int] = None,
                 obs: Optional[OBS.Observability] = None):
        self.cfg = cfg
        self.store = store
        # observability bundle (ISSUE 10). Device-side instrumentation is
        # UNCONDITIONAL (the slot obs accumulator exists either way, so
        # compiled programs are identical with or without a bundle); the
        # bundle only turns on host-side histogram/trace/sentinel work at
        # the sync boundaries the engine already has.
        self.obs = OBS.get(obs)
        self.S = max_seq
        self.n_slots = max_slots
        self.precompute = precompute and cfg.xpeft.enabled
        self.sync_every = sync_every
        self.mesh = mesh
        # continuous batching (ISSUE 7): the KV/recurrent cache and the
        # per-slot adapter records live in block-paged pools; slots retire,
        # refill, preempt and resume at every host sync instead of decoding
        # in lockstep waves. continuous=False keeps the PR-2 windowed
        # engine bit-for-bit (the parity baseline cb_smoke gates against).
        self.continuous = continuous
        self.page_size = page_size
        # self-speculative decoding (ISSUE 8): the shared frozen PLM — the
        # zero-adapter entry, bitwise the bare PLM — drafts spec_gamma
        # tokens per slot per round; the adapted model verifies all of them
        # in ONE batched step and commits the accepted prefix plus one
        # correction/bonus token. Greedy output is bitwise identical to
        # non-speculative greedy per request; speculation only changes how
        # many device steps the same tokens take.
        if cfg.decode_fused and \
                ops.resolve_impl(cfg.xpeft.kernel_impl) == "pallas":
            raise ValueError(
                "decode_fused cannot be served with compiled Pallas: the "
                "megakernel's row math reshapes the [1, H*hd] QKV rows to "
                "[1, H, hd], which Mosaic cannot lay out on the TPU, and "
                "it holds a whole layer's weights in VMEM (~25 MB at "
                "qwen1.5-0.5b widths, over v5e's 16 MiB scoped default). "
                "Serve with decode_fused=False, or kernel_impl='ref' / "
                "'interpret' to run the megakernel math")
        self.spec = bool(cfg.spec_enable)
        self.spec_gamma = int(cfg.spec_gamma)
        if self.spec:
            if not continuous:
                raise ValueError("spec_enable requires continuous=True "
                                 "(drafting rides the paged decode path)")
            if cfg.decode_fused:
                raise ValueError(
                    "spec_enable and decode_fused are exclusive per "
                    "engine: verification runs a T=gamma+1 composed "
                    "forward, which the T=1 megakernel cannot serve")
            if cfg.block_pattern != "attn":
                raise ValueError("spec_enable requires pure-attention "
                                 "blocks (recurrent state cannot rewind "
                                 "rejected drafts)")
            if self.spec_gamma < 1:
                raise ValueError("spec_gamma must be >= 1")
        # heterogeneous adapter-type bank (cfg.xpeft.bank_spec): typed
        # cache entries / slot buffers; prefix segments additionally
        # hydrate KV rows into the cache at admission. A type-pure
        # bottleneck spec keeps every code path below bitwise-identical.
        self.hetero = bool(cfg.xpeft.enabled and cfg.xpeft.is_hetero)
        self.prefix_len = int(cfg.xpeft.prefix_tokens) \
            if (self.hetero and cfg.xpeft.has_prefix) else 0
        self._prefix_seg = next(
            ((off, cnt) for t, off, cnt in cfg.xpeft.segments()
             if t == "prefix"), None)
        if self.hetero:
            if cfg.xpeft.bank_quant != "none":
                raise ValueError(
                    "bank_quant engines do not serve heterogeneous "
                    "bank_specs (quantize_bank_hetero covers storage; "
                    "serve with bank_quant='none')")
            if self.precompute and store.mask_type != "hard":
                raise ValueError(
                    "heterogeneous precompute serving requires hard-mask "
                    "profiles (per-type k-sparse aggregation)")
        if self.prefix_len:
            if self.spec:
                raise ValueError(
                    "spec_enable cannot serve a prefix-bearing bank_spec: "
                    "bare-PLM drafts would attend the adapted prefix KV "
                    "rows resident in the shared cache")
            if cfg.block_pattern != "attn":
                raise ValueError("prefix segments require pure-attention "
                                 "blocks (KV-row hydration)")
            if not (precompute and cfg.xpeft.enabled):
                raise ValueError(
                    "per-step mask serving cannot hydrate prefix KV rows; "
                    "a prefix-bearing bank_spec requires precompute=True")
            if self.prefix_len >= max_seq - 1:
                raise ValueError("prefix_tokens must leave room for the "
                                 f"prompt (max_seq={max_seq})")
        # quantized bank (cfg.xpeft.bank_quant): the bf16/fp32 bank is
        # quantized ONCE here and DROPPED from the resident params — the
        # engine serves every admission from the int8/int4 rows (k-sparse
        # aggregation dequantizes in-register) and every decode step from
        # quantized Â/B̂ records, so per-device residency shrinks by the
        # storage factor. bank_quant="none" leaves params untouched and
        # every code path below identical to the unquantized engine.
        self.quant = cfg.xpeft.bank_quant if self.precompute else "none"
        self.qbank = None
        self._qrow_bytes = 0
        if cfg.xpeft.enabled and cfg.xpeft.bank_quant != "none" \
                and not precompute:
            # refuse rather than silently serve the unquantized bank: the
            # per-step mask path hydrates against the fp bank every step,
            # so none of bank_quant's byte/residency savings would exist
            raise ValueError("bank_quant serving requires precompute "
                             "admission (per-step mask hydration reads "
                             "the unquantized bank)")
        if self.quant != "none":
            from repro.quant import schemes as QS
            QS.check_scheme(self.quant)
            if store.mask_type != "hard":
                raise ValueError("bank_quant serving requires hard-mask "
                                 "profiles (k-sparse quantized aggregation)")
            self.qbank = QS.quantize_bank(params["xpeft_bank"], self.quant,
                                          group=cfg.xpeft.quant_group)
            params = {k: v for k, v in params.items() if k != "xpeft_bank"}
            # TRUE quantized bank bytes of one (l, n) row across both banks
            # + scales — what one k-sparse admission read actually moves
            L_, N_ = self.qbank["bank_a_q"].shape[:2]
            self._qrow_bytes = sum(
                int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                for v in self.qbank.values()) // (L_ * N_)
        self.params = params
        # multi-device: same engine code on 1 device or an N-device mesh.
        # Params take the repo sharding rules (TP over "model": bank d_model,
        # heads, mlp, vocab — fsdp=False: serving replicates what TP doesn't
        # claim, an all-gather-on-use would sit on the decode critical path);
        # the KV/recurrent cache takes cache_specs (slots over "data",
        # kv/state heads over "model"); slot state + mask buffers shard
        # their slot axis over "data" (leading_axis_specs).
        self._specs = {}
        self._shardings = {}
        if mesh is not None:
            from repro.distributed import sharding as SH
            self._specs["params"] = SH.param_specs(params, mesh, fsdp=False)
            self._shardings["params"] = SH.to_shardings(
                self._specs["params"], mesh)
            self.params = jax.device_put(params, self._shardings["params"])
            if self.qbank is not None:
                # quantized leaves keep the bf16 bank's TP layout: bank_*_q
                # shard d_model over "model", scale arrays ride along on
                # their matching dims (rules in distributed/sharding.py)
                self._specs["qbank"] = SH.param_specs(self.qbank, mesh,
                                                      fsdp=False)
                self._shardings["qbank"] = SH.to_shardings(
                    self._specs["qbank"], mesh)
                self.qbank = jax.device_put(self.qbank,
                                            self._shardings["qbank"])
        # cache: dense [lead, n_slots, S, ...] block (windowed), or paged
        # pools + per-slot page table (continuous). Pure-recurrent archs
        # have no sequence-axis leaves — the pool degenerates away and the
        # continuous engine still gets mid-stream admission.
        self._paged = False
        self.page_alloc: Optional[PG.PageAllocator] = None
        self.mask_alloc: Optional[PG.PageAllocator] = None
        self.n_pages = 0
        if continuous:
            template = jax.eval_shape(
                lambda: MDL.init_cache(cfg, max_slots, max_seq))
            self._paged = PG.paged_seq_len(template) > 0
            if self._paged:
                if max_seq % page_size:
                    raise ValueError(f"max_seq {max_seq} must be a "
                                     f"multiple of page_size {page_size}")
                per_req = PG.pages_needed(max_seq, page_size)
                self.n_pages = (max_pages if max_pages is not None
                                else max_slots * per_req)
                if self.n_pages < per_req:
                    raise ValueError(
                        f"max_pages={self.n_pages} cannot hold one "
                        f"max-length request ({per_req} pages) — the "
                        "engine could deadlock instead of preempting")
                ncolors = 1
                if mesh is not None:
                    d = dict(mesh.shape).get("data", 1)
                    if self.n_pages % d == 0:
                        ncolors = d
                self.page_alloc = PG.PageAllocator(self.n_pages,
                                                   n_colors=ncolors)
            self.cache = PG.make_paged_cache(template, max(self.n_pages, 1),
                                             page_size, max_slots)
            self._mp = int(self.cache["table"].shape[1])
            self._sentinel = max(self.n_pages, 1)
            self._page_table_h = np.full((max_slots, self._mp),
                                         self._sentinel, np.int32)
        else:
            self.cache = MDL.init_cache(cfg, max_slots, max_seq)
        if mesh is not None:
            if continuous:
                self._specs["cache"] = SH.paged_cache_specs(
                    self.cache, mesh, cfg, max_slots)
            else:
                self._specs["cache"] = SH.cache_specs(self.cache, mesh, cfg,
                                                      max_slots)
            self._shardings["cache"] = SH.to_shardings(
                self._specs["cache"], mesh)
            self.cache = jax.device_put(self.cache, self._shardings["cache"])
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        # resilience: admission probes each profile (with retry) before
        # hydration; a request whose profile can't be served degrades to
        # the bare PLM (zero-adapter masks) instead of failing its wave
        self.fault_plan = fault_plan
        self.retry_policy = retry_policy or RetryPolicy()
        self.degraded_requests = 0
        self.hydration_retries = 0
        self.slot_degraded: List[bool] = [False] * max_slots
        # continuous mode admits in small increments (1-2 freed slots), so
        # largest-bucket-first keeps prefill launches full; max_wait_waves
        # (default 4 there) stops that from starving rare lengths. The
        # windowed engine keeps strict head-first FIFO.
        if max_wait_waves is None and continuous:
            max_wait_waves = 4
        self.scheduler = Scheduler(
            cfg.block_pattern,
            policy="efficiency" if continuous else "fifo",
            max_wait_waves=max_wait_waves)
        self.profile_cache = ProfileCache(cache_bytes)
        # re-graduation hook: the store notifies every added/replaced pid,
        # so a re-trained profile can never serve a stale cached aggregate.
        # In-flight slots keep their already-scattered Â/B̂ copy until they
        # finish; the NEXT admission of the pid re-aggregates fresh.
        store.subscribe(self.invalidate_profile)
        xp = cfg.xpeft
        L, N, b, d = cfg.num_layers, xp.num_adapters, xp.bottleneck, cfg.d_model
        # continuous mode: mask records live in an ENTRY POOL (one entry =
        # one request's aggregated record, the adapter-state analogue of a
        # KV page) addressed through a per-slot table, so record capacity
        # decouples from slot count and preempted records free their entry
        mask_lead = max_slots
        if continuous:
            self.n_mask_entries = (mask_pages if mask_pages is not None
                                   else max_slots)
            if self.n_mask_entries < 1:
                raise ValueError("mask_pages must be >= 1")
            mask_lead = self.n_mask_entries
        # entry key set: what one hydrated profile entry (and the slot
        # pool, minus prefix rows) carries. Pure bottleneck keeps the
        # historical fixed tuple; hetero derives it from the bank_spec.
        self._entry_keys = ("a_hat", "b_hat", "ln_scale", "ln_bias")
        if self.hetero and self.precompute and self.quant == "none":
            keys = list(XP.hetero_entry_keys(xp))
            if self.prefix_len:
                keys.append("prefix_skip")
            self._entry_keys = tuple(keys)
        if self.precompute and self.quant != "none":
            # per-slot QUANTIZED Â/B̂ records + fp16 scales — the decode
            # step reads these and dequantizes in-register
            # (kernels/fused_adapter_quant.py via models._xpeft_apply)
            from repro.quant import schemes as QS
            aq_s, aq_dt, as_s = QS.quant_spec((mask_lead, L, d, b),
                                              self.quant,
                                              group=xp.quant_group)
            bq_s, bq_dt, bs_s = QS.quant_spec((mask_lead, L, b, d),
                                              self.quant,
                                              group=xp.quant_group)
            self.masks = {
                "a_q": jnp.zeros(aq_s, aq_dt),
                "a_scale": jnp.zeros(as_s, jnp.float16),
                "b_q": jnp.zeros(bq_s, bq_dt),
                "b_scale": jnp.zeros(bs_s, jnp.float16),
                "ln_scale": jnp.ones((mask_lead, L, b), jnp.float32),
                "ln_bias": jnp.zeros((mask_lead, L, b), jnp.float32),
            }
        elif self.precompute and self.hetero:
            # typed slot pool: one leaf per entry key the spec's families
            # need. Prefix ROWS are absent by design — they hydrate into
            # the KV cache at prefill; only the per-layer skip gate rides
            # with the decode masks.
            dt = jnp.dtype(cfg.dtype)
            shapes = {
                "a_hat": ((L, d, b), dt), "b_hat": ((L, b, d), dt),
                "ln_scale": ((L, b), jnp.float32),
                "ln_bias": ((L, b), jnp.float32),
                "lora_a": ((L, d, b), dt), "lora_b": ((L, b, d), dt),
                "ia3_s": ((L, d), dt),
                "prefix_skip": ((L,), jnp.int32),
            }
            self.masks = {}
            for key in self._entry_keys:
                if key in ("prefix_k", "prefix_v"):
                    continue
                shp, kdt = shapes[key]
                init = jnp.ones if key == "ln_scale" else jnp.zeros
                self.masks[key] = init((mask_lead,) + shp, kdt)
        elif self.precompute:
            dt = jnp.dtype(cfg.dtype)
            self.masks = {
                "a_hat": jnp.zeros((mask_lead, L, d, b), dt),
                "b_hat": jnp.zeros((mask_lead, L, b, d), dt),
                "ln_scale": jnp.ones((mask_lead, L, b), jnp.float32),
                "ln_bias": jnp.zeros((mask_lead, L, b), jnp.float32),
            }
        elif cfg.xpeft.enabled:
            self.masks = {
                "w_a": jnp.zeros((mask_lead, L, N), jnp.float32),
                "w_b": jnp.zeros((mask_lead, L, N), jnp.float32),
                "ln_scale": jnp.ones((mask_lead, L, b), jnp.float32),
                "ln_bias": jnp.zeros((mask_lead, L, b), jnp.float32),
            }
        else:
            self.masks = None
        if continuous and self.masks is not None:
            self.mask_alloc = PG.PageAllocator(self.n_mask_entries)
            self._mask_table_h = np.full((max_slots,), self.n_mask_entries,
                                         np.int32)
            self.masks = {"pool": self.masks,
                          "table": jnp.asarray(self._mask_table_h)}
        if mesh is not None and self.masks is not None:
            from repro.distributed import sharding as SH
            self._specs["masks"] = SH.leading_axis_specs(self.masks, mesh)
            self._shardings["masks"] = SH.to_shardings(
                self._specs["masks"], mesh)
            self.masks = jax.device_put(self.masks, self._shardings["masks"])
        # continuous mode decodes against a slot-indexed VIEW of the mask
        # record pool, re-gathered only when an entry table moves (host
        # syncs) — the pool is the record store that makes swap/refill a
        # table edit; the view is what the per-token step actually reads,
        # so record pooling costs the decode loop nothing
        self._masks_view = None
        if continuous and self.masks is not None:
            view = jax.tree.map(
                lambda m: jnp.zeros((max_slots,) + m.shape[1:], m.dtype),
                self.masks["pool"])
            if mesh is not None:
                self._specs["masks_view"] = SH.leading_axis_specs(view, mesh)
                self._shardings["masks_view"] = SH.to_shardings(
                    self._specs["masks_view"], mesh)
                view = jax.device_put(view, self._shardings["masks_view"])
            self._masks_view = view
        # speculative draft masks: a constant all-slot zero-adapter view
        # (identity LN) — the draft model IS the bare PLM, at zero extra
        # parameter memory (the whole point of SELF-speculation)
        self._zero_view = None
        if self.spec and self._masks_view is not None:
            zv = jax.tree.map(jnp.zeros_like, self._masks_view)
            zv["ln_scale"] = jnp.ones_like(zv["ln_scale"])
            if mesh is not None:
                zv = jax.device_put(zv, self._shardings["masks_view"])
            self._zero_view = zv

        if continuous and self.spec:
            # speculation round (still ONE jitted program): gamma bare-PLM
            # draft steps (scan over the same paged T=1 decode), then ONE
            # adapted T=gamma+1 verify forward at each slot's own offset.
            # The verify rewrites the drafts' bare KV with adapted KV
            # before attending (write-then-read inside forward), and
            # writeback_span commits the whole span to pages — positions
            # past the accepted prefix hold stale KV that the causal mask
            # hides and the next round overwrites.
            gamma, W = self.spec_gamma, self.spec_gamma + 1

            def decode_fn(params, cache, last_tok, lengths, masks, active):
                adapted = None if masks is None else masks["adapted"]
                zero = None if masks is None else masks["zero"]
                table = cache["table"]

                def draft_step(carry, _):
                    data, tok, pos = carry
                    dense = PG.dense_view(data, table, page_size)
                    hidden, dense, _ = MDL.forward(
                        params, tok[:, None], cfg, profile_masks=zero,
                        cache=dense, cache_pos=pos)
                    # near capacity a draft can point past S-1; writeback's
                    # page lookup clamps, so mask those writes out entirely
                    # (the tokens still draft — only their KV is dropped,
                    # and positions that far are never committed anyway)
                    ok = active & (pos < self.S)
                    data = PG.writeback(data, dense, table, pos, ok,
                                        page_size)
                    nxt = greedy_next(MDL.lm_logits(params, hidden, cfg))
                    return (data, nxt, pos + 1), nxt

                (data, _, _), drafts = jax.lax.scan(
                    draft_step, (cache["data"], last_tok, lengths), None,
                    length=gamma)
                drafts = jnp.moveaxis(drafts, 0, 1)          # [n, gamma]
                seq = jnp.concatenate([last_tok[:, None], drafts], axis=1)
                dense = PG.dense_view(data, table, page_size)
                hidden, dense, _ = MDL.forward(
                    params, seq, cfg, profile_masks=adapted, cache=dense,
                    cache_pos=lengths)
                data = PG.writeback_span(data, dense, table, lengths, W,
                                         active, page_size)
                logits = MDL.lm_logits(params, hidden, cfg)
                # same vocab-axis argmax as greedy_next, one per position
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                match = (drafts == toks[:, :gamma]).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                return toks, n_acc, {"data": data, "table": table}
        elif continuous:
            # paged decode: gather KV through the page table back to the
            # dense layout forward() already takes (bitwise-identical
            # values — junk pages only cover positions attention masks to
            # NEG_INF), then scatter the one written position back to its
            # page. All inside the ONE jitted slot step. Masks arrive as
            # the slot-indexed VIEW materialized at table-change time
            # (entry tables only move at host syncs, so gathering the
            # record pool per step would be pure overhead).
            def decode_fn(params, cache, last_tok, lengths, masks, active):
                dense = PG.dense_view(cache["data"], cache["table"],
                                      page_size)
                hidden, dense, _ = MDL.forward(params, last_tok[:, None],
                                               cfg, profile_masks=masks,
                                               cache=dense,
                                               cache_pos=lengths)
                data = PG.writeback(cache["data"], dense, cache["table"],
                                    lengths, active, page_size)
                return greedy_next(MDL.lm_logits(params, hidden, cfg)), \
                    {"data": data, "table": cache["table"]}
        else:
            def decode_fn(params, cache, last_tok, lengths, masks, active):
                hidden, cache, _ = MDL.forward(params, last_tok[:, None],
                                               cfg, profile_masks=masks,
                                               cache=cache,
                                               cache_pos=lengths)
                return greedy_next(MDL.lm_logits(params, hidden, cfg)), \
                    cache

        decode_fn = self._on_mesh(decode_fn)
        self.slots = SlotState(max_slots, max_seq, sync_every, decode_fn,
                               mesh=mesh,
                               cache_shardings=self._shardings.get("cache"),
                               spec_width=(self.spec_gamma + 1
                                           if self.spec else 1))
        # prefill legitimately compiles once per (bucket, batch) shape —
        # the wrapper runs per TRACE and records the shapes it saw, so the
        # retrace sentinel can tell "new bucket" from "placement drift"
        # (same shape tracing twice)
        self._prefill_traces = 0
        self._prefill_shapes = set()

        def _prefill_traced(params, tokens, masks, lengths, cache_pos=None,
                            prefix_rows=None):
            self._prefill_traces += 1
            self._prefill_shapes.add(tuple(tokens.shape))
            return self._prefill_impl(params, tokens, masks, lengths,
                                      cache_pos, prefix_rows)

        self._prefill = jax.jit(self._on_mesh(_prefill_traced))
        # the cache/mask buffers round-trip through these every wave: pin
        # their out-shardings so placement never drifts (a drift would both
        # retrace the decode step and migrate the KV cache mid-serve)
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,),
                               out_shardings=self._shardings.get("cache"))
        self._scatter_masks = jax.jit(
            lambda buf, slots, rows: jax.tree.map(
                lambda b_, r_: b_.at[slots].set(r_.astype(b_.dtype)),
                buf, rows),
            out_shardings=self._shardings.get("masks"))
        if continuous:
            csh = self._shardings.get("cache")
            dsh = csh["data"] if csh is not None else None
            self._insert_cb = jax.jit(
                lambda data, mini, slots, table: PG.insert_group(
                    data, mini, slots, table, page_size),
                donate_argnums=(0,), out_shardings=dsh)
            self._extract_cb = jax.jit(PG.extract_slot)
            self._restore_cb = jax.jit(
                PG.restore_slot, donate_argnums=(0,), out_shardings=dsh)
            if self.masks is not None:
                msh = self._shardings.get("masks")
                psh = msh["pool"] if msh is not None else None
                self._scatter_pool = jax.jit(
                    lambda pool, idx, rows: jax.tree.map(
                        lambda b_, r_: b_.at[idx].set(r_.astype(b_.dtype)),
                        pool, rows),
                    out_shardings=psh)
                self._extract_mask = jax.jit(
                    lambda pool, entry: jax.tree.map(
                        lambda m: m[entry], pool))
                self._gather_mask_view = jax.jit(
                    lambda pool, table: jax.tree.map(
                        lambda m: jnp.take(m, table, axis=0, mode="clip"),
                        pool),
                    out_shardings=self._shardings.get("masks_view"))
        # jitted admission aggregations (padded to pow2 profile counts); the
        # sparse path reads only k·L·d·b bank bytes per aggregated profile.
        # Hetero banks swap in the per-type bucketing aggregation (same
        # kernels, one launch per typed segment) returning the entry dict.
        if self.hetero:
            self._aggregate_sparse = jax.jit(self._on_mesh(
                lambda bank, ia, wa, ib, wb:
                XP.precompute_effective_adapters_sparse_hetero(
                    bank, ia, wa, ib, wb, xp)))
        else:
            self._aggregate_sparse = jax.jit(self._on_mesh(
                lambda bank, ia, wa, ib, wb:
                XP.precompute_effective_adapters_sparse(
                    bank, ia, wa, ib, wb, xp)))
        self._aggregate_dense = jax.jit(
            XP.precompute_effective_adapters_dense_batched)
        if self.quant != "none":
            from repro.quant import schemes as QS
            self._aggregate_sparse_quant = jax.jit(self._on_mesh(
                lambda qbank, ia, wa, ib, wb:
                XP.precompute_effective_adapters_sparse_quant(
                    qbank, ia, wa, ib, wb, xp)))
            # re-quantize freshly aggregated fp32 rows into the cache/slot
            # record layout (per-row over the last axis, like the bank)
            def _requant(a_hat, b_hat):
                qa = QS.quantize(a_hat, self.quant, group=xp.quant_group)
                qb = QS.quantize(b_hat, self.quant, group=xp.quant_group)
                return {"a_q": qa["q"], "a_scale": qa["scale"],
                        "b_q": qb["q"], "b_scale": qb["scale"]}

            self._requantize = jax.jit(_requant)
        # what the last admission actually did (path, cache hits, bank bytes,
        # prefill occupancy) — serve_bench reports these so CI gates on
        # exercised behavior, not config math
        self.last_admission: Optional[dict] = None
        self.decode_tokens = 0
        # speculation accounting: drafts offered vs accepted, totals and
        # per-request (uid-keyed, so it survives preempt/resume cycles)
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._spec_by_uid: dict = {}
        self.prefill_batches = 0
        self.prefill_rows = 0
        self.prefill_real = 0
        # current sync window. Windowed: sync_every capped by the UPPER
        # bound on tokens any live request can still emit (slots never
        # dead-step a full window after every request finished).
        # Continuous: capped by the LOWER bound — the host predicts the
        # first retirement exactly (greedy decode terminates on budget or
        # capacity, both host-known), so the sync lands the moment a slot
        # frees and its capacity is re-admitted immediately.
        self._window = sync_every
        # continuous-batching state: admission-order stamps (preempt the
        # youngest), the preempted-request resume queue (oldest first),
        # and the capacity accounting serve_stats reports
        self._slot_seq = [0] * max_slots
        self._admit_seq = 0
        self._resume_q: List[dict] = []
        self._backlog = False
        self._tables_dirty = True
        self._view_dirty = True
        self.preemptions = 0
        self.resumes = 0
        self.useful_slot_steps = 0
        self.stranded_slot_steps = 0
        # retrace sentinel: the per-bench "one trace" assertions of PRs
        # 2-9, promoted to a runtime invariant checked at every sync. The
        # decode step has a FIXED signature (budget 1); admit scatter and
        # prefill are shape-polymorphic, so their contract is
        # traces <= distinct input shapes.
        # Watches hold the engine WEAKLY (the store's invalidation hooks
        # set that contract): the shared NULL_OBS sentinel — or a bundle
        # outliving this engine — must not pin dead device state. A dead
        # engine's count_fn returns None and the sentinel drops the watch.
        wself = weakref.ref(self)

        def _w(get):
            return lambda: (lambda e: None if e is None else get(e))(wself())

        self.obs.sentinel.watch(
            "serve.decode_step", _w(lambda e: e.slots.step_traces), budget=1)
        self.obs.sentinel.watch(
            "serve.admit_scatter", _w(lambda e: e.slots.admit_traces),
            shapes_fn=_w(lambda e: len(e.slots.admit_shapes)))
        self.obs.sentinel.watch(
            "serve.prefill", _w(lambda e: e._prefill_traces),
            shapes_fn=_w(lambda e: len(e._prefill_shapes)))
        self._win_t0 = time.perf_counter()  # host time the window opened

    # ------------------------------------------------------------- jit impls
    def _on_mesh(self, fn):
        """``fn`` traced under the engine's mesh for the kernel layer:
        GSPMD cannot partition a Mosaic kernel, so on a mesh every kernel
        call inside runs as a shard_map (kernels/ops.py)."""
        if self.mesh is None:
            return fn

        def traced(*args, **kw):
            with ops.kernel_mesh(self.mesh):
                return fn(*args, **kw)
        return traced

    def _prefill_impl(self, params, tokens, masks, lengths, cache_pos=None,
                      prefix_rows=None):
        """Batched prefill of one length bucket: tokens [B, pad], per-request
        masks [B, ...] (or None), lengths [B] -> (next_tok [B], mini cache).

        Prefix-bearing hetero specs pass ``cache_pos [B]`` (0 or P per
        request) and ``prefix_rows = (pk, pv) [B, L, P, kv]`` — the rows
        are written into the mini cache at buffer slots [0, P) BEFORE the
        forward, so the prompt attends them through the ordinary cached
        path (one trace; non-prefix requests carry zero rows at
        cache_pos 0 and never read them)."""
        B, P = tokens.shape
        mini = MDL.init_cache(self.cfg, B, self.S)
        if prefix_rows is not None:
            pk, pv = prefix_rows
            KV, hd = self.cfg.num_kv_heads, self.cfg.head_dim
            Pfx = pk.shape[2]

            def rows(x):
                x = x.reshape(x.shape[:3] + (KV, hd))   # [B, L, P, KV, hd]
                return jnp.moveaxis(x, 0, 1)            # [L, B, P, KV, hd]
            mini["k"] = mini["k"].at[:, :, :Pfx].set(
                rows(pk).astype(mini["k"].dtype))
            mini["v"] = mini["v"].at[:, :, :Pfx].set(
                rows(pv).astype(mini["v"].dtype))
        hidden, mini, _ = MDL.forward(
            params, tokens, self.cfg, profile_masks=masks, cache=mini,
            cache_pos=0 if cache_pos is None else cache_pos)
        idx = jnp.clip(lengths - 1, 0, P - 1)
        last_h = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        logits = MDL.lm_logits(params, last_h, self.cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), mini

    def _insert_impl(self, cache, mini, slots):
        B = slots.shape[0]

        def ins(big, small):
            # batch dim of stacked caches is axis 1; drop padded prefill rows
            return big.at[:, slots].set(small[:, :B].astype(big.dtype))
        return jax.tree.map(ins, cache, mini)

    # ------------------------------------------------------- paged memory
    def _push_tables(self) -> None:
        """Re-commit the host page/entry table mirrors to device with their
        PINNED shardings — a plain asarray would land on the default device
        and retrace the decode step on the next call. Mirrors are pushed
        only when dirty (every mutator sets the flag): a sync that retired
        nothing costs zero device traffic here."""
        if not self._tables_dirty:
            return
        self._tables_dirty = False
        t = jnp.asarray(self._page_table_h)
        csh = self._shardings.get("cache")
        if csh is not None:
            t = jax.device_put(t, csh["table"])
        self.cache["table"] = t
        if self.mask_alloc is not None:
            mt = jnp.asarray(self._mask_table_h)
            msh = self._shardings.get("masks")
            if msh is not None:
                mt = jax.device_put(mt, msh["table"])
            self.masks["table"] = mt

    def _slot_color(self, slot: int) -> int:
        """Data-shard index of a slot — the allocator color that keeps its
        pages on the shard that owns the slot."""
        if self.page_alloc is None or self.page_alloc.n_colors == 1:
            return 0
        return slot * self.page_alloc.n_colors // self.n_slots

    def _pages_for(self, length: int) -> int:
        return PG.pages_needed(length, self.page_size) if self._paged else 0

    def _reserve_resources(self, reqs: List[Request],
                           slots: List[int]) -> List[Request]:
        """Claim a mask entry + prompt-covering pages for each admission
        candidate; requests the pool can't hold yet go back to the FRONT of
        the scheduler queue (admission never preempts running requests —
        only page growth for already-running slots does)."""
        kept: List[Request] = []
        for k, r in enumerate(reqs):
            try:
                if self.mask_alloc is not None:
                    self.mask_alloc.alloc(1, r.uid)
                # pages must cover the hydrated prefix rows too — resolved
                # host-side from the store BEFORE hydration (a profile that
                # later degrades resolves to 0 here as well)
                need = self._pages_for(self._req_prefix_len(r)
                                       + len(r.prompt))
                if need:
                    try:
                        self.page_alloc.alloc(need, r.uid,
                                              color=self._slot_color(
                                                  slots[k]))
                    except PG.PageOOM:
                        if self.mask_alloc is not None:
                            self.mask_alloc.free_owner(r.uid)
                        raise
            except PG.PageOOM:
                self.scheduler.requeue_front(reqs[k:])
                break
            kept.append(r)
        return kept

    def _release_request(self, slot: int, req: Request) -> None:
        """Free a retired request's pages + mask entry and sentinel its
        table rows (pushed to device at the next table commit; the slot is
        already inactive on device, so its writes drop either way)."""
        if self._paged:
            self.page_alloc.free_owner(req.uid)
            self._page_table_h[slot] = self._sentinel
            self._tables_dirty = True
        if self.mask_alloc is not None:
            self.mask_alloc.free_owner(req.uid)
            self._mask_table_h[slot] = self.n_mask_entries
            self._tables_dirty = True
        # the freed slot is inactive on device, so its (stale) mask-view
        # row is never read — no view refresh on the retirement path

    def _preempt_slot(self, slot: int) -> None:
        """Swap a running request out to host (pages + mask record +
        host-reconstructible slot scalars), free its device resources, and
        queue it for resume. Swap, not recompute: the saved pages come back
        bit-identical, so a preempted request's tokens cannot drift."""
        r = self.slot_req[slot]
        rows = jax.device_get(self._extract_cb(
            self.cache["data"], jnp.asarray(self._page_table_h[slot]), slot))
        mask_row = None
        if self.mask_alloc is not None:
            entry = self.mask_alloc.pages_of(r.uid)[0]
            mask_row = jax.device_get(
                self._extract_mask(self.masks["pool"], entry))
        self._resume_q.append({
            "req": r, "rows": rows, "mask": mask_row,
            "len": self._rlen(r) + len(r.generated) - 1,
            "seq": self._slot_seq[slot],
            "degraded": self.slot_degraded[slot]})
        self._release_request(slot, r)
        hot = np.zeros((self.n_slots,), bool)
        hot[slot] = True
        self.slots.deactivate(hot)
        self.slot_req[slot] = None
        self.slot_degraded[slot] = False
        r.preemptions += 1
        self.preemptions += 1
        self.obs.tracer.instant(TR.CAT_PREEMPT, "preempt", slot=slot,
                                uid=r.uid)
        self.obs.metrics.inc("serve.preemptions")

    def _youngest_live(self, but: int) -> Optional[int]:
        """Preemption victim: the most recently admitted live slot other
        than `but` (LIFO preemption keeps the oldest work finishing)."""
        live = [(self._slot_seq[i], i)
                for i, r in enumerate(self.slot_req)
                if r is not None and i != but]
        return max(live)[1] if live else None

    def _try_resume(self) -> int:
        """Restore preempted requests (oldest first) into free slots while
        pages + entries allow. A blocked head blocks the queue — resumes
        never leapfrog, so preemption stays starvation-free."""
        n = 0
        while self._resume_q and self.free_slots():
            snap = self._resume_q[0]
            r = snap["req"]
            slot = self.free_slots()[0]
            try:
                if self.mask_alloc is not None:
                    self.mask_alloc.alloc(1, r.uid)
                need = self._pages_for(snap["len"])
                if need:
                    try:
                        self.page_alloc.alloc(
                            need, r.uid, color=self._slot_color(slot))
                    except PG.PageOOM:
                        if self.mask_alloc is not None:
                            self.mask_alloc.free_owner(r.uid)
                        raise
            except PG.PageOOM:
                break
            self._resume_q.pop(0)
            if self._paged:
                pages = self.page_alloc.pages_of(r.uid)
                self._page_table_h[slot] = self._sentinel
                self._page_table_h[slot, :len(pages)] = pages
            if self.mask_alloc is not None:
                entry = self.mask_alloc.pages_of(r.uid)[0]
                self._mask_table_h[slot] = entry
                self._view_dirty = True
            self._tables_dirty = True
            self._push_tables()
            self.cache["data"] = self._restore_cb(
                self.cache["data"],
                jax.tree.map(jnp.asarray, snap["rows"]),
                jnp.asarray(self._page_table_h[slot]), slot)
            if snap["mask"] is not None:
                row = jax.tree.map(lambda x: jnp.asarray(x)[None],
                                   snap["mask"])
                self.masks["pool"] = self._scatter_pool(
                    self.masks["pool"], jnp.asarray([entry]), row)
            self.slots.restore([slot], [r.generated[-1]], [snap["len"]],
                               [len(r.generated)], [r.max_new_tokens])
            self.slot_req[slot] = r
            self.slot_degraded[slot] = snap["degraded"]
            self._slot_seq[slot] = snap["seq"]
            self.resumes += 1
            self.obs.tracer.instant(TR.CAT_PREEMPT, "resume", slot=slot,
                                    uid=r.uid)
            self.obs.metrics.inc("serve.resumes")
            n += 1
        return n

    def _ensure_window_pages(self, window: int) -> None:
        """Grow every live slot's allocation to cover the next `window`
        decode writes, oldest slot first; on pool exhaustion the YOUNGEST
        live slot is preempted-to-pending and its pages reused. Init
        guarantees the pool holds one max-length request, so the oldest
        slot always makes progress — no deadlock, no starvation."""
        if not self._paged:
            return
        for _, i in sorted((self._slot_seq[i], i)
                           for i, r in enumerate(self.slot_req)
                           if r is not None):
            r = self.slot_req[i]
            if r is None:
                continue  # preempted by an earlier iteration
            cur = self._rlen(r) + len(r.generated) - 1
            need = PG.pages_needed(min(cur + window, self.S - 1),
                                   self.page_size)
            while need > len(self.page_alloc.pages_of(r.uid)):
                have = len(self.page_alloc.pages_of(r.uid))
                try:
                    new = self.page_alloc.alloc(need - have, r.uid,
                                                color=self._slot_color(i))
                    self._page_table_h[i, have:need] = new
                    self._tables_dirty = True
                except PG.PageOOM:
                    victim = self._youngest_live(but=i)
                    if victim is None:
                        raise  # can't happen: pool >= one full request
                    self._preempt_slot(victim)

    # ------------------------------------------------------------ resilience
    def _zero_entry(self):
        """One request's bare-PLM hydration entry: the free-slot buffer
        template (all-zero masks, identity LN). A zero adapter is the
        EXACT bare PLM — LN(0)·0 @ B̂ contributes 0 to the residual —
        so a degraded request decodes as if X-PEFT were disabled."""
        pool = self.masks["pool"] if self.continuous else self.masks
        zero = {k: jnp.zeros(v.shape[1:], v.dtype) for k, v in pool.items()}
        if "ln_scale" in zero:
            zero["ln_scale"] = jnp.ones_like(zero["ln_scale"])
        if self.prefix_len:
            # zero prefix ROWS complete the entry layout; a degraded
            # request admits with prefix_len 0 (prompt at buffer slot 0),
            # so these rows are never even written to its cache
            dt = jnp.dtype(self.cfg.dtype)
            shape = (self.cfg.num_layers, self.prefix_len, self.cfg.kv_dim)
            zero["prefix_k"] = jnp.zeros(shape, dt)
            zero["prefix_v"] = jnp.zeros(shape, dt)
        return zero

    def _rlen(self, r) -> int:
        """Device-buffer length of a request's prompt region: hydrated
        prefix rows + prompt tokens (every capacity/termination site must
        budget the prefix rows a request's cache actually holds)."""
        return getattr(r, "prefix_len", 0) + len(r.prompt)

    def _req_prefix_len(self, r) -> int:
        """Pre-hydration host-side prefix length of a request: P when its
        profile's hard masks select any prefix-segment slot, else 0 (a
        profile that never touches the prefix segment trains and serves
        at bare positions — bitwise, not just RoPE-shift-equivalent)."""
        if not self.prefix_len or getattr(r, "degraded", False):
            return 0
        try:
            ia, _, ib, _ = self.store.sparse_indices(int(r.profile_id))
        except Exception:
            return 0  # missing/corrupt record: the probe will degrade it
        off, cnt = self._prefix_seg
        ia, ib = np.asarray(ia), np.asarray(ib)
        hit = ((ia >= off) & (ia < off + cnt)).any() \
            or ((ib >= off) & (ib < off + cnt)).any()
        return self.prefix_len if hit else 0

    def _probe_profile(self, pid: int) -> bool:
        """Pre-hydration health probe for one profile, with retry.

        Transient (injected) hydration failures are retried under the
        engine's deadline-bounded backoff policy; a persistent failure, a
        quarantined/corrupt record, or a missing pid returns False — the
        caller degrades those requests to the bare PLM. `check_record`
        may legally shed a corrupt quantized agg payload here; that still
        probes True (the sparse path re-hydrates the intact masks)."""
        attempt = [0]

        def probe():
            i, attempt[0] = attempt[0], attempt[0] + 1
            if self.fault_plan is not None:
                self.fault_plan.on_hydration(pid, i)
            self.store.check_record(pid)

        def on_retry(exc, a, delay):
            self.hydration_retries += 1
            self.obs.metrics.inc("serve.hydration_retries")
            self.obs.metrics.observe("serve.hydration_retry_delay_us",
                                     delay * 1e6, "us")
            self.obs.tracer.instant(TR.CAT_RESILIENCE, "hydration_retry",
                                    profile=pid, attempt=a)

        try:
            retry_with_backoff(probe, policy=self.retry_policy,
                               retry_on=(InjectedHydrationError,),
                               seed=pid, on_retry=on_retry)
            return True
        except (InjectedHydrationError, RecordIntegrityError, KeyError):
            return False

    def _probe_wave(self, reqs: List[Request]) -> None:
        """Mark requests whose profile cannot be served as degraded
        (probed once per unique pid per wave)."""
        verdict = {}
        for r in reqs:
            pid = int(r.profile_id)
            if pid not in verdict:
                verdict[pid] = self._probe_profile(pid)
            if not verdict[pid] and not r.degraded:
                r.degraded = True
                self.degraded_requests += 1
                self.obs.metrics.inc("serve.degraded_requests")
                self.obs.tracer.instant(TR.CAT_RESILIENCE, "degraded",
                                        profile=pid, uid=r.uid)

    # ------------------------------------------------------------- hydration
    def _hydrate_stacked(self, reqs: List[Request]):
        """Stacked [R, ...] mask-row tree for an admission wave (or None).

        precompute=True: profile-cache lookups first; only missing profiles
        hit the bank, in ONE jitted aggregation padded to a pow2 count.
        precompute=False (paper-faithful): per-step mask weights hydrated
        through the store's public batch API; no cache involved.
        """
        if self.masks is None:
            return None
        R = len(reqs)
        pids = [int(r.profile_id) for r in reqs]
        if not self.precompute:
            ok_idx = [i for i, r in enumerate(reqs) if not r.degraded]
            if ok_idx:
                wa, wb, ls, lb = self.store.batch_mask_weights(
                    [pids[i] for i in ok_idx])
            zero = self._zero_entry()
            rows = [dict(zero) for _ in range(R)]
            for j, i in enumerate(ok_idx):
                rows[i] = {"w_a": wa[j], "w_b": wb[j],
                           "ln_scale": ls[j], "ln_bias": lb[j]}
            self.last_admission = {"path": "per_step", "requests": R,
                                   "cache_hits": 0,
                                   "cache_misses": len(ok_idx),
                                   "degraded": R - len(ok_idx),
                                   "bank_bytes_per_request": 0}
            return {key: jnp.stack([row[key] for row in rows])
                    for key in ("w_a", "w_b", "ln_scale", "ln_bias")}
        if self.quant != "none":
            return self._hydrate_stacked_quant(reqs, pids)

        entries = {}
        hits = misses = 0
        missing: List[int] = []  # unique uncached pids, admission order
        for pid, r in zip(pids, reqs):
            if r.degraded:
                continue  # bare-PLM entry; never cached, never aggregated
            entry = self.profile_cache.get(pid)
            if entry is not None:
                hits += 1
                entries[pid] = entry
            else:
                misses += 1
                if pid not in missing:
                    missing.append(pid)

        from repro.analysis.bytes import bank_slice_bytes
        bank = self.params["xpeft_bank"]
        L = self.cfg.num_layers
        N = self.cfg.xpeft.num_adapters
        if self.hetero:
            # average bytes of one unified-space (layer, slot) row across
            # the typed segments — what one k-sparse selection reads
            slice_bytes = sum(int(v.nbytes) for v in bank.values()) \
                // (L * N)
        else:
            d_, b_ = bank["bank_a"].shape[2], bank["bank_a"].shape[3]
            # Â+B̂ bytes per (layer, adapter) row — the shared analytic
            # helper (benchmarks consume it too, so gates can't drift)
            slice_bytes = bank_slice_bytes(
                d_, b_, itemsize=bank["bank_a"].dtype.itemsize)
        bank_bytes = 0
        aggregated = 0
        if missing:
            M = len(missing)
            Mp = pow2_count(M)
            aggregated = Mp
            if self.store.mask_type == "hard":
                # k-sparse fast path: only the top-k bank rows are read
                ia, wa, ib, wb = self.store.batch_sparse_indices(missing)
                pad_i = jnp.zeros((Mp - M,) + ia.shape[1:], ia.dtype)
                pad_w = jnp.zeros((Mp - M,) + wa.shape[1:], wa.dtype)
                agg = self._aggregate_sparse(
                    bank, jnp.concatenate([ia, pad_i]),
                    jnp.concatenate([wa, pad_w]),
                    jnp.concatenate([ib, pad_i]),
                    jnp.concatenate([wb, pad_w]))
                if not self.hetero:
                    agg = {"a_hat": agg[0], "b_hat": agg[1]}
                k = ia.shape[-1]
                path = "sparse"
                bank_bytes = Mp * k * L * slice_bytes
                ln_s, ln_b = self.store.ln_affines(missing)
                skip = on = None
                if self.prefix_len:
                    # host-side per-layer prefix gate from the SAME top-k
                    # indices the device aggregation consumed: a selected
                    # index carries weight 1/k > 0, so idx-in-segment is
                    # exactly wsum > 0
                    off, cnt = self._prefix_seg
                    ia_h, ib_h = np.asarray(ia), np.asarray(ib)
                    valid = (((ia_h >= off) & (ia_h < off + cnt)).any(-1)
                             | ((ib_h >= off) & (ib_h < off + cnt)).any(-1))
                    on = valid.any(-1)                       # [M]
                    skip = np.where(valid, 0,
                                    self.prefix_len).astype(np.int32)
            else:
                # soft masks are dense by construction; the jitted einsum
                # reads the bank once per call, amortized over the batch
                # (hetero precompute serving is hard-mask only — ctor)
                wa, wb, ln_s, ln_b = self.store.batch_mask_weights(missing)
                pad_w = jnp.zeros((Mp - M,) + wa.shape[1:], wa.dtype)
                a_hat, b_hat = self._aggregate_dense(
                    bank, jnp.concatenate([wa, pad_w]),
                    jnp.concatenate([wb, pad_w]))
                agg = {"a_hat": a_hat, "b_hat": b_hat}
                path = "dense"
                bank_bytes = N * L * slice_bytes
                skip = on = None
            for i, pid in enumerate(missing):
                entry = {}
                for key in self._entry_keys:
                    if key == "ln_scale":
                        entry[key] = ln_s[i]
                    elif key == "ln_bias":
                        entry[key] = ln_b[i]
                    elif key == "prefix_skip":
                        entry[key] = skip[i] if on[i] \
                            else np.zeros((L,), np.int32)
                    else:
                        entry[key] = agg[key][i]
                if self.prefix_len:
                    entry["prefix_on"] = np.int32(bool(on[i]))
                self.profile_cache.put(pid, entry)
                entries[pid] = entry
        else:
            path = "cached"

        if self.prefix_len:
            for pid, r in zip(pids, reqs):
                ent = None if r.degraded else entries.get(pid)
                r.prefix_len = 0 if ent is None \
                    else self.prefix_len * int(ent["prefix_on"])
        self.last_admission = {
            "path": path, "requests": R, "cache_hits": hits,
            "cache_misses": misses, "unique_profiles": len(set(pids)),
            "aggregated_profiles": aggregated,
            "degraded": sum(r.degraded for r in reqs),
            "bank_bytes_per_request": bank_bytes // R}
        zero = self._zero_entry()
        return {key: jnp.stack([zero[key] if r.degraded
                                else entries[pid][key]
                                for pid, r in zip(pids, reqs)])
                for key in self._entry_keys}

    def _hydrate_stacked_quant(self, reqs: List[Request], pids: List[int]):
        """Quantized-bank hydration: cache hits first; missing profiles
        hydrate from the store's persisted quantized Â/B̂ records when
        available (ZERO bank reads), else aggregate k-sparse against the
        quantized bank (dequant-in-register kernel) and re-quantize the
        fresh rows. Entries/slot buffers always hold the quantized record
        layout {a_q, a_scale, b_q, b_scale, ln_scale, ln_bias}."""
        R = len(reqs)
        entries = {}
        hits = misses = 0
        missing: List[int] = []  # unique uncached pids, admission order
        for pid, r in zip(pids, reqs):
            if r.degraded:
                continue  # bare-PLM entry; never cached, never aggregated
            entry = self.profile_cache.get(pid)
            if entry is not None:
                hits += 1
                entries[pid] = entry
            else:
                misses += 1
                if pid not in missing:
                    missing.append(pid)

        xp = self.cfg.xpeft
        L = self.cfg.num_layers
        bank_bytes = 0
        aggregated = 0
        store_hydrated = 0
        if missing:
            # persisted quantized records are usable only when the store's
            # scheme matches the engine's buffer layout
            rec_ok = (self.store.quant == self.quant
                      and self.store.quant_group == xp.quant_group)
            rec_pids = [p for p in missing
                        if rec_ok and self.store.has_quant_record(p)]
            agg_pids = [p for p in missing if p not in rec_pids]
            if agg_pids:
                M = len(agg_pids)
                Mp = pow2_count(M)
                aggregated = Mp
                ia, wa, ib, wb = self.store.batch_sparse_indices(agg_pids)
                pad_i = jnp.zeros((Mp - M,) + ia.shape[1:], ia.dtype)
                pad_w = jnp.zeros((Mp - M,) + wa.shape[1:], wa.dtype)
                a_hat, b_hat = self._aggregate_sparse_quant(
                    self.qbank, jnp.concatenate([ia, pad_i]),
                    jnp.concatenate([wa, pad_w]),
                    jnp.concatenate([ib, pad_i]),
                    jnp.concatenate([wb, pad_w]))
                q = self._requantize(a_hat, b_hat)
                k = ia.shape[-1]
                # TRUE quantized row bytes actually streamed from HBM
                bank_bytes = Mp * k * L * self._qrow_bytes
                ln_s, ln_b = self.store.ln_affines(agg_pids)
                for i, pid in enumerate(agg_pids):
                    entry = {"a_q": q["a_q"][i], "a_scale": q["a_scale"][i],
                             "b_q": q["b_q"][i], "b_scale": q["b_scale"][i],
                             "ln_scale": ln_s[i], "ln_bias": ln_b[i]}
                    self.profile_cache.put(pid, entry)
                    entries[pid] = entry
            if rec_pids:
                store_hydrated = len(rec_pids)
                recs = self.store.quant_records(rec_pids)
                ln_s, ln_b = self.store.ln_affines(rec_pids)
                for i, pid in enumerate(rec_pids):
                    entry = {key: recs[key][i] for key in
                             ("a_q", "a_scale", "b_q", "b_scale")}
                    entry["ln_scale"] = ln_s[i]
                    entry["ln_bias"] = ln_b[i]
                    self.profile_cache.put(pid, entry)
                    entries[pid] = entry
            if agg_pids and rec_pids:
                path = "quant_mixed"
            elif agg_pids:
                path = "quant_sparse"
            else:
                path = "quant_store"
        else:
            path = "cached"

        self.last_admission = {
            "path": path, "requests": R, "cache_hits": hits,
            "cache_misses": misses, "unique_profiles": len(set(pids)),
            "aggregated_profiles": aggregated,
            "store_hydrated_profiles": store_hydrated,
            "scheme": self.quant,
            "degraded": sum(r.degraded for r in reqs),
            "bank_bytes_per_request": bank_bytes // R}
        zero = self._zero_entry()
        return {key: jnp.stack([zero[key] if r.degraded
                                else entries[pid][key]
                                for pid, r in zip(pids, reqs)])
                for key in ("a_q", "a_scale", "b_q", "b_scale",
                            "ln_scale", "ln_bias")}

    # ---------------------------------------------------------------- public
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def active_count(self) -> int:
        """Host-visible count of occupied slots (refreshed at syncs)."""
        return sum(r is not None for r in self.slot_req)

    def admit_many(self, reqs: List[Request]) -> int:
        """Admit up to len(free_slots()) requests: one cache-aware batched
        hydration, one mask scatter, one prefill per length bucket, one
        slot-state scatter. Returns #admitted."""
        with self.obs.tracer.span(TR.CAT_ADMISSION, "admit_wave",
                                  offered=len(reqs)) as sp:
            n = self._admit_wave(reqs)
            sp["admitted"] = n
        return n

    def _admit_wave(self, reqs: List[Request]) -> int:
        t_wave = time.perf_counter()
        if self.slots.buf_fill:
            self.sync()  # flush the window before touching slot state
        resumed = 0
        if self.continuous and self._resume_q:
            resumed = self._try_resume()  # preempted work outranks fresh
        free = self.free_slots()
        if len(reqs) > len(free):
            # the caller sized the wave to the PRE-sync free count; the
            # sync/resume above may have shrunk it (resumed work outranks
            # fresh) — overflow goes back to the head, never dropped
            self.scheduler.requeue_front(reqs[len(free):])
            reqs = reqs[:len(free)]
        if self.continuous and reqs:
            reqs = self._reserve_resources(reqs, free)
        if not reqs:
            if resumed:
                self._refresh_window()  # resumed slots need window + view
            return 0
        assigned = free[:len(reqs)]
        if self.continuous:
            # commit page/entry tables BEFORE the prefill insert and mask
            # scatter — both address device memory through them
            for r, s in zip(reqs, assigned):
                if self._paged:
                    pages = self.page_alloc.pages_of(r.uid)
                    self._page_table_h[s] = self._sentinel
                    self._page_table_h[s, :len(pages)] = pages
                if self.mask_alloc is not None:
                    self._mask_table_h[s] = \
                        self.mask_alloc.pages_of(r.uid)[0]
                    self._view_dirty = True
                self._slot_seq[s] = self._admit_seq
                self._admit_seq += 1
            self._tables_dirty = True
            self._push_tables()
        if self.masks is not None:
            # health-probe every profile first (with retry): requests whose
            # profile can't be hydrated degrade to the bare PLM below,
            # never failing the wave for their healthy peers
            self._probe_wave(reqs)
        stacked = self._hydrate_stacked(reqs)
        prefix_rows = None
        if stacked is not None and self.prefix_len:
            # prefix KV rows hydrate into the cache at prefill, not into
            # the per-slot mask pool (the pool holds residual-path leaves
            # plus the per-layer skip gate)
            prefix_rows = (stacked.pop("prefix_k"), stacked.pop("prefix_v"))
        slot_of = {id(r): s for r, s in zip(reqs, assigned)}
        if stacked is not None:
            # ONE scatter into the per-slot buffers for the whole wave
            if self.continuous:
                entries = jnp.asarray(
                    [self.mask_alloc.pages_of(r.uid)[0] for r in reqs])
                self.masks["pool"] = self._scatter_pool(
                    self.masks["pool"], entries, stacked)
            else:
                self.masks = self._scatter_masks(
                    self.masks, jnp.asarray(assigned), stacked)

        idx_of = {id(r): i for i, r in enumerate(reqs)}
        groups = self.scheduler.group_by_bucket(reqs)
        next_toks = {}
        for pad, group in sorted(groups.items()):
            B = len(group)
            Bp = pow2_count(B)
            toks = np.zeros((Bp, pad), np.int32)
            lens = np.ones((Bp,), np.int32)
            for j, r in enumerate(group):
                toks[j, :len(r.prompt)] = r.prompt
                lens[j] = len(r.prompt)
            rows = None
            cpos = prows = None
            if stacked is not None:
                sel = jnp.asarray([idx_of[id(r)] for r in group]
                                  + [0] * (Bp - B))
                rows = jax.tree.map(lambda t: t[sel], stacked)
                if prefix_rows is not None:
                    # vector write offset: prompt lands at buffer P for
                    # prefix-on requests, 0 otherwise (one trace; pad rows
                    # use offset 0 and are dropped at insert)
                    cpos = jnp.asarray([r.prefix_len for r in group]
                                       + [0] * (Bp - B), jnp.int32)
                    prows = tuple(t[sel] for t in prefix_rows)
            with self.obs.tracer.span(TR.CAT_PREFILL, f"prefill[{pad}]",
                                      bucket=pad, rows=Bp, real=B):
                nxt, mini = self._prefill(self.params, jnp.asarray(toks),
                                          rows, jnp.asarray(lens), cpos,
                                          prows)
                gslots = jnp.asarray([slot_of[id(r)] for r in group])
                if self.continuous:
                    self.cache["data"] = self._insert_cb(
                        self.cache["data"], mini, gslots,
                        self.cache["table"])
                else:
                    self.cache = self._insert(self.cache, mini, gslots)
                nxt_h = np.asarray(nxt[:B])
            for j, r in enumerate(group):
                next_toks[id(r)] = int(nxt_h[j])
            self.prefill_batches += 1
            self.prefill_rows += Bp
            self.prefill_real += B
        if self.last_admission is not None:
            self.last_admission["prefill_batches"] = len(groups)
            self.last_admission["prefill_occupancy"] = round(
                len(reqs) / max(sum(pow2_count(len(g))
                                    for g in groups.values()), 1), 3)

        if self.obs.enabled:
            # first token exists as of the prefill above: TTFT + admission
            # wait for every submitted-through-the-scheduler request
            # (t_submit=0 means the caller bypassed submit(); skip)
            now = time.perf_counter()
            for r in reqs:
                t_sub = getattr(r, "t_submit", 0.0)
                if t_sub:
                    self.obs.metrics.observe("serve.ttft_us",
                                             (now - t_sub) * 1e6, "us")
                    self.obs.metrics.observe("serve.admission_wait_us",
                                             (t_wave - t_sub) * 1e6, "us")

        # slot lengths INCLUDE the hydrated prefix rows: the slot length is
        # the KV-buffer write position, and decode queries take their RoPE
        # position from it, so prefix-on requests continue at P + prompt
        lens_all = [self._rlen(r) for r in reqs]
        toks_all = [next_toks[id(r)] for r in reqs]
        self.slots.admit(assigned, toks_all, lens_all,
                         [r.max_new_tokens for r in reqs])
        for r, slot in zip(reqs, assigned):
            r.generated.append(next_toks[id(r)])
            if r.max_new_tokens <= 1 or self._rlen(r) >= self.S - 1:
                r.done = True  # budget spent by the prefill token
                if self.continuous:
                    self._release_request(slot, r)
            else:
                self.slot_req[slot] = r
                self.slot_degraded[slot] = r.degraded
        self._refresh_window()
        return len(reqs)

    def admit(self, req: Request) -> bool:
        return self.admit_many([req]) == 1

    def step(self) -> int:
        """One device decode step for all slots. Host state refreshes only
        at the `sync_every` cadence; returns the host-visible active count
        as of the last sync (an upper bound on live slots)."""
        active = self.active_count()
        if not active:
            return 0
        masks = self._masks_view if self.continuous else self.masks
        if self.spec and masks is not None:
            masks = {"adapted": masks, "zero": self._zero_view}
        self.cache = self.slots.step(self.params, self.cache, masks)
        if self.slots.buf_fill >= self._window:
            self.sync()
        return active

    def sync(self) -> int:
        """Force a device→host sync: distribute the window's tokens to
        their requests, mark finished requests done, free their slots (and,
        continuous mode, their pages/entries — then resume preempted work
        into the freed capacity). Returns the number of still-active
        slots."""
        s = self.slots.sync()
        if s.fill:
            # capacity accounting: an occupied slot that emitted fewer
            # tokens than the window stepped idled the difference
            # (stranded between finish and refill); an EMPTY slot strands
            # the whole window whenever work was waiting for it
            for i, req in enumerate(self.slot_req):
                c = int(s.counts[i])
                self.useful_slot_steps += c
                if req is not None:
                    # spec rounds commit up to W tokens per step, so only
                    # fully idle rounds count as stranded (max keeps the
                    # non-spec arithmetic untouched: c <= fill there)
                    self.stranded_slot_steps += max(s.fill - c, 0)
                elif self._backlog:
                    self.stranded_slot_steps += s.fill
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            c = int(s.counts[i])
            if c:
                toks = s.tokens[i, :c]
                assert (toks >= 0).all(), "non-contiguous slot activity"
                req.generated.extend(int(t) for t in toks)
                self.decode_tokens += c
            if s.drafted is not None and int(s.drafted[i]):
                d, a = int(s.drafted[i]), int(s.accepted[i])
                self.spec_drafted += d
                self.spec_accepted += a
                rec = self._spec_by_uid.setdefault(req.uid, [0, 0])
                rec[0] += d
                rec[1] += a
            if not s.active[i]:
                req.done = True
                self.slot_req[i] = None
                self.slot_degraded[i] = False
                if self.continuous:
                    self._release_request(i, req)
        self._flush_obs(s)
        if self.continuous and self._resume_q:
            self._try_resume()
        self._refresh_window()
        return self.active_count()

    def _flush_obs(self, s) -> None:
        """Observability flush at the sync boundary — the ONLY place decode
        metrics touch the host, and only on data the sync's single
        device_get already moved (s.obs is the device accumulator's window
        delta). Zero extra syncs per token by construction."""
        now = time.perf_counter()
        if s.fill and self.obs.enabled:
            acc = s.obs
            toks = int(acc[:, OBS.OBS_TOKENS].sum())
            m = self.obs.metrics
            m.inc("serve.decode_tokens", toks)
            m.inc("serve.device_steps", s.fill)
            m.inc("serve.active_slot_steps",
                  int(acc[:, OBS.OBS_ACTIVE_STEPS].sum()))
            m.inc("serve.stranded_slot_steps",
                  int(acc[:, OBS.OBS_STRANDED_STEPS].sum()))
            elapsed = now - self._win_t0
            if toks:
                # mean host-side per-token latency over this window (the
                # finest granularity observable without per-token syncs)
                m.observe("serve.decode_token_us", elapsed / toks * 1e6,
                          "us")
            m.observe("serve.queue_depth", self.scheduler.pending(), "reqs")
            m.set_gauge("serve.queue_depth_now", self.scheduler.pending())
            self.obs.tracer.complete(TR.CAT_DECODE_WINDOW, "decode_window",
                                     self._win_t0, now, steps=s.fill,
                                     tokens=toks)
            if s.drafted is not None:
                d, a = int(s.drafted.sum()), int(s.accepted.sum())
                if d:
                    m.inc("serve.spec_drafted", d)
                    m.inc("serve.spec_accepted", a)
                    m.observe("serve.spec_accept_rate", a / d, "ratio")
                    self.obs.tracer.instant(TR.CAT_SPEC, "spec_window",
                                            drafted=d, accepted=a,
                                            rounds=s.fill)
        self.obs.sentinel.check()
        self._win_t0 = now

    def _refresh_window(self) -> None:
        # device capacity stop is lengths >= S-1 post-increment with
        # lengths = prompt + generated - 1, so a slot can still emit
        # S - prompt - generated tokens (not S-1 - ...). Windowed mode
        # bounds the window by the MAX remaining (don't dead-step after
        # everyone finished); continuous mode by the MIN remaining — greedy
        # decode retires deterministically, so the sync lands exactly when
        # the first slot frees and its capacity turns over immediately.
        remaining = [min(r.max_new_tokens - len(r.generated),
                         self.S - self._rlen(r) - len(r.generated))
                     for r in self.slot_req if r is not None]
        if self.continuous:
            bound = min(remaining) if remaining else self.sync_every
        else:
            bound = max(remaining) if remaining else self.sync_every
        # spec mode windows count ROUNDS (up to W tokens each): the first
        # retirement can land after as few as ceil(bound / W) rounds, so
        # the sync bound shrinks accordingly (an early sync just costs one
        # host round-trip; a late one would strand the freed slot)
        W = self.spec_gamma + 1 if self.spec else 1
        self._window = max(1, min(self.sync_every, -(-bound // W)))
        if self.continuous:
            # page growth must cover every position the window can WRITE —
            # rounds x W tokens (draft + verify spans), not rounds tokens
            self._ensure_window_pages(self._window * W)
            self._push_tables()
            if self.masks is not None and self._view_dirty:
                self._view_dirty = False
                self._masks_view = self._gather_mask_view(
                    self.masks["pool"], self.masks["table"])
        self._backlog = bool(self.scheduler.pending() or self._resume_q)

    def submit(self, reqs) -> None:
        """Queue requests with the scheduler (admitted as slots free up)."""
        self.scheduler.submit(reqs)

    def invalidate_profile(self, pid: int) -> bool:
        """Drop a profile's cached Â/B̂ — REQUIRED after re-training updates
        its masks in the store (cache entries are keyed by pid alone, so a
        stale entry would otherwise keep serving the old adapters forever).
        The engine subscribes this hook to its store at construction, so
        `ProfileStore.add_profile` / `merge_from` (the graduation and
        resume-merge paths) invalidate automatically. Already-admitted
        slots finish on their scattered copy of the OLD masks; the next
        admission of the pid re-aggregates from the updated store."""
        return self.profile_cache.invalidate(pid)

    def abort_all(self) -> None:
        """Abort every in-flight request (tokens already decoded are kept);
        slots become free, caches/masks are left to be overwritten."""
        if self.slots.buf_fill:
            self.sync()
        self.slots.deactivate_all()
        for i, req in enumerate(self.slot_req):
            if req is not None:
                req.done = True
                self.slot_req[i] = None
                if self.continuous:
                    self._release_request(i, req)
            self.slot_degraded[i] = False
        for snap in self._resume_q:
            snap["req"].done = True  # preempted work aborts too
        self._resume_q.clear()
        self._refresh_window()

    def run_until_drained(self, queue: Optional[List[Request]] = None,
                          max_steps: int = 10_000) -> int:
        """Serve until the queue and all slots are empty. Admission happens
        whenever the host view shows free slots (i.e. after syncs)."""
        if queue:
            self.scheduler.submit(list(queue))
        steps = 0
        while steps < max_steps:
            if self._resume_q and self.free_slots() \
                    and self.slots.buf_fill == 0:
                # window boundary only: slot restore requires a synced
                # window (slots/pages can only have freed at a sync anyway)
                if self._try_resume():
                    self._refresh_window()
            free = self.free_slots()
            if free and self.scheduler.pending():
                self.admit_many(self.scheduler.next_batch(len(free)))
            if not self.active_count():
                if not self.scheduler.pending() and not self._resume_q:
                    break
                continue  # admission freed nothing; next wave will
            self.step()
            steps += 1
        if self.slots.buf_fill:
            self.sync()
        return steps

    def resident_bytes_per_device(self) -> dict:
        """Analytic per-device resident bytes of the engine's device state
        (params / KV cache / mask buffers) under the active sharding —
        identical to total bytes on a single device. serve_bench emits this
        so memory planning tracks the mesh, not the global shapes."""
        from repro.analysis.bytes import tree_nbytes
        from repro.distributed.sharding import sharded_bytes_per_device
        trees = {"params": self.params, "cache": self.cache}
        if self.qbank is not None:
            trees["qbank"] = self.qbank
        if self.masks is not None:
            trees["masks"] = self.masks
        out = {}
        for name, tree in trees.items():
            if self.mesh is None:
                out[name] = tree_nbytes(tree)
            else:
                out[name] = sharded_bytes_per_device(
                    tree, self._specs[name], self.mesh)
        out["total"] = sum(out.values())
        return out

    def reset_stats(self) -> None:
        """Zero every accounting counter PRs 2-9 accumulated piecemeal in
        __init__ (decode/prefill/spec/preempt/resilience, scheduler, the
        profile cache's hit/miss/byte counters, page allocators, host
        syncs) in ONE call — e.g. to measure steady state after warmup.
        Deliberately untouched: in-flight requests, caches/pools, and the
        compile-cache trace counters (`step_traces` etc.), which count
        compilations, not events in a measurement window."""
        self.decode_tokens = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._spec_by_uid.clear()
        self.prefill_batches = 0
        self.prefill_rows = 0
        self.prefill_real = 0
        self.preemptions = 0
        self.resumes = 0
        self.useful_slot_steps = 0
        self.stranded_slot_steps = 0
        self.degraded_requests = 0
        self.hydration_retries = 0
        self.last_admission = None
        self.slots.reset_counters()
        self.scheduler.reset_stats()
        self.profile_cache.reset_stats()
        if self.page_alloc is not None:
            self.page_alloc.reset_stats()
        if self.mask_alloc is not None:
            self.mask_alloc.reset_stats()
        self.obs.metrics.reset()

    def serve_stats(self) -> dict:
        """Counters the bench reports (and operators can scrape)."""
        out = {
            "mode": "continuous" if self.continuous else "windowed",
            "devices": 1 if self.mesh is None else self.mesh.size,
            "bank_quant": self.quant,
            # capacity accounting: slot_occupancy = share of slot-steps
            # that emitted a token; stranded_slot_steps = active-capable
            # slot-steps idled between a finish and the refill (the number
            # continuous batching exists to drive to ~0)
            "useful_slot_steps": self.useful_slot_steps,
            "stranded_slot_steps": self.stranded_slot_steps,
            "slot_occupancy": _rate(
                self.useful_slot_steps,
                self.n_slots * self.slots.device_steps),
            "step_traces": self.slots.step_traces,
            "resident_bytes_per_device": self.resident_bytes_per_device(),
            "host_syncs": self.slots.host_syncs,
            "device_steps": self.slots.device_steps,
            "decode_tokens": self.decode_tokens,
            # committed tokens vs device decode steps: equal for plain
            # decode, committed > steps is the speculation win
            "committed_tokens": self.decode_tokens,
            "committed_per_device_step": _rate(self.decode_tokens,
                                               self.slots.device_steps),
            "syncs_per_token": _rate(self.slots.host_syncs,
                                     self.decode_tokens),
            "sync_every": self.sync_every,
            "prefill_batches": self.prefill_batches,
            "prefill_occupancy": _rate(self.prefill_real,
                                       self.prefill_rows),
            "profile_cache": self.profile_cache.stats(),
            "scheduler": self.scheduler.stats(),
            # resilience surface: how often serving fell back to the bare
            # PLM, how hard hydration had to retry, and what the store has
            # quarantined — the operator's first look under chaos
            "degraded_requests": self.degraded_requests,
            "degraded_slots": sum(self.slot_degraded),
            "hydration_retries": self.hydration_retries,
            "quarantined_profiles": len(self.store.quarantined_ids()),
            "store_integrity": self.store.integrity_stats(),
        }
        if self.spec:
            out["spec"] = {
                "gamma": self.spec_gamma,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "acceptance_rate": _rate(self.spec_accepted,
                                         self.spec_drafted),
                "committed_per_device_step": _rate(
                    self.decode_tokens, self.slots.device_steps),
                # per-request acceptance (uid-keyed; survives preemption)
                "per_request_acceptance": {
                    uid: _rate(a, d)
                    for uid, (d, a) in sorted(self._spec_by_uid.items())},
            }
        if self.continuous:
            out["preemptions"] = self.preemptions
            out["resumes"] = self.resumes
            out["resume_pending"] = len(self._resume_q)
            out["page_size"] = self.page_size
            if self.page_alloc is not None:
                out["pages"] = self.page_alloc.stats()
            if self.mask_alloc is not None:
                out["mask_entries"] = self.mask_alloc.stats()
        return out
