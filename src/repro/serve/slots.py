"""Device-resident decode state for the serving slot batch.

`SlotState` owns everything the per-token loop touches — ``last_tok``,
``lengths``, ``active``, generation counters, and a token ring buffer — as
DEVICE arrays, and advances all of it in ONE jitted step that also decides
per-slot termination on device. The host only sees the state at an explicit
``sync()``: one device→host transfer every ``sync_every`` steps instead of
a round-trip per token, so steady-state decode never blocks on Python.

Invariants the engine relies on:
- activity is contiguous within a sync window: a slot admitted at window
  position 0 emits tokens at buffer positions 0..c-1 and then goes (and
  stays) inactive, so the sync can hand exactly ``n_gen`` deltas of tokens
  to the request without per-step bookkeeping;
- admission/restore must be preceded by a sync (the engine flushes the
  window before touching slot state), so buffers always start a window
  clean;
- the step traces exactly ONCE (``step_traces``): every mutator pins its
  out-shardings, so no admit/retire/preempt cycle can drift a placement
  and recompile the decode program mid-serve.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.metrics import device_acc_init, device_acc_update


def _admit_scatter(arrays, slots, last_toks, lengths, n_gens, max_news,
                   actives):
    """One batched scatter of an admission (or resume) wave into the slot
    arrays. n_gens is 1 for fresh admissions (the prefill token) and the
    already-generated count when restoring a preempted request. Extra
    (speculation) keys pass through, with the per-slot acceptance counters
    reset for the admitted slots."""
    out = dict(arrays)
    out.update({"last_tok": arrays["last_tok"].at[slots].set(last_toks),
                "lengths": arrays["lengths"].at[slots].set(lengths),
                "active": arrays["active"].at[slots].set(actives),
                "n_gen": arrays["n_gen"].at[slots].set(n_gens),
                "max_new": arrays["max_new"].at[slots].set(max_news)})
    if "drafted" in arrays:
        z = jnp.zeros_like(last_toks)
        out["drafted"] = arrays["drafted"].at[slots].set(z)
        out["accepted"] = arrays["accepted"].at[slots].set(z)
    return out


def _deactivate_scatter(arrays, mask):
    """Clear `active` for the masked slots (preemption; fixed [S] shape)."""
    out = dict(arrays)
    out["active"] = arrays["active"] & ~mask
    return out


class SlotSync(NamedTuple):
    """Host view of slot state at a sync point."""
    tokens: np.ndarray       # [n_slots, <=sync_every*W] int32, -1 padded
    counts: np.ndarray       # [n_slots] tokens emitted since last sync
    lengths: np.ndarray      # [n_slots] int32
    active: np.ndarray       # [n_slots] bool
    fill: int                # device steps this window took (stranding calc)
    drafted: Optional[np.ndarray] = None   # [n_slots] spec drafts this window
    accepted: Optional[np.ndarray] = None  # [n_slots] accepted drafts
    obs: Optional[np.ndarray] = None       # [n_slots, OBS_COLS] window deltas


class SlotState:
    """Slot decode state + the single jitted step advancing it.

    decode_fn(params, cache, last_tok [S], lengths [S], masks, active [S])
    -> (next_tok [S], cache) is the model-side half the engine provides
    (`active` lets a paged cache drop writes from slots whose pages were
    re-owned; the dense engine ignores it).

    With a `mesh`, the slot axis shards over the "data" mesh axis
    (`distributed.sharding.leading_axis_specs`) and the jitted step pins
    its out-shardings (slot arrays + the model cache via
    `cache_shardings`), so the same step serves 1 device or an N-device
    GSPMD mesh without retracing — and, because no contraction is ever
    split along the slot axis, with per-slot numerics identical to the
    single-device path.
    """

    def __init__(self, n_slots: int, max_seq: int, sync_every: int,
                 decode_fn: Callable, *, mesh=None, cache_shardings=None,
                 spec_width: int = 1):
        assert sync_every >= 1
        assert spec_width >= 1
        self.n_slots = n_slots
        self.S = max_seq
        self.sync_every = sync_every
        self.spec_width = spec_width  # gamma+1 (speculative), 1 = plain
        self.mesh = mesh
        spec = spec_width > 1
        self.last_tok = jnp.zeros((n_slots,), jnp.int32)
        self.lengths = jnp.zeros((n_slots,), jnp.int32)
        self.active = jnp.zeros((n_slots,), bool)
        self.n_gen = jnp.zeros((n_slots,), jnp.int32)
        self.max_new = jnp.zeros((n_slots,), jnp.int32)
        # speculative rounds commit a VARIABLE 1..W tokens per slot per
        # step: the buffer holds the worst case and tokens pack densely
        # from buf_len (the -1 padding moves to the tail, so the sync-side
        # contract — counts[i] tokens then padding — is unchanged)
        self.tok_buf = jnp.full((n_slots, sync_every * spec_width), -1,
                                jnp.int32)
        self.buf_len = jnp.zeros((n_slots,), jnp.int32) if spec else None
        self.drafted = jnp.zeros((n_slots,), jnp.int32) if spec else None
        self.accepted = jnp.zeros((n_slots,), jnp.int32) if spec else None
        # UNCONDITIONAL per-slot obs accumulator (repro.obs.metrics column
        # layout): updated inside the jitted step, fetched by the SAME
        # sync() device_get as the tokens. Always present so the compiled
        # program is identical whether observability is consumed or not.
        self.obs_acc = device_acc_init(n_slots)
        self.buf_fill = 0            # host: steps since last sync
        self._prev_n_gen = np.zeros((n_slots,), np.int32)  # host mirror
        self._prev_drafted = np.zeros((n_slots,), np.int32)
        self._prev_accepted = np.zeros((n_slots,), np.int32)
        self.host_syncs = 0
        self.device_steps = 0
        self.step_traces = 0         # times the decode step (re)compiled
        # multi-device: slot axis over "data" (per-slot decode stays
        # device-local), arrays committed once and every jitted update
        # pinned to the same shardings so the step never retraces on a
        # placement change across admit/sync/step cycles
        self.arr_shardings = None
        if mesh is not None:
            from repro.distributed import sharding as SH
            specs = SH.leading_axis_specs(self._arrays(), mesh)
            self.arr_shardings = SH.to_shardings(specs, mesh)
            self._set_arrays(jax.device_put(self._arrays(),
                                            self.arr_shardings))
        # immutable templates reused by sync()/deactivate_all() so resets
        # keep the committed sharding (a fresh jnp.full would land on the
        # default device and force a retrace)
        self._empty_buf = self.tok_buf
        self._all_inactive = self.active
        self._zero_counts = self.buf_len
        self._zero_obs = self.obs_acc

        def step_impl(params, cache, masks, arrays, step_idx):
            self.step_traces += 1    # python side effect: runs per TRACE
            nxt, cache = decode_fn(params, cache, arrays["last_tok"],
                                   arrays["lengths"], masks,
                                   arrays["active"])
            was_active = arrays["active"]
            lengths = arrays["lengths"] + was_active.astype(jnp.int32)
            n_gen = arrays["n_gen"] + was_active.astype(jnp.int32)
            last_tok = jnp.where(was_active, nxt, arrays["last_tok"])
            # on-device termination: token budget or sequence capacity
            done = (n_gen >= arrays["max_new"]) | (lengths >= self.S - 1)
            tok_buf = arrays["tok_buf"].at[:, step_idx].set(
                jnp.where(was_active, nxt, -1))
            obs = device_acc_update(arrays["obs"], was_active,
                                    jnp.ones_like(n_gen))
            return cache, {"last_tok": last_tok, "lengths": lengths,
                           "active": was_active & ~done, "n_gen": n_gen,
                           "max_new": arrays["max_new"], "tok_buf": tok_buf,
                           "obs": obs}

        def spec_step_impl(params, cache, masks, arrays, step_idx):
            """One SPECULATION ROUND for all slots: decode_fn drafts W-1
            tokens with the bare PLM, verifies with the adapted model, and
            returns (toks [n, W] — the adapted model's token at every
            position — and n_acc [n], the accepted-draft prefix length).
            Commit c = min(n_acc+1, budget/capacity) tokens: the accepted
            prefix plus either the correction token at the first mismatch
            or the verify bonus token, so greedy output is bitwise the
            non-speculative sequence. Tokens pack densely at buf_len."""
            self.step_traces += 1    # python side effect: runs per TRACE
            del step_idx             # spec rounds index by buf_len instead
            W = self.spec_width
            toks, n_acc, cache = decode_fn(params, cache,
                                           arrays["last_tok"],
                                           arrays["lengths"], masks,
                                           arrays["active"])
            was_active = arrays["active"]
            cap = jnp.minimum(arrays["max_new"] - arrays["n_gen"],
                              (self.S - 1) - arrays["lengths"])
            c = jnp.where(was_active,
                          jnp.clip(jnp.minimum(n_acc + 1, cap), 1, W), 0)
            lengths = arrays["lengths"] + c
            n_gen = arrays["n_gen"] + c
            sel = jnp.clip(c - 1, 0, W - 1)
            new_last = jnp.take_along_axis(toks, sel[:, None], axis=1)[:, 0]
            last_tok = jnp.where(was_active, new_last, arrays["last_tok"])
            done = (n_gen >= arrays["max_new"]) | (lengths >= self.S - 1)
            # packed scatter: row i gets toks[i, :c] at buf_len[i]...; the
            # uncommitted tail routes to an out-of-range column and drops
            col = arrays["buf_len"][:, None] + jnp.arange(W)[None, :]
            ok = was_active[:, None] & (jnp.arange(W)[None, :] < c[:, None])
            col = jnp.where(ok, col, self.sync_every * W)
            tok_buf = arrays["tok_buf"].at[
                jnp.arange(self.n_slots)[:, None], col].set(toks,
                                                            mode="drop")
            # acceptance stats: every round drafts W-1; committed drafts
            # are c-1 (the final commit is the correction/bonus token)
            drafted = arrays["drafted"] + \
                (W - 1) * was_active.astype(jnp.int32)
            accepted = arrays["accepted"] + jnp.maximum(c - 1, 0)
            return cache, {"last_tok": last_tok, "lengths": lengths,
                           "active": was_active & ~done, "n_gen": n_gen,
                           "max_new": arrays["max_new"], "tok_buf": tok_buf,
                           "buf_len": arrays["buf_len"] + c,
                           "drafted": drafted, "accepted": accepted,
                           "obs": device_acc_update(arrays["obs"],
                                                    was_active, c)}

        if spec:
            step_impl = spec_step_impl

        # Admit scatter is shape-polymorphic (one compile per wave size),
        # so the retrace sentinel contract is traces <= distinct shapes:
        # the wrapper runs per TRACE (jit only re-enters python to trace),
        # and a repeat trace of an already-seen wave size means the
        # inputs' placement drifted. The engine watches both counters.
        self.admit_traces = 0
        self.admit_shapes = set()

        def admit_impl(arrays, slots, *rest):
            self.admit_traces += 1
            self.admit_shapes.add(int(slots.shape[0]))
            return _admit_scatter(arrays, slots, *rest)

        # the cache is donated, so the step's cache writes land in place
        if mesh is not None:
            self._step = jax.jit(
                step_impl, donate_argnums=(1,),
                out_shardings=(cache_shardings, self.arr_shardings))
            self._admit_scatter = jax.jit(
                admit_impl, out_shardings=self.arr_shardings)
            self._deactivate = jax.jit(
                _deactivate_scatter, out_shardings=self.arr_shardings)
        else:
            self._step = jax.jit(step_impl, donate_argnums=(1,))
            self._admit_scatter = jax.jit(admit_impl)
            self._deactivate = jax.jit(_deactivate_scatter)

    # ----------------------------------------------------------------- device
    def _arrays(self) -> dict:
        out = {"last_tok": self.last_tok, "lengths": self.lengths,
               "active": self.active, "n_gen": self.n_gen,
               "max_new": self.max_new, "tok_buf": self.tok_buf,
               "obs": self.obs_acc}
        if self.spec_width > 1:
            out.update({"buf_len": self.buf_len, "drafted": self.drafted,
                        "accepted": self.accepted})
        return out

    def _set_arrays(self, arrays: dict) -> None:
        self.last_tok = arrays["last_tok"]
        self.lengths = arrays["lengths"]
        self.active = arrays["active"]
        self.n_gen = arrays["n_gen"]
        self.max_new = arrays["max_new"]
        self.tok_buf = arrays["tok_buf"]
        self.obs_acc = arrays["obs"]
        if self.spec_width > 1:
            self.buf_len = arrays["buf_len"]
            self.drafted = arrays["drafted"]
            self.accepted = arrays["accepted"]

    def step(self, params, cache, masks):
        """One decode step for ALL slots (inactive ones pad-compute);
        returns the updated model cache. No host transfer happens here."""
        assert self.buf_fill < self.sync_every, "sync() before stepping more"
        cache, arrays = self._step(params, cache, masks, self._arrays(),
                                   self.buf_fill)
        self._set_arrays(arrays)
        self.buf_fill += 1
        self.device_steps += 1
        return cache

    def restore(self, slots, last_toks, lengths, n_gens, max_news) -> None:
        """Scatter requests into the slot arrays with explicit generation
        counters — fresh admissions (n_gen=1, the prefill token) and
        preempt-resumes (n_gen = tokens already emitted) share this one
        jitted update. A request whose budget or sequence capacity is
        already spent never becomes active."""
        assert self.buf_fill == 0, "engine must sync() before admission"
        slots_h = np.asarray(slots, np.int32)
        lengths_h = np.asarray(lengths, np.int32)
        n_gens_h = np.asarray(n_gens, np.int32)
        max_news_h = np.asarray(max_news, np.int32)
        actives_h = (n_gens_h < max_news_h) & (lengths_h < self.S - 1)
        arrays = self._admit_scatter(
            self._arrays(), jnp.asarray(slots_h),
            jnp.asarray(np.asarray(last_toks, np.int32)),
            jnp.asarray(lengths_h), jnp.asarray(n_gens_h),
            jnp.asarray(max_news_h), jnp.asarray(actives_h))
        self._set_arrays(arrays)
        self._prev_n_gen[slots_h] = n_gens_h
        if self.spec_width > 1:
            # _admit_scatter zeroed the device counters for these slots
            self._prev_drafted[slots_h] = 0
            self._prev_accepted[slots_h] = 0

    def admit(self, slots, last_toks, lengths, max_news) -> None:
        """Scatter freshly prefilled requests into the slot arrays (one
        jitted update for the whole admission batch). The prefill's first
        generated token counts toward ``max_new`` (n_gen starts at 1); a
        request whose budget is exhausted by that token (or whose prompt
        already fills the sequence) never becomes active."""
        self.restore(slots, last_toks, lengths,
                     np.ones((len(np.asarray(slots)),), np.int32), max_news)

    def deactivate(self, mask) -> None:
        """Mark the masked slots inactive on device (preemption; the engine
        syncs first so no window tokens are in flight)."""
        assert self.buf_fill == 0, "sync() before deactivating"
        self._set_arrays(self._deactivate(self._arrays(),
                                          jnp.asarray(mask, bool)))

    def deactivate_all(self) -> None:
        """Mark every slot inactive on device (abort; engine syncs first)."""
        assert self.buf_fill == 0, "sync() before deactivating"
        self.active = self._all_inactive

    # ------------------------------------------------------------------- host
    def reset_counters(self) -> None:
        """Zero the host-side rate counters (engine.reset_stats()). The
        trace counters (`step_traces`, `admit_traces`/`admit_shapes`) are
        deliberately NOT reset — they are compile-cache facts the retrace
        sentinel watches, not per-window rates."""
        self.host_syncs = 0
        self.device_steps = 0

    def sync(self) -> SlotSync:
        """ONE device→host transfer of the window's tokens + slot status;
        resets the window. The engine distributes tokens to requests. In
        spec mode the window holds up to fill*W packed tokens per slot and
        the acceptance counters come back as per-window deltas."""
        fill = self.buf_fill
        W = self.spec_width
        width = fill * W
        if W > 1:
            (tok_buf, lengths, active, n_gen, drafted,
             accepted, obs) = jax.device_get(
                (self.tok_buf[:, :width], self.lengths, self.active,
                 self.n_gen, self.drafted, self.accepted, self.obs_acc))
            d_drafted = np.asarray(drafted) - self._prev_drafted
            d_accepted = np.asarray(accepted) - self._prev_accepted
            self._prev_drafted = np.asarray(drafted).copy()
            self._prev_accepted = np.asarray(accepted).copy()
            if fill:
                self.buf_len = self._zero_counts
        else:
            tok_buf, lengths, active, n_gen, obs = jax.device_get(
                (self.tok_buf[:, :width], self.lengths, self.active,
                 self.n_gen, self.obs_acc))
            d_drafted = d_accepted = None
        counts = np.asarray(n_gen) - self._prev_n_gen
        self._prev_n_gen = np.asarray(n_gen).copy()
        if fill:
            self.tok_buf = self._empty_buf
            # the accumulator resets each window (template keeps the
            # committed sharding), so the fetched values ARE the deltas
            self.obs_acc = self._zero_obs
        self.buf_fill = 0
        self.host_syncs += 1
        return SlotSync(np.asarray(tok_buf), counts, np.asarray(lengths),
                        np.asarray(active), fill, d_drafted, d_accepted,
                        np.asarray(obs))
