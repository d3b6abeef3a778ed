"""Chip smoke: the serving and onboarding main paths, once, on a TPU, at the
full width of qwen1.5-0.5b (24 layers, d 1024, vocab 151936; X-PEFT bank
N=256, k=50, b=64), through the repo's own entry points.

  python chip_smoke.py                # one chip: serving + onboarding
  python chip_smoke.py --four-chips   # 2x2 data,model mesh vs one chip

Weights, profiles and prompts are random, made from ``--seed``. The script
exits non-zero, with no result line, unless JAX finds a TPU: the kernels
it checks are the compiled Pallas ones, never the CPU reference. Every
phase failure propagates. The per-phase lines carry set-up and wall
seconds of this smoke (compiles included) — not benchmark metrics. The
last stdout line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen1.5-0.5b"
SLOTS, PROFILES, REQUESTS = 8, 8, 16
MAX_SEQ, PAGE_SIZE, MAX_NEW = 1024, 16, 32
PROMPT_MIN, PROMPT_MAX = 64, 256
ROSTER_SLOTS, GANG_STEPS, PER_SLOT, TRAIN_SEQ = 4, 5, 4, 128
# Pallas vs kernels/ref.py on the chip, as max|pallas - ref| / max|ref|.
# Aggregation: both accumulate 50 f32 terms, in different orders (ref at
# "highest" matmul precision). Fused adapter: the kernel rounds the
# bottleneck activations and the residual add to bf16 (about 2 bf16 ulps
# of the output); the ref computes in f32 and rounds once.
AGG_TOL, FUSED_TOL = 1e-4, 2e-2
# --four-chips: first-step logits of the 2x2 mesh vs one chip, as
# max|mesh - single| / max|single|. Tensor-parallel matmuls reduce in a
# different order, and bf16 rounding differences grow over 24 layers.
LOGIT_TOL = 5e-2


def make_requests(cfg, seed: int, n: int = REQUESTS, lo: int = PROMPT_MIN,
                  hi: int = PROMPT_MAX, max_new: int = MAX_NEW,
                  profiles: int = PROFILES):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(lo, hi + 1))),
                    profile_id=i % profiles, max_new_tokens=max_new)
            for i in range(n)]


def check(ok, what: str) -> None:
    """A failed check fails the smoke (not an assert: -O must not skip
    it)."""
    if not ok:
        raise RuntimeError(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since `reset()`."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs

    def reset(self) -> None:
        self.secs = 0.0


def peak_bytes() -> list:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def serve(cfg, seed: int, *, mesh=None, **shape):
    """Build the paged engine the way `repro.launch.serve` does, drain the
    seeded requests, and check what came out. Returns (engine, requests,
    phase record)."""
    import jax

    from repro.launch.serve import build_engine

    t0 = time.perf_counter()
    eng = build_engine(cfg, profiles=shape.get("profiles", PROFILES),
                       slots=shape.get("slots", SLOTS),
                       max_seq=shape.get("max_seq", MAX_SEQ), seed=seed,
                       mesh=mesh, continuous=True,
                       page_size=shape.get("page_size", PAGE_SIZE))
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0
    reqs = make_requests(cfg, seed, **shape.get("requests", {}))
    t0 = time.perf_counter()
    eng.run_until_drained(reqs)
    jax.block_until_ready(eng.cache)
    wall_s = time.perf_counter() - t0

    st = eng.serve_stats()
    for r in reqs:
        toks = np.asarray(r.generated)
        check(len(toks) == r.max_new_tokens,
              f"request {r.uid}: {len(toks)} tokens")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {r.uid}: token out of vocab")
        check(not r.degraded, f"request {r.uid} degraded")
    check(st["degraded_requests"] == 0,
          f"{st['degraded_requests']} degraded requests")
    check(st["step_traces"] == 1, f"decode traced {st['step_traces']}x")
    rec = {"setup_s": round(setup_s, 3), "wall_s": round(wall_s, 3),
           "requests": len(reqs),
           "tokens": sum(len(r.generated) for r in reqs),
           "syncs_per_token": st["syncs_per_token"],
           "step_traces": st["step_traces"],
           "resident_bytes_per_device":
               st["resident_bytes_per_device"]["total"]}
    return eng, reqs, rec


def program_text(eng) -> dict:
    """Optimized HLO of the engine's admission aggregate and decode step,
    recompiled at the shapes it served (the persistent cache has them)."""
    pids = list(range(len(eng.store.profile_ids())))
    ia, wa, ib, wb = eng.store.batch_sparse_indices(pids)
    admit = eng._aggregate_sparse.lower(
        eng.params["xpeft_bank"], ia, wa, ib, wb).compile().as_text()
    decode = eng.slots._step.lower(
        eng.params, eng.cache, eng._masks_view, eng.slots._arrays(),
        0).compile().as_text()
    return {"admission": admit, "decode": decode}


def kernel_agreement(eng, seed: int) -> dict:
    """Compiled Pallas vs kernels/ref.py on this engine's own bank and
    profiles: the admission aggregate (Â and B̂ sides) and the fused
    adapter at T=1 and a prefill bucket."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    xp = eng.cfg.xpeft
    bank = eng.params["xpeft_bank"]
    L, N, d, b = bank["bank_a"].shape
    pids = list(range(len(eng.store.profile_ids())))
    ia, wa, ib, wb = eng.store.batch_sparse_indices(pids)
    off = (jnp.arange(L, dtype=jnp.int32) * N)[:, None]

    def fold(idx, w):
        return ((jnp.asarray(idx, jnp.int32) + off).reshape(-1, xp.k),
                jnp.asarray(w, jnp.float32).reshape(-1, xp.k))

    def ref_agg(flat, fi, fw):
        with jax.default_matmul_precision("highest"):
            f = jax.jit(ref.mask_aggregate_batched_ref)
            return jnp.concatenate([f(flat, fi[i:i + L], fw[i:i + L])
                                    for i in range(0, fi.shape[0], L)])

    out, hats = {}, {}
    for side, flat, idx, w in (
            ("a", bank["bank_a"].reshape(L * N, d, b), ia, wa),
            ("b", bank["bank_b"].reshape(L * N, b, d), ib, wb)):
        fi, fw = fold(idx, w)
        got = ops.mask_aggregate_batched(flat, fi, fw, impl="pallas")
        out[f"aggregate_{side}"] = rel_err(got, ref_agg(flat, fi, fw))
        hats[side] = got.reshape((len(pids), L) + got.shape[1:])

    ln_s, ln_b = eng.store.ln_affines(pids)
    dt = bank["bank_a"].dtype
    a_hat, b_hat = hats["a"][:, 0].astype(dt), hats["b"][:, 0].astype(dt)
    ls, lb = jnp.asarray(ln_s)[:, 0], jnp.asarray(ln_b)[:, 0]
    for T in (1, PROMPT_MIN):
        x = jax.random.normal(jax.random.key(seed), (len(pids), T, d), dt)
        got = ops.fused_adapter(x, a_hat, b_hat, ls, lb,
                                activation=xp.adapter_activation,
                                impl="pallas")
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: ref.fused_adapter_batched_ref(
                *a, activation=xp.adapter_activation))(x, a_hat, b_hat, ls,
                                                       lb)
        out[f"fused_adapter_T{T}"] = rel_err(got, want)
    return out


def first_step_logits(eng, reqs):
    """Logits of each request's first generated token: its prompt through
    the adapted model (the engine's cached aggregates), uncached."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import model as MDL

    cfg = eng.cfg
    ent = [eng.profile_cache.peek(r.profile_id) for r in reqs]
    masks = {k: jnp.stack([e[k] for e in ent])
             for k in ("a_hat", "b_hat", "ln_scale", "ln_bias")}
    T = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), T), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r.prompt)] = r.prompt
    last = jnp.asarray([len(r.prompt) - 1 for r in reqs], jnp.int32)

    def f(params, toks, masks, last):
        hidden, _, _ = MDL.forward(params, toks, cfg, profile_masks=masks)
        h = jnp.take_along_axis(hidden, last[:, None, None], axis=1)
        return MDL.lm_logits(params, h, cfg)[:, 0]

    with ops.kernel_mesh(eng.mesh):    # traced here: kernels shard_map
        out = jax.jit(f)(eng.params, jnp.asarray(toks), masks, last)
    return np.asarray(out)


def device_bytes(eng) -> list:
    """Bytes the engine's params, cache and mask buffers actually hold on
    each device (from the arrays' shards, not the sharding rules)."""
    import jax

    per = {}
    for leaf in jax.tree.leaves((eng.params, eng.cache, eng.masks)):
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return [per.get(d.id, 0) for d in jax.local_devices()]


def phase_serving(cfg, seed: int, clock: CompileClock) -> dict:
    clock.reset()
    eng, _, rec = serve(cfg, seed)
    rec["compile_s"] = round(clock.secs, 3)
    text = program_text(eng)
    for name, hlo in text.items():
        check("tpu_custom_call" in hlo, f"no Pallas kernel in {name}")
    rec["pallas_in"] = sorted(text)
    agree = kernel_agreement(eng, seed)
    for name, err in agree.items():
        tol = AGG_TOL if name.startswith("aggregate") else FUSED_TOL
        check(err <= tol, f"{name}: pallas vs ref {err} > {tol}")
    rec["pallas_vs_ref"] = agree
    rec["peak_bytes_in_use"] = peak_bytes()
    return rec


def phase_onboarding(cfg, seed: int, clock: CompileClock, *,
                     slots: int = ROSTER_SLOTS, steps: int = GANG_STEPS,
                     per_slot: int = PER_SLOT, seq: int = TRAIN_SEQ) -> dict:
    import jax

    from repro.data import MarkovLM
    from repro.train import GraduationPolicy
    from repro.train.onboarding import build_onboarding_run

    clock.reset()
    t0 = time.perf_counter()
    trainer, gang = build_onboarding_run(
        cfg, MarkovLM(cfg.vocab_size, slots, seed=seed), range(slots),
        slots=slots, per_slot=per_slot, seq_len=seq,
        policy=GraduationPolicy(min_steps=steps, max_steps=steps),
        seed=seed, log_every=steps)
    jax.block_until_ready(trainer.state)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = trainer.run_until_drained(max_steps=steps)
    wall_s = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    check(len(hist) == steps, f"{len(hist)} gang steps, want {steps}")
    check(all(np.isfinite(losses)) and all(
        h["nonfinite_slots"] == 0 and h["active_slots"] == slots
        for h in hist), f"gang step records {hist}")
    check(gang.trace_counter["traces"] == 1,
          f"gang step traced {gang.trace_counter['traces']}x")
    st = trainer.scheduler.stats()
    return {"setup_s": round(setup_s, 3), "wall_s": round(wall_s, 3),
            "compile_s": round(clock.secs, 3), "gang_steps": len(hist),
            "tokens": steps * slots * per_slot * seq,
            "losses": [round(x, 6) for x in losses],
            "gang_traces": gang.trace_counter["traces"],
            "graduated": st["graduated"],
            "host_syncs_per_step": round(trainer.host_syncs / len(hist), 4),
            "peak_bytes_in_use": peak_bytes()}


def phase_four_chips(cfg, seed: int, clock: CompileClock) -> dict:
    from repro.launch.mesh import parse_mesh

    clock.reset()
    mesh_eng, mesh_reqs, mesh_rec = serve(
        cfg, seed, mesh=parse_mesh("2x2:data,model"))
    held = device_bytes(mesh_eng)
    one_eng, one_reqs, one_rec = serve(cfg, seed)
    pairs = [(a, b) for r4, r1 in zip(mesh_reqs, one_reqs)
             for a, b in zip(r4.generated, r1.generated)]
    first = one_reqs[:SLOTS]
    l4 = first_step_logits(mesh_eng, mesh_reqs[:SLOTS])
    l1 = first_step_logits(one_eng, first)
    err = rel_err(l4, l1)
    rec = {"mesh": mesh_rec, "single": one_rec,
           "compile_s": round(clock.secs, 3),
           "token_agreement": round(
               sum(a == b for a, b in pairs) / len(pairs), 6),
           "requests_identical": sum(
               list(a.generated) == list(b.generated)
               for a, b in zip(mesh_reqs, one_reqs)),
           "first_step_max_abs_logit_diff": float(np.abs(l4 - l1).max()),
           "first_step_rel_logit_diff": err,
           "first_step_argmax_agree": int((l4.argmax(-1)
                                           == l1.argmax(-1)).sum()),
           "mesh_bytes_per_device": held,
           "peak_bytes_in_use": peak_bytes()}
    # every device holds a share, none holds the whole single-chip state
    check(min(held) > 0 and max(held) < one_rec["resident_bytes_per_device"],
          f"mesh bytes per device {held}")
    check(err <= LOGIT_TOL, f"mesh vs one chip logits {err} > {LOGIT_TOL}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2 data,model mesh serving path and the "
                    "same requests on one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    if args.four_chips and jax.device_count() < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{jax.device_count()}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    check(ops.resolve_impl("auto") == "pallas",
          f"kernel_impl auto -> {ops.resolve_impl('auto')}")
    cfg = get_config(ARCH)
    clock = CompileClock()
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("serving", phase_serving), ("onboarding", phase_onboarding)])
    for name, fn in phases:
        rec = fn(cfg, args.seed, clock)
        print(f"phase {name}: {json.dumps(rec, sort_keys=True)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
