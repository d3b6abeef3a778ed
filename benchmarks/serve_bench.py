"""Serve bench: decode throughput + admission cost across the layered
serving subsystem (scheduler / slot-state / profile-cache).

Measured numbers come from the CPU-runnable smoke engine (reduced
qwen1.5-family config); the analytic columns are computed at the FULL
config's X-PEFT dimensions (N=256, k=50). Records emitted into
BENCH_serve.json (gated by benchmarks/check_bench.py):

- admission.aggregate_bytes   analytic dense-vs-sparse bank bytes (full cfg)
- admission.batched           COLD batched admission: the k-sparse path the
                              engine actually ran + bytes it read
- admission.profile_cache     WARM admission of the same profiles: the LRU
                              hit path must read ZERO bank bytes
- prefill.batched             bucketed-prefill batch occupancy
- decode.throughput           tokens/s with full slots
- decode.host_syncs           host syncs per decoded token (< 1 with
                              sync_every > 1: device-resident decode state)
- cb.parity                   continuous-batching engine vs windowed on the
                              skewed workload: per-request token ids BITWISE
                              equal, decode step compiled exactly once
- cb.occupancy                mean slot occupancy + stranded slot-steps,
                              continuous vs windowed (continuous must win)
- cb.tok_s_vs_windowed        decode tok/s ratio (>= 1.3x floor under
                              BENCH_STRICT only; structural gates above are
                              unconditional) — see benchmarks/cb_smoke.py
- spec.parity                 self-speculative greedy (bare-PLM draft,
                              adapted verify) BITWISE equal plain greedy per
                              request — normal AND adversarial-profile
                              workloads — in one compiled step
- spec.acceptance             drafted/accepted counters, acceptance rate
                              (adversarial profile must force rejections),
                              committed tokens per device step (> 1)
- spec.tok_s_vs_plain         spec-vs-plain decode tok/s + device-step
                              ratio (tok/s floor under BENCH_STRICT only:
                              CPU toy shapes are compute-bound, see
                              benchmarks/spec_smoke.py)

The mesh-vs-one-device parity lives in ``make multidev-smoke``
(benchmarks/sharded_smoke.py --check): it needs its own process, and this
one has already touched JAX.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from benchmarks.common import BenchWriter
from repro.configs import get_config, reduce_for_smoke
from repro.utils import pow2_bucket, pow2_count


def _build_engine(cfg, n_profiles: int, max_slots: int, max_seq: int,
                  precompute: bool = True, sync_every: int = 8,
                  store_agg: bool = False):
    from repro.core import xpeft as XP
    from repro.core.profiles import ProfileStore
    from repro.models import init_lm
    from repro.serve.engine import ServeEngine

    xp = cfg.xpeft
    key = jax.random.key(0)
    params = init_lm(key, cfg)
    store = ProfileStore(cfg.num_layers, xp.num_adapters, xp.bottleneck,
                         xp.mask_type, xp.k, quant=xp.bank_quant,
                         quant_group=xp.quant_group)
    table = XP.init_profile_table(key, cfg)
    for pid in range(n_profiles):
        prof = jax.tree.map(lambda t: t[pid], table)
        agg = None
        if store_agg and xp.bank_quant != "none":
            # graduation-style quantized Â/B̂ record (quantize-on-write):
            # serving admits these with ZERO bank reads
            eff = XP.precompute_effective_adapters(params["xpeft_bank"],
                                                   prof, xp)
            agg = (eff["a_hat"], eff["b_hat"])
        store.add_profile(pid, prof, agg=agg)
    eng = ServeEngine(cfg, params, store, max_slots=max_slots,
                      max_seq=max_seq, precompute=precompute,
                      sync_every=sync_every)
    return eng


# the analytic admission byte math lives in repro.analysis.bytes (shared
# with the engine's admit stats and the quant gates in check_bench)
from repro.analysis.bytes import aggregation_bytes  # noqa: E402


def main(smoke: bool = False):
    from repro.serve.engine import Request

    # analytic admission-aggregation bytes at the FULL config dims
    full = get_config("qwen1.5-0.5b")
    w = BenchWriter("serve", cfg=full)
    agg = aggregation_bytes(full)
    w.emit("admission.aggregate_bytes", None, **agg)

    cfg = reduce_for_smoke(full)
    max_slots = 2 if smoke else 4
    steps = 24 if smoke else 32
    sync_every = 8
    n_prof = max_slots + 1
    eng = _build_engine(cfg, n_prof, max_slots, max_seq=128,
                        sync_every=sync_every)

    def make_reqs(n, base=0, max_new=10_000):
        return [Request(uid=base + i, prompt=np.arange(6 + i) % cfg.vocab_size,
                        profile_id=i % n_prof, max_new_tokens=max_new)
                for i in range(n)]

    # warm up every jit variant (admission bucket, prefill buckets, decode)
    eng.admit_many(make_reqs(max_slots))
    for _ in range(2):
        eng.step()
    eng.abort_all()

    # COLD admission latency (batched k-sparse aggregation + prefill); the
    # path/bytes come from the ENGINE's record of what it actually ran, so
    # check_bench gates on exercised behavior, not config arithmetic
    eng.profile_cache.clear()
    t0 = time.perf_counter()
    n_adm = eng.admit_many(make_reqs(max_slots, base=100))
    adm_us = (time.perf_counter() - t0) / max(n_adm, 1) * 1e6
    adm = eng.last_admission
    smoke_dense = aggregation_bytes(cfg)["bytes_dense"]
    w.emit("admission.batched", adm_us, requests=n_adm, path=adm["path"],
           cache_misses=adm["cache_misses"],
           bank_bytes_per_request=adm["bank_bytes_per_request"],
           measured_reduction=round(
               smoke_dense / adm["bank_bytes_per_request"], 2))
    eng.abort_all()

    # WARM admission: the same profiles are now LRU-cached, so the whole
    # wave admits with ZERO bank reads (the dominant multi-profile case)
    t0 = time.perf_counter()
    n_adm = eng.admit_many(make_reqs(max_slots, base=200))
    warm_us = (time.perf_counter() - t0) / max(n_adm, 1) * 1e6
    adm = eng.last_admission
    w.emit("admission.profile_cache", warm_us, requests=n_adm,
           path=adm["path"], cache_hits=adm["cache_hits"],
           bank_bytes_per_request=adm["bank_bytes_per_request"],
           hit_rate=round(adm["cache_hits"] / max(adm["requests"], 1), 4),
           lifetime_hit_rate=eng.profile_cache.stats()["hit_rate"],
           cold_us=round(adm_us, 1),
           speedup=round(adm_us / max(warm_us, 1e-9), 2))

    # bucketed batched prefill occupancy (same-bucket requests share ONE
    # jitted prefill launch; pow2 row padding is the occupancy loss)
    st = eng.serve_stats()
    wave = make_reqs(max_slots)
    buckets = sorted({pow2_bucket(len(r.prompt)) for r in wave})
    w.emit("prefill.batched", None, batches=st["prefill_batches"],
           occupancy=st["prefill_occupancy"],
           last_wave_occupancy=adm.get("prefill_occupancy", 0.0),
           wave_buckets=buckets, wave_padded_rows=pow2_count(len(wave)))

    # decode throughput with full slots (device-resident slot state;
    # host syncs amortized over sync_every-step windows)
    for _ in range(2):
        eng.step()
    eng.sync()  # flush warmup tokens so no window inherits them
    syncs0, toks0 = eng.slots.host_syncs, eng.decode_tokens

    def timed_windows(per_token: bool):
        """Best-of-3 windows (CPU timing is noisy); tokens come from the
        SYNCED count of the winning window, never step()'s host-visible
        upper bound."""
        best = None
        for _ in range(3):
            w0 = eng.decode_tokens
            t0 = time.perf_counter()
            for _ in range(steps):
                eng.step()
                if per_token:
                    eng.sync()  # PR 1-era cadence: host round-trip/token
            eng.sync()
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, eng.decode_tokens - w0)
        return best

    best_dt, toks = timed_windows(per_token=False)
    w.emit("decode.throughput", best_dt / steps * 1e6, steps=steps,
           slots=max_slots, tokens=toks,
           tokens_per_s=round(toks / best_dt, 1))
    d_syncs = eng.slots.host_syncs - syncs0
    d_toks = max(eng.decode_tokens - toks0, 1)
    w.emit("decode.host_syncs", None, sync_every=sync_every,
           window_syncs=d_syncs, window_tokens=d_toks,
           syncs_per_token=round(d_syncs / d_toks, 4))

    # same-machine, same-run baseline at the PR 1 architecture's cadence
    # (host sync after every token) — the machine-independent reference
    # check_bench gates the windowed number against. Fresh admission so
    # both measurements decode at comparable cache positions.
    eng.abort_all()
    eng.admit_many(make_reqs(max_slots, base=300))
    for _ in range(2):
        eng.step()
    eng.sync()
    base_dt, base_toks = timed_windows(per_token=True)
    w.emit("decode.throughput_per_token_sync", base_dt / steps * 1e6,
           steps=steps, slots=max_slots, tokens=base_toks,
           tokens_per_s=round(base_toks / base_dt, 1))

    # ---- quantized bank (int8/int4): measured bytes + decode parity ----
    # fresh engines on the same reduced config/seed; the bf16 reference
    # tokens come from a fresh none-engine so every path decodes the same
    # requests from a cold start
    def quant_reqs(n, max_new):
        return [Request(uid=900 + i,
                        prompt=np.arange(5 + i % 4) % cfg.vocab_size,
                        profile_id=i % n_prof, max_new_tokens=max_new)
                for i in range(n)]

    n_dec = 2 * max_slots
    ref_eng = _build_engine(cfg, n_prof, max_slots, max_seq=128,
                            sync_every=sync_every)
    cold_reqs = quant_reqs(max_slots, 2)
    ref_eng.admit_many(cold_reqs)
    ref_cold_bytes = ref_eng.last_admission["bank_bytes_per_request"]
    ref_eng.abort_all()
    dec = quant_reqs(n_dec, 16)
    t0 = time.perf_counter()
    ref_eng.run_until_drained(dec)
    ref_tps = sum(len(r.generated) for r in dec) / (time.perf_counter() - t0)
    ref_toks = [list(r.generated) for r in dec]

    for scheme in ("int8", "int4"):
        qcfg = cfg.with_xpeft(bank_quant=scheme)
        eng_q = _build_engine(qcfg, n_prof, max_slots, max_seq=128,
                              sync_every=sync_every)
        t0 = time.perf_counter()
        n_adm = eng_q.admit_many(quant_reqs(max_slots, 2))
        adm_us = (time.perf_counter() - t0) / max(n_adm, 1) * 1e6
        adm = eng_q.last_admission
        eng_q.abort_all()
        dec_q = quant_reqs(n_dec, 16)
        t0 = time.perf_counter()
        eng_q.run_until_drained(dec_q)
        tps = sum(len(r.generated) for r in dec_q) / \
            (time.perf_counter() - t0)
        toks = [list(r.generated) for r in dec_q]
        pairs = [(t, u) for s, su in zip(toks, ref_toks)
                 for t, u in zip(s, su)]
        agree = sum(t == u for t, u in pairs) / max(len(pairs), 1)
        # per-STEP agreement: first generated token of each request is an
        # independent trial (no autoregressive compounding)
        step_pairs = [(s[0], su[0]) for s, su in zip(toks, ref_toks)]
        step_agree = sum(t == u for t, u in step_pairs) / len(step_pairs)
        w.emit(f"admission.quant_{scheme}", adm_us, requests=n_adm,
               path=adm["path"], scheme=adm["scheme"],
               bank_bytes_per_request=adm["bank_bytes_per_request"],
               none_bytes_per_request=ref_cold_bytes,
               vs_none=round(adm["bank_bytes_per_request"]
                             / max(ref_cold_bytes, 1), 3))
        w.emit(f"decode.quant_{scheme}", None, tokens_per_s=round(tps, 1),
               none_tokens_per_s=round(ref_tps, 1),
               token_agreement=round(agree, 4),
               step_agreement=round(step_agree, 4),
               resident_bytes=eng_q.resident_bytes_per_device()["total"],
               none_resident_bytes=ref_eng.
               resident_bytes_per_device()["total"])

        # store-hydrated admission: graduated quantized Â/B̂ records admit
        # with ZERO bank reads (the quantize-on-write train→serve path)
        eng_s = _build_engine(qcfg, n_prof, max_slots, max_seq=128,
                              sync_every=sync_every, store_agg=True)
        eng_s.admit_many(quant_reqs(max_slots, 2))
        adm_s = eng_s.last_admission
        w.emit(f"admission.quant_store_{scheme}", None,
               path=adm_s["path"],
               bank_bytes_per_request=adm_s["bank_bytes_per_request"],
               store_hydrated=adm_s["store_hydrated_profiles"])

    # ---- continuous batching vs windowed (paged KV + slot memory) -------
    # same skewed workload through both engines; cb_smoke owns the
    # workload + comparison so `make cb-smoke` and this record agree
    from benchmarks.cb_smoke import run_cb_workload
    cb = run_cb_workload(n_reqs=12)
    win_cb, cont = cb["windowed"], cb["continuous"]
    w.emit("cb.parity", None, tokens_equal=cb["tokens_equal"],
           requests=cb["requests"], step_traces=cont["step_traces"],
           preemptions=cont["preemptions"], resumes=cont["resumes"])
    w.emit("cb.occupancy", None, windowed=win_cb["occupancy"],
           continuous=cont["occupancy"],
           windowed_stranded=win_cb["stranded_slot_steps"],
           continuous_stranded=cont["stranded_slot_steps"],
           windowed_device_steps=win_cb["device_steps"],
           continuous_device_steps=cont["device_steps"])
    w.emit("cb.tok_s_vs_windowed", None,
           windowed_tokens_per_s=win_cb["tokens_per_s"],
           continuous_tokens_per_s=cont["tokens_per_s"],
           ratio=cb["tok_s_ratio"], page_size=cb["page_size"],
           pages=cont["pages"])

    # ---- self-speculative decoding (bare-PLM draft, adapted verify) -----
    # spec_smoke owns the workloads + comparison so `make spec-smoke` and
    # these records agree; the adversarial profile forces rejections so
    # the fallback path is measured, not just the accept-everything case
    from benchmarks.spec_smoke import run_spec_workload
    sp = run_spec_workload(n_reqs=6)
    w.emit("spec.parity", None, tokens_equal=sp["tokens_equal"],
           adversarial_tokens_equal=sp["adversarial_tokens_equal"],
           requests=sp["requests"], gamma=sp["gamma"],
           step_traces=sp["spec"]["step_traces"])
    w.emit("spec.acceptance", None, gamma=sp["gamma"],
           drafted=sp["spec"]["drafted"], accepted=sp["spec"]["accepted"],
           acceptance_rate=sp["spec"]["acceptance_rate"],
           adversarial_acceptance_rate=sp["spec"]
           ["adversarial_acceptance_rate"],
           committed_per_device_step=sp["spec"]
           ["committed_per_device_step"],
           plain_committed_per_device_step=sp["plain"]
           ["committed_per_device_step"])
    w.emit("spec.tok_s_vs_plain", None,
           plain_tokens_per_s=sp["plain"]["tokens_per_s"],
           spec_tokens_per_s=sp["spec"]["tokens_per_s"],
           ratio=sp["tok_s_ratio"],
           plain_device_steps=sp["plain"]["device_steps"],
           spec_device_steps=sp["spec"]["device_steps"])

    # ---- heterogeneous adapter-type bank (typed segments, one mask space)
    # hetero_smoke owns the workload + comparison so `make hetero-smoke`
    # and these records agree; the crafted no-prefix profile keeps the
    # prefix-off admission path (buffer offset 0) measured every run
    from benchmarks.hetero_smoke import run_hetero_workload
    ht = run_hetero_workload(n_reqs=6)
    w.emit("hetero.parity", None, tokens_equal=ht["tokens_equal"],
           requests=ht["requests"], step_traces=ht["step_traces"],
           prefix_on_requests=ht["prefix_on_requests"],
           prefix_off_requests=ht["prefix_off_requests"])
    w.emit("hetero.admission", None, path=ht["admission_path"],
           bank_bytes_per_request=ht["bank_bytes_per_request"],
           **{f"record_bytes_{t}": v
              for t, v in ht["record_bytes_per_type"].items()})
    w.emit("hetero.kernel_parity", None,
           **{t: int(ok) for t, ok in ht["kernel_parity"].items()})

    w.write()
    return w.records


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="small shapes / CI smoke")
    main(smoke=p.parse_args().smoke)
