"""CI gate: BENCH_*.json emission sanity.

Fails (exit 1) if the kernel/serve bench JSON artifacts are missing, have
no records, or the serving subsystem stopped delivering its measured
properties:

- k-sparse admission >= 4x analytic bank-byte reduction (N=256, k=50)
- cold admission exercised the sparse path with a >= 2x measured reduction
- WARM admission hit the profile cache and read ZERO bank bytes
- bucketed prefill occupancy >= 0.5 (pow2 padding bounds the loss)
- host syncs per decoded token < 1 (device-resident slot state)
- windowed decode no slower than the SAME RUN's per-token-sync baseline
  (the PR 1 architecture's cadence, so the gate is machine-independent);
  BENCH_STRICT=1 additionally enforces the absolute PR 1 number — for
  perf machines, not shared CI runners whose wall clock varies 2-4x
- continuous batching (PR 7 paged engine, cb.* records): per-request token
  ids BITWISE equal to the windowed engine on the skewed workload, the
  decode step compiled exactly ONCE across admissions/preemptions/resumes,
  mean slot occupancy strictly higher and stranded slot-steps strictly
  lower than windowed; BENCH_STRICT=1 additionally enforces the >= 1.3x
  decode tok/s floor (wall clock on shared runners varies — the
  structural gates are the unconditional contract)
- self-speculative decoding (ISSUE 8, spec.* records): greedy speculative
  output BITWISE equal plain greedy per request on the normal AND the
  adversarial-profile workload, ONE compiled decode step, committed tokens
  per device step > 1, acceptance within [floor, 1), and the adversarial
  profile actually forced rejections; the spec-vs-plain tok/s floor is
  BENCH_STRICT-only (CPU toy shapes are compute-bound — verify is a
  gamma+1-token forward)
- the decode megakernel records (decode_fused.*.pallas_interpret) exist
  for every adapter route (none/bf16/int8/int4) with bitwise parity
  against the jitted jnp oracle and an activation-traffic win > 1

and the quantized bank (repro/quant, bank_quant=int8|int4):

- the quant kernel records exist and their byte reduction (tpu_win) > 1
- ANALYTIC at full dims: int8 k-sparse admission <= 0.30x and int4
  <= 0.20x the bf16 DENSE bank bytes per request, and <= 0.55x / 0.32x
  the bf16 SPARSE read (2x is the physical bf16->int8 payload limit; the
  fp16 scales cost the rest — the acceptance's 0.30x/0.20x constants are
  only reachable against the dense bf16 baseline)
- MEASURED on the smoke engine: the quant cold admission took the
  quant_sparse path and read <= 0.55x (int8) / 0.35x (int4) of the
  same-run bf16 cold admission's bank bytes; store-hydrated admission
  (graduated quantized Â/B̂ records) read ZERO bank bytes
- int8 end-to-end greedy decode agrees with the bf16 path on >= 99% of
  tokens; int4 must hold >= 75% (autoregressive compounding: one argmax
  flip on the random-weight smoke model diverges the rest of the
  sequence — per-STEP agreement is also gated at >= 75%)
- quantized engines are strictly lighter per device than the bf16 engine
- BENCH_STRICT=1 additionally enforces a quant-vs-none decode throughput
  floor (dequant must not cost more than it saves)

and the training-side lifecycle (BENCH_train.json, PR 3):

- host syncs per TRAINING step < 1 (metrics buffered on device between
  log/checkpoint boundaries)
- every pending profile is accounted for (graduated + evicted == streamed)
- the gang step retraced ZERO times across admission waves
- the graduation roundtrip is bit-exact (persisted store == trained masks)
- BENCH_STRICT=1 additionally enforces an absolute profiles-graduated/min
  floor (perf machines only, same policy as the decode floor)

and the chaos soak (BENCH_fault.json, PR 6 resilience layer — also
runnable standalone via `check_bench.py --fault-only`, the chaos-smoke
path):

- >= 20% of profiles injected with persistent hydration failures and
  >= 2 store records corrupted; every admission wave still completes
- degraded_requests == the count the PLAN predicts (persistent failures
  + quarantined corrupt records) — nothing more, nothing less; flaky
  (transient) hydrations recover via retry and never degrade
- no checksum-failing record is ever served; corrupt records are all
  detected and quarantined
- UNAFFECTED requests in faulted waves decode BITWISE identical to the
  no-fault run
- gang finite guard: healthy slots bitwise-unaffected by a NaN-poisoned
  slot, the poisoned slot's params/moments bitwise-untouched
- a torn (truncated) checkpoint is rejected and resume falls back to the
  last checksum-clean step
- poisoned onboarding profiles quarantine without graduating and the
  lifecycle accounting still closes

and the observability layer (BENCH_obs.json, ISSUE 10 — produced by
`make obs-smoke`, gated opportunistically here like the chaos artifact):

- obs-on decode tokens BITWISE identical to obs-off (the device
  accumulator is unconditional, so the compiled programs are the same)
- host syncs/token and decode-step jit traces EXACTLY unchanged — the
  layer's zero-extra-syncs / zero-retraces contract
- the exported Chrome trace validates and spans >= 6 categories
- TTFT / per-token decode latency / admission wait / gang-step time
  histograms populated with 0 < p50 <= p99
- zero retrace-sentinel violations; BENCH_STRICT=1 additionally enforces
  the obs-on tok/s floor

A missing BENCH_<family>.json fails with the `make` target that produces
it (run that first); `check_bench.py --summary` instead prints one
consolidated line per family from whatever artifacts exist (each with its
recorded provenance — jax version, devices, mesh, git SHA, config hash),
marking absent families with their target.
"""
from __future__ import annotations

import json
import os
import sys

MIN_ADMISSION_REDUCTION = 4.0
MIN_MEASURED_REDUCTION = 2.0
MIN_PREFILL_OCCUPANCY = 0.5
MAX_SYNCS_PER_TOKEN = 1.0
MIN_VS_PER_TOKEN_BASELINE = 0.9   # windowed >= 0.9x same-run baseline
MIN_DECODE_TOKENS_PER_S = 2723.0  # PR 1 absolute, BENCH_STRICT only
MIN_CB_TOK_S_RATIO = 1.3          # continuous vs windowed, STRICT only
MAX_SYNCS_PER_TRAIN_STEP = 1.0
MIN_PROFILES_PER_MIN = 300.0      # smoke-config absolute, BENCH_STRICT only

# quantized bank (bank bytes are the mandatory reduction; agreement and
# the STRICT throughput floor keep the quality/latency story honest)
QUANT_GATES = {
    "int8": {"vs_dense": 0.30, "vs_sparse": 0.55, "measured_vs_none": 0.55,
             "token_agreement": 0.99},
    "int4": {"vs_dense": 0.20, "vs_sparse": 0.32, "measured_vs_none": 0.35,
             "token_agreement": 0.75},
}
MIN_INT4_STEP_AGREEMENT = 0.75
MIN_QUANT_VS_NONE_TPS = 0.15      # BENCH_STRICT only

# chaos soak (BENCH_fault.json, PR 6): injected-failure floors the plan
# must reach for the soak to mean anything
MIN_INJECTED_FAIL_RATE = 0.20
MIN_CORRUPT_RECORDS = 2

# self-speculative decoding (spec.* records, ISSUE 8). The floor is low on
# purpose: acceptance depends on how far the adapter moves the bare PLM's
# argmax, which the random-weight smoke model only loosely controls — the
# hard gates are parity, one trace, and committed tokens/device-step.
MIN_SPEC_ACCEPTANCE = 0.05
MIN_SPEC_COMMITTED_PER_STEP = 1.0
MIN_SPEC_TOK_S_RATIO = 0.4        # BENCH_STRICT only (CPU is compute-bound)

# observability (BENCH_obs.json, ISSUE 10)
MIN_OBS_TRACE_CATEGORIES = 6
MIN_OBS_TOK_S_RATIO = 0.5         # BENCH_STRICT only

# which `make` target (re)produces each BENCH_<family>.json artifact
FAMILIES = {"kernels": "bench-smoke", "serve": "bench-smoke",
            "train": "bench-smoke", "fault": "chaos-smoke",
            "obs": "obs-smoke"}


def fail(msg: str):
    print(f"check_bench: FAIL — {msg}")
    sys.exit(1)


def family_path(family: str) -> str:
    return os.path.join(os.environ.get("BENCH_DIR", "."),
                        f"BENCH_{family}.json")


def load_family(family: str) -> dict:
    path = family_path(family)
    if not os.path.exists(path):
        fail(f"BENCH_{family}.json missing — run `make {FAMILIES[family]}` "
             f"first (looked in {os.path.dirname(path) or '.'})")
    with open(path) as f:
        data = json.load(f)
    if not data.get("records"):
        fail(f"{path} has no records — run `make {FAMILIES[family]}` again")
    return data


def record(data: dict, name: str) -> dict:
    rec = next((r for r in data["records"] if r["name"] == name), None)
    if rec is None:
        fail(f"BENCH_{data['suite']}.json missing record {name!r}")
    return rec


def check_fault(fault: dict):
    """Chaos-soak gates (BENCH_fault.json): every resilience contract the
    PR 6 layer claims, checked against what the soak actually observed."""
    chaos = record(fault, "resilience.serve_chaos")
    if chaos.get("failed_waves", 1) != 0 or not chaos.get("all_done"):
        fail(f"chaos soak dropped work: {chaos.get('failed_waves')} failed "
             f"admission waves, all_done={chaos.get('all_done')} — degraded "
             "serving must complete every wave")
    if chaos.get("injected_fail_rate", 0) < MIN_INJECTED_FAIL_RATE:
        fail(f"chaos plan injected only {chaos.get('injected_fail_rate')} "
             f"persistent hydration failures < {MIN_INJECTED_FAIL_RATE} — "
             "the soak is not stressing anything")
    if chaos.get("corrupt_records", 0) < MIN_CORRUPT_RECORDS:
        fail(f"chaos plan corrupted {chaos.get('corrupt_records')} records "
             f"< {MIN_CORRUPT_RECORDS}")
    if chaos.get("corrupt_detected") != chaos.get("corrupt_records"):
        fail(f"store crc missed corruption: {chaos.get('corrupt_detected')} "
             f"detected of {chaos.get('corrupt_records')} injected")
    if chaos.get("corrupt_served", 1) != 0:
        fail(f"{chaos.get('corrupt_served')} requests were served from a "
             "checksum-failing record — corrupt records must NEVER serve")
    exp, got = chaos.get("expected_degraded"), chaos.get("degraded_requests")
    if not exp or got != exp:
        fail(f"degraded accounting broken: plan predicts {exp} degraded "
             f"requests, engine served {got} — every persistent failure "
             "degrades, nothing else does")
    if chaos.get("flaky_degraded", 1) != 0:
        fail(f"{chaos.get('flaky_degraded')} flaky-profile requests "
             "degraded — transient hydration failures must recover via "
             "retry")
    if chaos.get("hydration_retries", 0) <= 0:
        fail("the soak recorded zero hydration retries — the backoff path "
             "is not being exercised")
    if chaos.get("quarantined_profiles", 0) < MIN_CORRUPT_RECORDS:
        fail(f"only {chaos.get('quarantined_profiles')} profiles "
             f"quarantined, expected every corrupt record's")
    if not chaos.get("unaffected_bitwise"):
        fail("UNAFFECTED requests in faulted waves decoded differently "
             "from the no-fault run — degradation must be surgical")

    gang = record(fault, "resilience.gang_guard")
    if not gang.get("healthy_bitwise"):
        fail("gang finite guard: healthy slots' params/moments are not "
             "bitwise-identical to the injection-off run")
    if not gang.get("poisoned_untouched"):
        fail("gang finite guard: the poisoned slot's params or Adam "
             "moments moved — a non-finite update leaked through")
    if gang.get("nonfinite_detected", 0) <= 0:
        fail("gang finite guard saw zero non-finite strikes despite "
             "injection — the detector is dead")

    ck = record(fault, "resilience.ckpt_fallback")
    if not ck.get("fallback_ok"):
        fail(f"checkpoint fallback broken: torn step {ck.get('torn_step')} "
             f"rejected={ck.get('torn_rejected')}, resumed from "
             f"{ck.get('resumed_step')} — resume must land on the last "
             "checksum-clean checkpoint")

    ob = record(fault, "resilience.onboard_quarantine")
    if ob.get("quarantined", 0) < 1:
        fail("poisoned onboarding quarantined zero profiles")
    if not ob.get("accounting_ok"):
        fail(f"onboarding lost profiles under poisoning: "
             f"{ob.get('graduated')} graduated + {ob.get('evicted')} "
             f"evicted + {ob.get('quarantined')} quarantined != "
             f"{ob.get('profiles')} streamed")
    if ob.get("quarantined_served", 1) != 0:
        fail("a quarantined profile reached the serving store")

    # elastic reshard record is emitted only on >= 8-device runs
    el = next((r for r in fault["records"]
               if r["name"] == "resilience.elastic"), None)
    if el is not None and not el.get("bitwise"):
        fail("surviving-mesh reshard changed state values")

    print(f"check_bench[fault]: OK — {chaos['degraded_requests']}/"
          f"{chaos['requests']} requests degraded as planned over "
          f"{chaos['waves']} waves (0 failed), "
          f"{chaos['corrupt_detected']} corrupt records caught, "
          f"{chaos['hydration_retries']} retries; gang guard bitwise OK "
          f"({gang['nonfinite_detected']} strikes), checkpoint fell back "
          f"to step {ck['resumed_step']}, onboarding quarantined "
          f"{ob['quarantined']}/{ob['profiles']}"
          + ("" if el is None else
             f"; elastic reshard bitwise on {el['devices']} devices"))


def check_obs(obs: dict):
    """Observability gates (BENCH_obs.json): obs-on must be free — bitwise
    tokens, unchanged syncs/token, unchanged jit trace counts — and the
    trace/histogram artifacts must actually carry signal."""
    par = record(obs, "obs.parity")
    if not par.get("tokens_equal"):
        fail("obs-on decode tokens != obs-off — attaching the bundle "
             "changed the compiled program (parity broken)")
    if par.get("host_syncs_on") != par.get("host_syncs_off") or \
            par.get("syncs_per_token_on") != par.get("syncs_per_token_off"):
        fail(f"obs changed host syncs: {par.get('host_syncs_off')} -> "
             f"{par.get('host_syncs_on')} "
             f"({par.get('syncs_per_token_off')} -> "
             f"{par.get('syncs_per_token_on')} syncs/token) — the layer "
             "must add ZERO syncs per token")
    if par.get("step_traces_on") != par.get("step_traces_off"):
        fail(f"obs changed decode jit traces: {par.get('step_traces_off')} "
             f"-> {par.get('step_traces_on')} — the layer must add ZERO "
             "retraces")
    tr = record(obs, "obs.trace")
    if not tr.get("valid"):
        fail("exported trace is not valid Chrome trace-event JSON")
    if tr.get("categories", 0) < MIN_OBS_TRACE_CATEGORIES:
        fail(f"trace covers {tr.get('categories')} span categories < "
             f"{MIN_OBS_TRACE_CATEGORIES} — the smoke must exercise "
             "admission/prefill/decode-window/gang-step/graduation/"
             "resilience")
    hist = record(obs, "obs.histograms")
    for prefix in ("ttft", "decode_token", "admission_wait", "gang_step"):
        cnt = hist.get(f"{prefix}_count", 0)
        p50, p99 = hist.get(f"{prefix}_p50_us", 0), \
            hist.get(f"{prefix}_p99_us", 0)
        if not cnt or not (0 < p50 <= p99):
            fail(f"{prefix} latency histogram empty or inconsistent "
                 f"(count={cnt}, p50={p50}, p99={p99})")
    sen = record(obs, "obs.sentinel")
    if sen.get("violations", 1) != 0:
        fail(f"{sen.get('violations')} retrace-sentinel violations — a "
             "hot-path fn recompiled beyond its contract")
    ov = record(obs, "obs.overhead")
    if os.environ.get("BENCH_STRICT") and \
            ov.get("ratio", 0) < MIN_OBS_TOK_S_RATIO:
        fail(f"obs-on decode at {ov.get('ratio')}x obs-off tok/s < "
             f"{MIN_OBS_TOK_S_RATIO}x floor (BENCH_STRICT)")
    print(f"check_bench[obs]: OK — parity bitwise, "
          f"{par['syncs_per_token_on']} syncs/token unchanged, "
          f"{par['step_traces_on']} decode trace(s) unchanged, "
          f"{tr['events']} trace events over {tr['categories']} "
          f"categories, {ov['ratio']}x tok/s with obs on")


def main(fault_only: bool = False):
    if fault_only:
        check_fault(load_family("fault"))
        return
    kernels = load_family("kernels")
    serve = load_family("serve")
    train = load_family("train")
    # the chaos and obs artifacts are produced by `make chaos-smoke` /
    # `make obs-smoke`, each of which runs its own mandatory gate in
    # `make verify` — here they are gated opportunistically
    # (stale-artifact safety net)
    if os.path.exists(family_path("fault")):
        check_fault(load_family("fault"))
    if os.path.exists(family_path("obs")):
        check_obs(load_family("obs"))

    names = {r["name"] for r in kernels["records"]}
    for required in ("mask_aggregate_batched.pallas_interpret",
                     "fused_adapter_batched.decode.pallas_interpret"):
        if required not in names:
            fail(f"BENCH_kernels.json missing record {required!r}")

    # decode megakernel: every adapter route present, bitwise parity vs
    # the jitted oracle, activation round-trips actually collapsed
    for route in ("none", "bf16", "int8", "int4"):
        mk = record(kernels, f"decode_fused.{route}.pallas_interpret")
        if not mk.get("parity"):
            fail(f"decode_fused.{route}: megakernel output != the jitted "
                 "jnp oracle — the fused decode step is no longer bitwise")
        if mk.get("tpu_win", 0) <= 1.0:
            fail(f"decode_fused.{route}: activation-traffic win "
                 f"{mk.get('tpu_win')}x <= 1x — the megakernel stopped "
                 "collapsing per-layer intermediate round-trips")
    for scheme in QUANT_GATES:
        for required in (f"mask_aggregate_quant_{scheme}.pallas_interpret",
                         f"fused_adapter_quant_{scheme}.decode"
                         ".pallas_interpret"):
            rec = record(kernels, required)
            if rec.get("tpu_win", 0) <= 1.0:
                fail(f"{required}: quantized bytes reduction "
                     f"{rec.get('tpu_win')}x <= 1x — the dequant-fused "
                     "kernel stopped saving HBM traffic")

    agg = record(serve, "admission.aggregate_bytes")
    if agg["reduction"] < MIN_ADMISSION_REDUCTION:
        fail(f"admission byte reduction {agg['reduction']}x < "
             f"{MIN_ADMISSION_REDUCTION}x (bytes_dense={agg['bytes_dense']}, "
             f"bytes_sparse={agg['bytes_sparse']})")

    # the record the ENGINE wrote about the cold admission it actually ran:
    # hard masks must go k-sparse and read fewer bank bytes than dense
    adm = record(serve, "admission.batched")
    if adm.get("path") != "sparse":
        fail(f"cold admission took the {adm.get('path')!r} path — the "
             "k-sparse fast path is not being exercised")
    if adm.get("measured_reduction", 0) < MIN_MEASURED_REDUCTION:
        fail(f"measured admission reduction {adm.get('measured_reduction')}x "
             f"< {MIN_MEASURED_REDUCTION}x — sparse aggregation is reading "
             "too much of the bank")

    # warm admission: every request's profile was LRU-cached, so the wave
    # must admit without touching the bank at all
    warm = record(serve, "admission.profile_cache")
    if warm.get("path") != "cached":
        fail(f"warm admission took the {warm.get('path')!r} path — the "
             "profile cache is not being hit")
    if warm.get("bank_bytes_per_request", -1) != 0:
        fail(f"cache-hit admission read {warm.get('bank_bytes_per_request')} "
             "bank bytes/request — the hit path must read ZERO")
    if warm.get("hit_rate", 0) <= 0:
        fail("profile cache hit rate is zero")

    pre = record(serve, "prefill.batched")
    if pre.get("occupancy", 0) < MIN_PREFILL_OCCUPANCY:
        fail(f"prefill batch occupancy {pre.get('occupancy')} < "
             f"{MIN_PREFILL_OCCUPANCY} — bucketing is fragmenting waves")

    sync = record(serve, "decode.host_syncs")
    if sync.get("syncs_per_token", 1.0) >= MAX_SYNCS_PER_TOKEN:
        fail(f"{sync.get('syncs_per_token')} host syncs per decoded token — "
             "decode state is not staying device-resident")

    tp = record(serve, "decode.throughput")
    if tp.get("tokens_per_s", 0) <= 0:
        fail("BENCH_serve.json has no positive decode throughput")
    base = record(serve, "decode.throughput_per_token_sync")
    floor = MIN_VS_PER_TOKEN_BASELINE * base.get("tokens_per_s", 0)
    if tp["tokens_per_s"] < floor:
        fail(f"windowed decode {tp['tokens_per_s']} tok/s < "
             f"{MIN_VS_PER_TOKEN_BASELINE}x the same-run per-token-sync "
             f"baseline {base.get('tokens_per_s')} — device-resident slot "
             "state stopped paying for itself")
    if os.environ.get("BENCH_STRICT") and \
            tp["tokens_per_s"] < MIN_DECODE_TOKENS_PER_S:
        fail(f"decode {tp['tokens_per_s']} tok/s < PR 1 absolute baseline "
             f"{MIN_DECODE_TOKENS_PER_S} on the smoke config (BENCH_STRICT)")

    # ---- quantized bank (int8/int4) -------------------------------------
    for scheme, g in QUANT_GATES.items():
        if agg.get(f"{scheme}_vs_dense", 1.0) > g["vs_dense"]:
            fail(f"analytic {scheme} sparse admission at "
                 f"{agg.get(f'{scheme}_vs_dense')}x the bf16 dense bytes "
                 f"> {g['vs_dense']}x ceiling")
        if agg.get(f"{scheme}_vs_sparse", 1.0) > g["vs_sparse"]:
            fail(f"analytic {scheme} sparse admission at "
                 f"{agg.get(f'{scheme}_vs_sparse')}x the bf16 sparse bytes "
                 f"> {g['vs_sparse']}x ceiling")
        qadm = record(serve, f"admission.quant_{scheme}")
        if qadm.get("path") != "quant_sparse":
            fail(f"{scheme} cold admission took the {qadm.get('path')!r} "
                 "path — the quantized k-sparse kernel is not being "
                 "exercised")
        got = qadm.get("bank_bytes_per_request", 0)
        ref_b = qadm.get("none_bytes_per_request", 0)
        if not (0 < got <= g["measured_vs_none"] * ref_b):
            fail(f"{scheme} admission read {got} bank B/req vs bf16 "
                 f"{ref_b} — outside (0, {g['measured_vs_none']}x] "
                 "(quantization must actually cut the measured read)")
        qstore = record(serve, f"admission.quant_store_{scheme}")
        if qstore.get("path") != "quant_store" or \
                qstore.get("bank_bytes_per_request", -1) != 0:
            fail(f"store-record {scheme} admission path="
                 f"{qstore.get('path')!r} read "
                 f"{qstore.get('bank_bytes_per_request')} B/req — "
                 "graduated quantized records must admit with ZERO bank "
                 "reads")
        qdec = record(serve, f"decode.quant_{scheme}")
        if qdec.get("token_agreement", 0) < g["token_agreement"]:
            fail(f"{scheme} greedy decode agreed on "
                 f"{qdec.get('token_agreement')} of tokens < "
                 f"{g['token_agreement']} floor")
        if scheme == "int4" and \
                qdec.get("step_agreement", 0) < MIN_INT4_STEP_AGREEMENT:
            fail(f"int4 per-step agreement {qdec.get('step_agreement')} < "
                 f"{MIN_INT4_STEP_AGREEMENT}")
        if not (0 < qdec.get("resident_bytes", 0)
                < qdec.get("none_resident_bytes", 0)):
            fail(f"{scheme} engine resident bytes "
                 f"{qdec.get('resident_bytes')} not below the bf16 "
                 f"engine's {qdec.get('none_resident_bytes')} — dropping "
                 "the bf16 bank stopped paying for itself")
        if os.environ.get("BENCH_STRICT") and \
                qdec.get("tokens_per_s", 0) < \
                MIN_QUANT_VS_NONE_TPS * qdec.get("none_tokens_per_s", 0):
            fail(f"{scheme} decode {qdec.get('tokens_per_s')} tok/s < "
                 f"{MIN_QUANT_VS_NONE_TPS}x the same-run bf16 rate "
                 f"{qdec.get('none_tokens_per_s')} (BENCH_STRICT)")

    # ---- continuous batching (paged KV + adapter-slot memory) -----------
    cbp = record(serve, "cb.parity")
    if not cbp.get("tokens_equal"):
        fail("continuous-batching tokens != windowed tokens — the paged "
             "engine must be BITWISE identical per request")
    if cbp.get("step_traces") != 1:
        fail(f"continuous decode step traced {cbp.get('step_traces')} "
             "times — admissions/preemptions/resumes must reuse ONE "
             "compiled program")
    cbo = record(serve, "cb.occupancy")
    if cbo.get("continuous", 0) <= cbo.get("windowed", 1):
        fail(f"continuous slot occupancy {cbo.get('continuous')} <= "
             f"windowed {cbo.get('windowed')} — continuous batching "
             "stopped filling freed slots mid-decode")
    if cbo.get("continuous_stranded", 1) >= cbo.get("windowed_stranded", 0):
        fail(f"continuous stranded slot-steps {cbo.get('continuous_stranded')}"
             f" >= windowed {cbo.get('windowed_stranded')} — short requests "
             "are still waiting out the wave straggler")
    cbt = record(serve, "cb.tok_s_vs_windowed")
    if cbt.get("ratio", 0) <= 0:
        fail("continuous-vs-windowed tok/s ratio is not positive")
    if os.environ.get("BENCH_STRICT") and \
            cbt.get("ratio", 0) < MIN_CB_TOK_S_RATIO:
        fail(f"continuous decode at {cbt.get('ratio')}x windowed tok/s < "
             f"{MIN_CB_TOK_S_RATIO}x floor (BENCH_STRICT)")

    # ---- self-speculative decoding (bare-PLM draft, adapted verify) -----
    spp = record(serve, "spec.parity")
    if not spp.get("tokens_equal"):
        fail("speculative greedy tokens != plain greedy tokens — "
             "draft/verify/commit must be BITWISE per request")
    if not spp.get("adversarial_tokens_equal"):
        fail("adversarial-profile speculative tokens != plain — the "
             "rejection fallback must be the verifier's own argmax")
    if spp.get("step_traces") != 1:
        fail(f"spec decode step traced {spp.get('step_traces')} times — "
             "draft+verify must stay ONE compiled program")
    spa = record(serve, "spec.acceptance")
    if spa.get("committed_per_device_step", 0) <= \
            MIN_SPEC_COMMITTED_PER_STEP:
        fail(f"spec committed {spa.get('committed_per_device_step')} "
             f"tokens/device-step <= {MIN_SPEC_COMMITTED_PER_STEP} — "
             "speculation is not amortizing decode steps")
    if not (MIN_SPEC_ACCEPTANCE <= spa.get("acceptance_rate", -1) <= 1.0):
        fail(f"spec acceptance rate {spa.get('acceptance_rate')} outside "
             f"[{MIN_SPEC_ACCEPTANCE}, 1]")
    if spa.get("adversarial_acceptance_rate", 1.0) >= 1.0:
        fail("the adversarial profile forced no rejections — the "
             "reject/fallback path is not being measured")
    spt = record(serve, "spec.tok_s_vs_plain")
    if spt.get("spec_device_steps", 1) >= spt.get("plain_device_steps", 0):
        fail(f"spec used {spt.get('spec_device_steps')} device steps >= "
             f"plain's {spt.get('plain_device_steps')} — the same tokens "
             "must take strictly fewer steps")
    if os.environ.get("BENCH_STRICT") and \
            spt.get("ratio", 0) < MIN_SPEC_TOK_S_RATIO:
        fail(f"spec decode at {spt.get('ratio')}x plain tok/s < "
             f"{MIN_SPEC_TOK_S_RATIO}x floor (BENCH_STRICT)")

    # ---- heterogeneous adapter-type bank (typed segments) ---------------
    htp = record(serve, "hetero.parity")
    if not htp.get("tokens_equal"):
        fail("hetero engine tokens != composed dense reference — "
             "cross-segment aggregation / composed apply / prefix "
             "hydration must be BITWISE per emitted token")
    if htp.get("step_traces") != 1:
        fail(f"hetero decode step traced {htp.get('step_traces')} times — "
             "typed entries must serve through ONE compiled program")
    if not htp.get("prefix_on_requests") or \
            not htp.get("prefix_off_requests"):
        fail(f"hetero workload did not exercise both prefix paths "
             f"(on={htp.get('prefix_on_requests')}, "
             f"off={htp.get('prefix_off_requests')})")
    hta = record(serve, "hetero.admission")
    if hta.get("path") != "sparse":
        fail(f"hetero cold admission took the {hta.get('path')!r} path — "
             "the unified-space k-sparse fast path is not being exercised")
    for col, v in hta.items():
        if col.startswith("record_bytes_") and v <= 0:
            fail(f"hetero admission {col} = {v} — a typed segment "
                 "contributed no record bytes")
    htk = record(serve, "hetero.kernel_parity")
    for t, ok in htk.items():
        if t != "name" and not ok:
            fail(f"hetero kernel parity broken for {t!r}: interpret != "
                 "ref on the admitted entries")

    # ---- training lifecycle (roster / onboarding / gang-step) -----------
    tsync = record(train, "train.host_syncs")
    if tsync.get("syncs_per_step", 1.0) >= MAX_SYNCS_PER_TRAIN_STEP:
        fail(f"{tsync.get('syncs_per_step')} host syncs per TRAIN step — "
             "metrics are not staying device-resident between boundaries")
    life = record(train, "onboard.lifecycle")
    if life.get("graduated", 0) <= 0:
        fail("onboarding graduated zero profiles")
    if life.get("graduated", 0) + life.get("evicted", 0) != \
            life.get("profiles", -1):
        fail(f"onboarding lost profiles: {life.get('graduated')} graduated "
             f"+ {life.get('evicted')} evicted != {life.get('profiles')} "
             "streamed")
    if life.get("retraces", 1) != 0:
        fail(f"gang step retraced {life.get('retraces')} times across "
             f"{life.get('admission_waves')} admission waves — slot "
             "admission must not invalidate the jitted step")
    rt = record(train, "graduation.roundtrip")
    if not rt.get("ok"):
        fail("graduation roundtrip is not bit-exact: persisted store masks "
             "differ from the trained profiles'")
    if os.environ.get("BENCH_STRICT") and \
            life.get("profiles_per_min", 0) < MIN_PROFILES_PER_MIN:
        fail(f"onboarding {life.get('profiles_per_min')} profiles/min < "
             f"absolute floor {MIN_PROFILES_PER_MIN} on the smoke config "
             "(BENCH_STRICT)")

    q8 = record(serve, "admission.quant_int8")
    q4 = record(serve, "admission.quant_int4")
    print(f"check_bench: OK — admission reduction {agg['reduction']}x "
          f"(int8 {q8['vs_none']}x / int4 {q4['vs_none']}x of bf16 sparse "
          f"bytes, int8 agreement "
          f"{record(serve, 'decode.quant_int8')['token_agreement']}), "
          f"cache-hit admission {warm['bank_bytes_per_request']} B/req "
          f"(hit rate {warm['hit_rate']}), prefill occupancy "
          f"{pre['occupancy']}, {sync['syncs_per_token']} syncs/token, "
          f"decode {tp['tokens_per_s']} tok/s "
          f"(per-token-sync baseline {base.get('tokens_per_s')}); "
          f"continuous batching bitwise OK, occupancy "
          f"{cbo['windowed']} -> {cbo['continuous']}, stranded "
          f"{cbo['windowed_stranded']} -> {cbo['continuous_stranded']}, "
          f"{cbt['ratio']}x tok/s; "
          f"train {tsync['syncs_per_step']} syncs/step, onboarding "
          f"{life['graduated']}/{life['profiles']} graduated @ "
          f"{life['profiles_per_min']} profiles/min, {life['retraces']} "
          f"gang retraces; speculative decode bitwise OK at "
          f"{spa['committed_per_device_step']} committed tokens/step "
          f"(acceptance {spa['acceptance_rate']}, adversarial "
          f"{spa['adversarial_acceptance_rate']}), megakernel parity "
          "bitwise on all 4 routes")


def _fmt(recs: dict, name: str, key: str, label: str):
    """One `label value` fragment, or None when the record/key is absent
    (summary mode tolerates partial artifacts)."""
    v = recs.get(name, {}).get(key)
    return None if v is None else f"{label} {v}"


def _gate_families() -> list:
    """Re-run the gates over whatever artifacts exist; returns the list of
    failing family groups (empty = all present families pass). SystemExit
    from fail() is caught per group so one failing family can't mask
    another's verdict in the summary read-out."""
    present = {f for f in FAMILIES if os.path.exists(family_path(f))}
    failures = []

    def run(label, fn):
        try:
            fn()
        except SystemExit as e:
            if e.code:
                failures.append(label)
        except Exception as exc:  # corrupt artifact == failing gate
            print(f"check_bench: FAIL — {label}: {exc}")
            failures.append(label)

    if {"kernels", "serve", "train"} <= present:
        # main() gates the three bench-smoke families together (fault and
        # obs opportunistically) — run it once, attribute to the group
        run("kernels/serve/train", main)
    else:
        # partial artifact sets stay tolerated (the absent families are
        # already marked in the read-out) — gate what exists
        if "fault" in present:
            run("fault", lambda: check_fault(load_family("fault")))
        if "obs" in present:
            run("obs", lambda: check_obs(load_family("obs")))
    return failures


def summary():
    """One consolidated line per family from whatever artifacts exist;
    absent families are marked with the `make` target that produces them.
    The read-out is ALSO a gate: any present family whose checks fail
    exits non-zero (a green summary can be trusted in CI)."""
    digests = {
        "kernels": [
            ("mask_aggregate.sparse_ref", "tpu_win", "sparse-agg win"),
            ("fused_adapter.pallas_interpret", "tpu_win", "fused-adapter"),
            ("mask_aggregate_quant_int4.pallas_interpret", "tpu_win",
             "int4-agg"),
            ("decode_fused.bf16.pallas_interpret", "tpu_win",
             "megakernel-act"),
        ],
        "serve": [
            ("admission.aggregate_bytes", "reduction", "admission"),
            ("decode.throughput", "tokens_per_s", "decode tok/s"),
            ("cb.tok_s_vs_windowed", "ratio", "cb ratio"),
            ("spec.acceptance", "committed_per_device_step",
             "spec tokens/step"),
            ("spec.acceptance", "acceptance_rate", "acceptance"),
            ("hetero.parity", "tokens_equal", "hetero parity"),
            ("hetero.admission", "bank_bytes_per_request",
             "hetero bank B/req"),
        ],
        "train": [
            ("train.host_syncs", "syncs_per_step", "syncs/step"),
            ("onboard.lifecycle", "graduated", "graduated"),
            ("onboard.lifecycle", "profiles_per_min", "profiles/min"),
        ],
        "fault": [
            ("resilience.serve_chaos", "degraded_requests", "degraded"),
            ("resilience.serve_chaos", "corrupt_detected",
             "corrupt caught"),
            ("resilience.onboard_quarantine", "quarantined", "quarantined"),
        ],
        "obs": [
            ("obs.parity", "tokens_equal", "parity"),
            ("obs.parity", "syncs_per_token_on", "syncs/token"),
            ("obs.trace", "categories", "trace cats"),
            ("obs.overhead", "ratio", "obs-on tok/s ratio"),
        ],
    }
    for family, target in FAMILIES.items():
        path = family_path(family)
        if not os.path.exists(path):
            print(f"{family:7s} — missing: run `make {target}` first")
            continue
        with open(path) as f:
            data = json.load(f)
        recs = {r["name"]: r for r in data.get("records", [])}
        parts = [p for n, k, lbl in digests[family]
                 for p in [_fmt(recs, n, k, lbl)] if p]
        body = ", ".join(parts) if parts else "no gated records"
        print(f"{family:7s} — {len(recs)} records: {body}")
        prov = data.get("provenance")
        if prov:
            mesh = prov.get("mesh_shape")
            print(f"        provenance: jax {prov.get('jax_version')}, "
                  f"{prov.get('device_count')}x "
                  f"{prov.get('device_kind')} ({prov.get('platform')}), "
                  f"mesh {mesh if mesh else '1-device'}, "
                  f"git {prov.get('git_sha') or '?'}, "
                  f"config {prov.get('config_hash', '?')}")
    failures = _gate_families()
    if failures:
        print(f"check_bench: summary gate FAILED — {', '.join(failures)}")
        sys.exit(1)


if __name__ == "__main__":
    if "--summary" in sys.argv:
        summary()
    else:
        main(fault_only="--fault-only" in sys.argv)
