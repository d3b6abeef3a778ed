# Repo verification entry points. `make verify` is what CI runs
# (.github/workflows/ci.yml): the FULL tier-1 test suite (the 7 seed-era
# multi-device failures were jax-version API breaks, fixed in PR 2 — no
# deselects remain) plus a kernel/serve/train bench smoke that gates on
# BENCH_*.json emission, and the onboarding smoke (--onboard through the
# launcher: roster admission, graduation, store emission).

PY      := python
PP      := PYTHONPATH=src:.

.PHONY: verify test bench-smoke onboard-smoke multidev-smoke quant-smoke \
	chaos-smoke cb-smoke spec-smoke hetero-smoke obs-smoke bench

test:
	PYTHONPATH=src $(PY) -m pytest -q

bench-smoke:
	$(PP) $(PY) benchmarks/kernel_bench.py --smoke
	$(PP) $(PY) benchmarks/serve_bench.py --smoke
	$(PP) $(PY) benchmarks/train_bench.py --smoke
	$(PP) $(PY) benchmarks/check_bench.py

onboard-smoke:
	PYTHONPATH=src $(PY) -m repro.launch.train --onboard --smoke \
		--arch qwen1.5-0.5b --profiles 6 --roster-slots 2 \
		--per-slot-batch 2 --seq 16 --graduate-min-steps 4 \
		--graduate-max-steps 10 --steps 200 \
		--store-out /tmp/onboard_smoke_store.npz

# 8-fake-device CPU mesh: serve + onboard must be BITWISE identical to the
# 1-device path and shard per-device bytes (the script forces its own
# device-count XLA flag). Not a verify dep: the tier-1 suite
# (test_distributed) already runs the same vehicle — this target is the
# standalone entry the CI multi-device job and humans use.
multidev-smoke:
	$(PP) $(PY) benchmarks/sharded_smoke.py --check

# quantized-bank smoke: none/int8/int4 engines end to end — admission
# byte ceilings, int8 greedy-decode agreement, zero-bank-read admission
# from graduated quantized records, per-device residency reduction. The
# BENCH json quant rows are gated by check_bench inside bench-smoke; this
# is the fast standalone probe (also a CI job).
quant-smoke:
	$(PP) $(PY) benchmarks/quant_smoke.py

# chaos soak (PR 6 resilience layer): a seeded FaultPlan injects >= 20%
# persistent hydration failures, 2 corrupted store records, NaN-poisoned
# roster slots and a torn checkpoint; check_bench --fault-only gates the
# degradation contract (every wave completes, degraded == planned, corrupt
# never served, unaffected requests bitwise, quarantine accounting closes).
# 8 forced host devices so the elastic-reshard record is emitted too.
chaos-smoke:
	$(PP) XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) benchmarks/fault_bench.py --smoke
	$(PP) $(PY) benchmarks/check_bench.py --fault-only

# continuous-batching smoke (PR 7 paged engine): the windowed and the
# paged continuous engine drain the SAME skewed-length workload; gates are
# bitwise token parity, strictly higher slot occupancy / lower stranded
# slot-steps, and one decode trace across admissions/preemptions/resumes.
# The >= 1.3x tok/s floor applies under BENCH_STRICT=1 only (shared CI
# wall clock varies). The same numbers land in BENCH_serve.json (cb.*
# records, gated by check_bench inside bench-smoke).
cb-smoke:
	$(PP) $(PY) benchmarks/cb_smoke.py --check

# self-speculative decoding smoke (ISSUE 8): the bare PLM (zero-adapter
# view, zero extra weight memory) drafts gamma tokens per slot, the
# adapted model verifies them in ONE batched step. Gates: greedy spec
# output BITWISE equal plain greedy per request — on the normal workload
# AND with an adversarial profile that forces rejections — one compiled
# decode step, committed tokens per device step > 1, strictly fewer
# device steps than plain. The spec-vs-plain tok/s floor applies under
# BENCH_STRICT=1 only (CPU toy shapes are compute-bound; verify costs
# gamma+1 tokens of FLOPs). The same numbers land in BENCH_serve.json
# (spec.* records, gated by check_bench inside bench-smoke).
spec-smoke:
	$(PP) $(PY) benchmarks/spec_smoke.py --check

# heterogeneous adapter-bank smoke (ISSUE 9): typed segments — bottleneck /
# LoRA / IA3 / prefix — tile ONE unified mask index space; mixed-type
# profiles admit through the k-sparse fast path, prefix KV rows hydrate
# into the paged cache, and decode stays ONE compiled program. Gates:
# engine tokens BITWISE equal a composed dense reference, prefix-on AND
# prefix-off admissions both exercised, per-type record bytes positive,
# per-type interpret-vs-ref kernel parity. The same numbers land in
# BENCH_serve.json (hetero.* records, gated by check_bench inside
# bench-smoke).
hetero-smoke:
	$(PP) $(PY) benchmarks/hetero_smoke.py --check

# observability smoke (ISSUE 10): the SAME serve workload runs obs-off and
# obs-on (retrace sentinel in raise mode), plus a small onboarding run on
# the shared bundle. Gates: obs-on decode tokens BITWISE equal obs-off,
# host syncs/token and decode jit traces EXACTLY unchanged (obs adds zero
# syncs, zero retraces), the exported Chrome trace validates with >= 6
# span categories (admission / prefill / decode-window / gang-step /
# graduation / resilience), and the TTFT / decode-latency / admission-wait
# / gang-step histograms carry p50/p99. The obs-on tok/s floor applies
# under BENCH_STRICT=1 only. Emits BENCH_obs.json (obs.* records, gated by
# check_bench) and BENCH_obs_trace.json (open in Perfetto).
obs-smoke:
	$(PP) $(PY) benchmarks/obs_smoke.py --check

bench:
	$(PP) $(PY) benchmarks/run.py

verify: test bench-smoke onboard-smoke quant-smoke chaos-smoke cb-smoke \
	spec-smoke hetero-smoke obs-smoke
	@echo "verify: OK"
