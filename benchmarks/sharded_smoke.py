"""8-fake-device serve + onboard smoke: bitwise parity with 1 device.

Runs the SAME engine/trainer code twice — once unsharded, once on a
(data=N/2, model=2) mesh over N forced host CPU devices (default N=8,
i.e. 4x2; on an accelerator the N real devices instead) — and checks:

- onboarding: the graduated `ProfileStore` records (packed mask bytes,
  fp16 LN affines) are byte-identical,
- serving:    the admission-time aggregated Â/B̂ cache entries are
              bit-identical and the decoded token ids equal,
- continuous: the paged continuous-batching engine decodes the skewed
              workload bitwise-identical on the mesh (pages shard like
              the slot axis, so the page-table gather/scatter never
              crosses a device boundary) with its step compiled once,

- memory:     per-device resident bytes on the mesh strictly below the
              single-device total (the mesh really shards state),

plus throughput for both paths. Prints ONE JSON line (the last stdout
line); ``--check`` exits 1 unless every bit above holds.

Standalone (also how CI's multi-device job and tests/test_distributed.py
invoke it):

  PYTHONPATH=src:. python benchmarks/sharded_smoke.py --check
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_DEVICE_COUNT_FLAG = "xla_force_host_platform_device_count"


def strip_device_count_flag(flags: str) -> str:
    """Drop any --xla_force_host_platform_device_count token, keeping every
    other compiler flag (measurements must share the caller's XLA settings)."""
    return " ".join(t for t in flags.split() if _DEVICE_COUNT_FLAG not in t)


def run_subprocess(*, check: bool = False, timeout: int = 1200) -> dict:
    """Run this smoke in a fresh subprocess and return its parsed JSON
    record — the entry point the tests use (the smoke must force its own
    device count before jax initializes, so it can never run in an
    already-started jax process — on a CPU host only: a chip belongs to
    one process, so run the script itself there)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    kept = strip_device_count_flag(env.get("XLA_FLAGS", ""))
    if kept:
        env["XLA_FLAGS"] = kept
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, os.path.abspath(__file__)]
    if check:
        cmd.append("--check")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=root, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"sharded_smoke failed:\nSTDOUT:{r.stdout}\n"
                           f"STDERR:{r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count on a CPU host (an "
                    "accelerator host uses all its devices); the mesh is "
                    "(devices/2, 2) over (data, model) and the roster/"
                    "slot count equals the data axis")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless every parity bit holds")
    args = ap.parse_args()
    if args.devices < 4 or args.devices % 2:
        ap.error("--devices must be an even number >= 4")

    # must happen before the first jax import in this process; --devices is
    # authoritative (any inherited device-count token is replaced, other
    # compiler flags carry over). The flag only shapes the CPU backend:
    # the platform is whatever JAX finds, so a chip host runs on its chips
    kept = strip_device_count_flag(os.environ.get("XLA_FLAGS", ""))
    want = f"--{_DEVICE_COUNT_FLAG}={args.devices}"
    os.environ["XLA_FLAGS"] = (kept + " " + want).strip()

    import numpy as np
    import jax

    if jax.default_backend() != "cpu":
        args.devices = jax.device_count()
        if args.devices < 4 or args.devices % 2:
            ap.error(f"{args.devices} {jax.default_backend()} devices: "
                     "need an even number >= 4")

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.profiles import ProfileStore
    from repro.data import MarkovLM
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request
    from repro.train import GraduationPolicy
    from repro.train.onboarding import build_onboarding_run

    assert jax.device_count() >= args.devices, jax.device_count()
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    data_ax = args.devices // 2
    mesh = make_mesh((data_ax, 2), ("data", "model"))
    mesh_str = f"{data_ax}x2:data,model"
    n_prof, slots = 4, data_ax

    # ---------------------------------------------------------- onboarding
    def onboard(mesh_):
        data = MarkovLM(cfg.vocab_size, n_prof, seed=1)
        policy = GraduationPolicy(min_steps=3, max_steps=5, target_acc=2.0)
        trainer, gang = build_onboarding_run(
            cfg, data, range(n_prof), slots=slots, per_slot=2, seq_len=8,
            policy=policy, lr=5e-2, seed=0, rng=jax.random.key(1),
            log_every=50, mesh=mesh_)
        trainer.run_until_drained(max_steps=200)
        assert len(trainer.scheduler.graduated) == n_prof
        return (trainer.scheduler.store, trainer.state["frozen"],
                gang.trace_counter["traces"])

    store1, frozen, traces1 = onboard(None)
    store8, _, traces8 = onboard(mesh)

    def store_records_equal(a: ProfileStore, b: ProfileStore) -> bool:
        if a.profile_ids() != b.profile_ids():
            return False
        for pid in a.profile_ids():
            ra, rb = a._rec[pid], b._rec[pid]
            if sorted(ra) != sorted(rb):
                return False
            for key in ra:
                if ra[key].dtype != rb[key].dtype or \
                        not np.array_equal(ra[key], rb[key]):
                    return False
        return True

    onboard_ok = store_records_equal(store1, store8)

    # ------------------------------------------------------------- serving
    def serve(mesh_):
        eng = ServeEngine(cfg, frozen, store1, max_slots=slots, max_seq=64,
                          sync_every=4, mesh=mesh_)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size, size=5 + i),
                        profile_id=i % n_prof, max_new_tokens=8)
                for i in range(2 * slots)]
        eng.run_until_drained(list(reqs))  # warm up every jit variant
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size, size=5 + i),
                        profile_id=i % n_prof, max_new_tokens=8)
                for i in range(2 * slots)]
        t0 = time.perf_counter()
        eng.run_until_drained(list(reqs))
        dt = time.perf_counter() - t0
        toks = [list(map(int, r.generated)) for r in reqs]
        entries = {pid: {k: np.asarray(v) for k, v in
                         eng.profile_cache.peek(pid).items()}
                   for pid in range(n_prof)}
        n_tok = sum(len(t) for t in toks)
        return toks, entries, round(n_tok / dt, 1), \
            eng.resident_bytes_per_device()

    toks1, ent1, tps1, bytes1 = serve(None)
    toks8, ent8, tps8, bytes8 = serve(mesh)

    entries_ok = all(
        np.array_equal(ent1[pid][k], ent8[pid][k])
        for pid in ent1 for k in ent1[pid])
    tokens_ok = toks1 == toks8

    # ------------------------------------------------- continuous batching
    # the paged engine holds the same mesh-vs-single bitwise contract on
    # the skewed workload (mid-decode admission + page reuse included)
    from benchmarks.cb_smoke import skewed_requests

    def serve_cb(mesh_):
        eng = ServeEngine(cfg, frozen, store1, max_slots=slots, max_seq=64,
                          sync_every=4, continuous=True, page_size=8,
                          mesh=mesh_)
        eng.run_until_drained(skewed_requests(cfg, 2 * slots, seed=0,
                                              long_new=16))
        reqs = skewed_requests(cfg, 2 * slots, seed=0, long_new=16)
        eng.run_until_drained(reqs)
        return ({r.uid: list(map(int, r.generated)) for r in reqs},
                eng.serve_stats()["step_traces"])

    cb_toks1, cb_tr1 = serve_cb(None)
    cb_toks8, cb_tr8 = serve_cb(mesh)
    cb_tokens_ok = cb_toks1 == cb_toks8

    out = {
        "devices": args.devices,
        "mesh": mesh_str,
        "onboard_store_bitwise_equal": bool(onboard_ok),
        "serve_entries_bitwise_equal": bool(entries_ok),
        "decode_tokens_equal": bool(tokens_ok),
        "cb_decode_tokens_equal": bool(cb_tokens_ok),
        "cb_step_traces": {"single": cb_tr1, "sharded": cb_tr8},
        "gang_traces": {"single": traces1, "sharded": traces8},
        "single": {"tokens_per_s": tps1,
                   "resident_bytes_per_device": bytes1},
        "sharded": {"tokens_per_s": tps8,
                    "resident_bytes_per_device": bytes8},
        "sharded_vs_single": round(tps8 / max(tps1, 1e-9), 3),
    }
    print(json.dumps(out))
    cb_ok = cb_tokens_ok and cb_tr1 == 1 and cb_tr8 == 1
    mem_ok = 0 < bytes8["total"] < bytes1["total"]
    if args.check and not (onboard_ok and entries_ok and tokens_ok
                           and cb_ok and mem_ok):
        print("sharded_smoke: PARITY FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
