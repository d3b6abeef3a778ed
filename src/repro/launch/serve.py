"""Serving launcher: continuous-batching multi-profile inference demo on
the layered engine (scheduler / slot-state / profile-cache).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --requests 8 --slots 4 --sync-every 8

Multi-device (same engine code, GSPMD-sharded; on CPU validate with
XLA_FLAGS=--xla_force_host_platform_device_count=8):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --smoke --mesh 4x2:data,model
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax


def build_engine(cfg, *, profiles: int, slots: int, max_seq: int,
                 seed: int = 0, mesh=None, obs=None, **engine_kw):
    """Random-init PLM + bank from ``seed``, ``profiles`` random profiles
    in a ProfileStore, and a ServeEngine over them (``engine_kw`` pass
    through: precompute, sync_every, cache_bytes, continuous, page_size,
    ...). The one assembly this launcher and ``chip_smoke.py`` share."""
    from repro.core import xpeft as XP
    from repro.core.profiles import ProfileStore
    from repro.models import init_lm
    from repro.serve.engine import ServeEngine

    key = jax.random.key(seed)
    params = init_lm(key, cfg)
    xp = cfg.xpeft
    store = ProfileStore(cfg.num_layers, xp.num_adapters, xp.bottleneck,
                         xp.mask_type, xp.k)
    table = XP.init_profile_table(key, cfg)
    for pid in range(profiles):
        store.add_profile(pid, jax.tree.map(lambda t: t[pid], table))
    return ServeEngine(cfg, params, store, max_slots=slots, max_seq=max_seq,
                       mesh=mesh, obs=obs, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--profiles", type=int, default=4)
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode steps between host syncs (device-resident "
                    "slot state; 1 = paper-era per-token round trips)")
    ap.add_argument("--cache-mb", type=int, default=64,
                    help="profile-cache capacity in MiB (0 disables)")
    ap.add_argument("--no-precompute", action="store_true",
                    help="paper-faithful per-step mask aggregation")
    ap.add_argument("--mesh", default="",
                    help="e.g. 4x2:data,model — GSPMD-shard the engine "
                    "(slots over data, bank d_model/heads/vocab TP over "
                    "model)")
    from repro import obs as OBS
    OBS.add_cli_args(ap)  # --metrics-json PATH, --trace PATH
    args = ap.parse_args()

    from repro.configs import get_config, reduce_for_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import parse_mesh
    from repro.serve.engine import Request

    enable_compile_cache()
    mesh = parse_mesh(args.mesh)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    obs = OBS.from_cli_args(args)
    eng = build_engine(cfg, profiles=args.profiles, slots=args.slots,
                       max_seq=args.max_seq, mesh=mesh, obs=obs,
                       precompute=not args.no_precompute,
                       sync_every=args.sync_every,
                       cache_bytes=args.cache_mb << 20)
    print(f"profiles: {args.profiles} x {eng.store.bytes_per_profile()} B "
          f"each (masks, byte-level)")
    if mesh is not None:
        rb = eng.resident_bytes_per_device()
        print(f"mesh {dict(mesh.shape)}: {rb['total']} resident B/device "
              f"(params {rb['params']}, cache {rb['cache']})")
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=rng.integers(4, 17)),
                    profile_id=i % args.profiles,
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    steps = eng.run_until_drained(list(reqs))
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests / {toks} tokens in {steps} engine "
          f"steps, {dt:.1f}s ({toks / dt:.1f} tok/s)")
    st = eng.serve_stats()
    print(f"profile cache: hit rate {st['profile_cache']['hit_rate']}, "
          f"{st['profile_cache']['entries']} entries / "
          f"{st['profile_cache']['bytes']} B; "
          f"prefill occupancy {st['prefill_occupancy']} over "
          f"{st['prefill_batches']} batches; "
          f"{st['syncs_per_token']} host syncs/token "
          f"(sync_every={st['sync_every']})")
    for r in reqs[:3]:
        print(f"  req {r.uid} (profile {r.profile_id}): {r.generated}")
    if obs is not None:
        obs.export(args.metrics_json or None, args.trace or None)
        cats = obs.tracer.category_counts()
        print(f"obs: {sum(cats.values())} trace events {cats}; "
              f"retrace watches {obs.sentinel.counts()}")


if __name__ == "__main__":
    main()
