"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --mode xpeft --steps 100 --batch 8 --seq 64 --smoke \
      --ckpt-dir /tmp/ck

--onboard switches to the profile-lifecycle flow: stream P >> S profiles
through an S-slot device-resident roster (train/roster.py), graduating
converged profiles into a serving ProfileStore:

  PYTHONPATH=src python -m repro.launch.train --onboard --smoke \
      --arch qwen1.5-0.5b --profiles 12 --roster-slots 4 \
      --store-out /tmp/profiles.npz --ckpt-dir /tmp/ck

--smoke uses the reduced config (CPU-runnable); the full config is for real
accelerators. On several chips also pass --mesh to enable pjit sharding.
"""
from __future__ import annotations

import argparse

import jax


def run_onboarding(args):
    """--onboard: stream P >> S profiles through an S-slot roster and
    graduate converged profiles into a ProfileStore (train→serve loop)."""
    from repro import obs as OBS
    from repro.configs import get_config, reduce_for_smoke
    from repro.data import MarkovLM, ProfileClassification
    from repro.distributed.fault import PreemptionHandler
    from repro.launch.mesh import parse_mesh
    from repro.train import GraduationPolicy
    from repro.train.onboarding import build_onboarding_run

    obs = OBS.from_cli_args(args)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.num_labels:
        cfg = cfg.with_(num_labels=args.num_labels)
    mesh = parse_mesh(args.mesh)
    if mesh is not None:
        print(f"onboarding on mesh {dict(mesh.shape)} "
              f"({mesh.size} devices; roster slots over 'data')")

    if cfg.num_labels:
        source = ProfileClassification(cfg.vocab_size, cfg.num_labels,
                                       num_profiles=args.profiles,
                                       seed=args.seed)
    else:
        source = MarkovLM(cfg.vocab_size, args.profiles, seed=args.seed)
    policy = GraduationPolicy(
        min_steps=args.graduate_min_steps, max_steps=args.graduate_max_steps,
        ema_decay=args.ema_decay,
        target_loss=args.target_loss, target_acc=args.target_acc)
    trainer, gang = build_onboarding_run(
        cfg, source, range(args.profiles), slots=args.roster_slots,
        per_slot=args.per_slot_batch, seq_len=args.seq, policy=policy,
        lr=args.lr, seed=args.seed, mesh=mesh,
        store_path=args.store_out or None,
        ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
        preemption=PreemptionHandler(),
        log_every=args.log_every, obs=obs)
    scheduler, store = trainer.scheduler, trainer.scheduler.store
    if args.resume and trainer.try_resume():
        print(f"resumed onboarding from step {trainer.step}: "
              f"{scheduler.stats()}")
    trainer.run_until_drained(max_steps=args.steps)
    st = scheduler.stats()
    print(f"onboarding done at step {trainer.step}: "
          f"{st['graduated']} graduated, {st['evicted']} evicted, "
          f"{st['in_training']} in training, {st['pending']} pending, "
          f"gang-step traces {gang.trace_counter['traces']}, "
          f"host syncs/step "
          f"{trainer.host_syncs / max(trainer.step, 1):.3f}")
    if args.store_out:
        store.save(args.store_out)
        print(f"wrote {args.store_out}: {len(store.profile_ids())} profiles, "
              f"{store.bytes_per_profile()} B/profile (masks)")
    if obs is not None:
        obs.export(args.metrics_json or None, args.trace or None)
        cats = obs.tracer.category_counts()
        print(f"obs: {sum(cats.values())} trace events {cats}; "
              f"retrace watches {obs.sentinel.counts()}")
    if st["graduated"] == 0:
        raise SystemExit("onboarding graduated zero profiles")
    if not scheduler.finished():
        # the --steps backstop cut the stream short: in-slot / queued
        # profiles never reached the store — that must not look like success
        raise SystemExit(
            f"onboarding truncated by --steps {args.steps}: "
            f"{st['in_training']} profiles still in slots, "
            f"{st['pending']} pending — raise --steps (or --resume from "
            "the checkpoint) to finish the stream")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--mode", default="xpeft",
                    choices=["xpeft", "adapter", "head_only"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--profiles", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2:data,model — enable pjit sharding")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # --onboard: profile-lifecycle flow (roster/onboarding/gang-step)
    ap.add_argument("--onboard", action="store_true",
                    help="stream --profiles through a roster, graduating "
                         "converged profiles into --store-out")
    ap.add_argument("--roster-slots", type=int, default=4)
    ap.add_argument("--per-slot-batch", type=int, default=4)
    ap.add_argument("--num-labels", type=int, default=0,
                    help="add a classification head (0 = LM objective)")
    ap.add_argument("--graduate-min-steps", type=int, default=20)
    ap.add_argument("--graduate-max-steps", type=int, default=80)
    ap.add_argument("--target-loss", type=float, default=None)
    ap.add_argument("--target-acc", type=float, default=None)
    ap.add_argument("--ema-decay", type=float, default=0.9)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--store-out", default="")
    from repro import obs as OBS
    OBS.add_cli_args(ap)  # --metrics-json PATH, --trace PATH
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.onboard:
        run_onboarding(args)
        return

    from repro import obs as OBS
    from repro.configs import get_config, reduce_for_smoke
    from repro.data import MarkovLM
    from repro.data.loader import ShardedLoader
    from repro.distributed import ctx
    from repro.distributed.fault import PreemptionHandler
    from repro.distributed.sharding import (batch_specs, param_specs,
                                            to_shardings)
    from repro.train.steps import init_train_state, make_train_step
    from repro.train.trainer import Trainer

    obs = OBS.from_cli_args(args)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    cfg = cfg.with_xpeft(max_profiles=max(args.profiles, 2))

    key = jax.random.key(args.seed)
    state = init_train_state(key, cfg, args.mode)
    step = make_train_step(cfg, args.mode, lr=args.lr)

    if args.mesh:
        from repro.launch.mesh import parse_mesh
        mesh = parse_mesh(args.mesh)
        cm = ctx.mesh_context(mesh)
        cm.__enter__()
        st_sh = to_shardings(param_specs(state, mesh), mesh)
        step = jax.jit(step, in_shardings=(st_sh, None, None),
                       out_shardings=(st_sh, None))
    else:
        step = jax.jit(step)

    loader = ShardedLoader(
        MarkovLM(cfg.vocab_size, args.profiles, seed=args.seed),
        args.batch, args.seq)
    trainer = Trainer(step, state, loader,
                      ckpt_dir=args.ckpt_dir or None,
                      ckpt_every=args.ckpt_every,
                      preemption=PreemptionHandler(),
                      rng=jax.random.key(args.seed + 1), obs=obs)
    if args.resume and trainer.try_resume():
        print(f"resumed from step {trainer.step}")
    hist = trainer.run(args.steps)
    if hist:
        print(f"final loss {hist[-1]['loss']:.4f} "
              f"(stragglers: {trainer.watchdog.slow_steps})")
    if obs is not None:
        obs.export(args.metrics_json or None, args.trace or None)
        cats = obs.tracer.category_counts()
        print(f"obs: {sum(cats.values())} trace events {cats}; "
              f"retrace watches {obs.sentinel.counts()}")


if __name__ == "__main__":
    main()
