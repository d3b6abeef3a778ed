"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: abstract params
(jax.eval_shape — no allocation), production shardings, GSPMD compile, then
memory_analysis() (fits?) + cost_analysis() (FLOPs/bytes) + collective-bytes
parsing for the §Roofline table.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k \
      --mesh single --variant remat_dots
"""
# The VERY FIRST lines, before any jax-importing module: the dry-run (and
# only the dry-run) needs 512 placeholder devices.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import numpy as np       # noqa: E402
import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import model_flops, roofline_terms  # noqa: E402
from repro.analysis.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.configs import (ASSIGNED_ARCHS, get_config, get_shape,  # noqa: E402
                           shapes_for)
from repro.distributed import ctx  # noqa: E402
from repro.distributed.sharding import (batch_specs, cache_specs,  # noqa: E402
                                        param_specs,
                                        sharded_bytes_per_device,
                                        to_shardings)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as MDL  # noqa: E402
from repro.serve.steps import make_decode_step, make_prefill_step  # noqa: E402
from repro.train.steps import init_train_state, make_train_step  # noqa: E402


# ----------------------------------------------------------------------------
# Variants (the §Perf hillclimb levers — baseline first)
# ----------------------------------------------------------------------------
VARIANTS = {
    "baseline": {},
    "remat_dots": {"cfg": {"remat": "dots"}},
    "remat_none": {"cfg": {"remat": "none"}},
    "no_fsdp": {"fsdp": False},
    "precomputed_adapters": {"precomputed": True},   # decode fast path
    "sparse_k_agg": {"sparse_agg": True},            # k-sparse decode agg
    "soft_masks": {"xpeft": {"mask_type": "soft"}},
    "bank_n_shard": {"overrides": {"bank_a": ("tp_n", None, None),
                                   "bank_b": ("tp_n", None, None)},
                     "logical_map": {"tp_n": "model"}},
    "seq_sp": {"seq_sp": True},  # Megatron-SP residuals (refuted; §Perf)
    # q-seq context parallelism: shard Q's sequence over model and gather
    # K/V per layer (ring-attention-like) instead of per-tile AV reduces
    "cp_qseq": {"act_rules": {"q_seq": "model", "kv_seq": None}},
    # round-2 combos + quantized KV cache
    "cp_qseq_remat_dots": {"act_rules": {"q_seq": "model", "kv_seq": None},
                           "cfg": {"remat": "dots"}},
    "kv_f8": {"cfg": {"cache_dtype": "float8_e4m3fn"}},
    "precomputed_kv_f8": {"precomputed": True,
                          "cfg": {"cache_dtype": "float8_e4m3fn"}},
    # pure FSDP: drop tensor parallelism entirely, shard batch over ALL
    # axes; weights gathered per layer (ZeRO-3). Viable when per-layer
    # weights fit VMEM-adjacent HBM transients (not for 132B dbrx).
    "no_tp": {
        "logical_map": {"vocab": None, "heads": None, "kv_heads": None,
                        "mlp": None, "expert": None, "tp_d": None,
                        "mlp_fsdp": "data"},
        "act_rules": {"batch": ("pod", "data", "model"), "heads": None,
                      "kv_heads": None, "kv_seq": None, "mlp": None,
                      "vocab": None, "expert": None},
    },
    "no_tp_remat_dots": {
        "cfg": {"remat": "dots"},
        "logical_map": {"vocab": None, "heads": None, "kv_heads": None,
                        "mlp": None, "expert": None, "tp_d": None,
                        "mlp_fsdp": "data"},
        "act_rules": {"batch": ("pod", "data", "model"), "heads": None,
                      "kv_heads": None, "kv_seq": None, "mlp": None,
                      "vocab": None, "expert": None},
    },
}


def input_specs(cfg, shape):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, T = shape.global_batch, shape.seq_len
    xp = cfg.xpeft
    L, N, b, d = cfg.num_layers, xp.num_adapters, xp.bottleneck, cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        lab = sds((B,), i32) if cfg.num_labels else sds((B, T), i32)
        batch = {"tokens": sds((B, T), i32), "labels": lab,
                 "profile_ids": sds((B,), i32)}
        if cfg.num_prefix_tokens:
            batch["prefix_embeds"] = sds((B, cfg.num_prefix_tokens, d), dt)
        return batch
    masks = {"w_a": sds((B, L, N), f32), "w_b": sds((B, L, N), f32),
             "ln_scale": sds((B, L, b), f32), "ln_bias": sds((B, L, b), f32)}
    if shape.kind == "prefill":
        out = {"tokens": sds((B, T), i32), "masks": masks}
        if cfg.num_prefix_tokens:
            out["prefix_embeds"] = sds((B, cfg.num_prefix_tokens, d), dt)
        return out
    return {"tokens": sds((B, 1), i32), "cache_pos": sds((), i32),
            "masks": masks}


def _precomputed_masks(cfg, B):
    xp = cfg.xpeft
    L, b, d = cfg.num_layers, xp.bottleneck, cfg.d_model
    dt, f32 = jnp.dtype(cfg.dtype), jnp.float32
    sds = jax.ShapeDtypeStruct
    return {"a_hat": sds((B, L, d, b), dt), "b_hat": sds((B, L, b, d), dt),
            "ln_scale": sds((B, L, b), f32), "ln_bias": sds((B, L, b), f32)}


def _sparse_masks(cfg, B):
    xp = cfg.xpeft
    L, b, k = cfg.num_layers, xp.bottleneck, xp.k
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    return {"idx_a": sds((B, L, k), i32), "w_a": sds((B, L, k), f32),
            "idx_b": sds((B, L, k), i32), "w_b": sds((B, L, k), f32),
            "ln_scale": sds((B, L, b), f32), "ln_bias": sds((B, L, b), f32)}


def _mask_shardings(masks_abs, mesh):
    """Per-request masks: batch over (pod,data); a_hat/b_hat d over model."""
    def one(path, x):
        nd = len(x.shape)
        ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
        n = int(np.prod([mesh.shape[a] for a in ba]))
        first = ba if x.shape[0] % n == 0 and x.shape[0] >= n else None
        spec = [first] + [None] * (nd - 1)
        name = path.rsplit("/", 1)[-1]
        if name == "a_hat" and x.shape[2] % mesh.shape.get("model", 1) == 0:
            spec[2] = "model"
        if name == "b_hat" and x.shape[3] % mesh.shape.get("model", 1) == 0:
            spec[3] = "model"
        return P(*spec)
    from repro.utils import map_with_path
    return map_with_path(one, masks_abs)


# ----------------------------------------------------------------------------
# Cell lowering
# ----------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline", xpeft_n: int = 256):
    vspec = VARIANTS[variant]
    cfg = get_config(arch)
    if cfg.name != "bert-base-xpeft":
        cfg = cfg.with_xpeft(num_adapters=xpeft_n, bottleneck=64)
    if "cfg" in vspec:
        cfg = cfg.with_(**vspec["cfg"])
    if "xpeft" in vspec:
        cfg = cfg.with_xpeft(**vspec["xpeft"])
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = int(np.prod(list(mesh.shape.values())))
    fsdp = vspec.get("fsdp", True)
    sh_kw = {k: vspec[k] for k in ("overrides", "logical_map") if k in vspec}

    act_rules = {}
    if shape.global_batch == 1:
        # batch=1 long-context: sequence parallelism over the data axis —
        # the KV/seq hints must agree with the cache specs
        act_rules = {"seq": "data", "kv_seq": ("data", "model"),
                     "batch": None}
    if "act_rules" in vspec:
        act_rules.update(vspec["act_rules"])
    elif vspec.get("seq_sp") and shape.kind == "train" \
            and shape.seq_len % 16 == 0:
        # Megatron-SP residual sharding — kept as a VARIANT: measured on
        # this GSPMD version it increased collective volume 6x (hypothesis
        # refuted; see EXPERIMENTS.md §Perf).
        act_rules = {"seq": "model"}

    t0 = time.time()
    with ctx.mesh_context(mesh, act_rules=act_rules):
        if shape.kind == "train":
            lowered, state_bytes = _lower_train(cfg, shape, mesh, fsdp, sh_kw)
        else:
            lowered, state_bytes = _lower_serve(cfg, shape, mesh, fsdp, vspec,
                                                sh_kw)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    # XLA's HloCostAnalysis counts while bodies ONCE (verified); re-derive
    # flops/bytes/collectives with trip-count multiplication from the HLO.
    an = hlo_analyze(compiled.as_text())
    flops = an["flops"]
    acc_bytes = an["bytes"]
    colls = an["collectives"]
    terms = roofline_terms(flops, acc_bytes, colls["total"])
    mflops = model_flops(cfg, shape, ndev, workload="xpeft")

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant, "ok": True,
        "compile_s": round(compile_s, 2),
        "num_devices": ndev,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "state_bytes_per_dev_analytic": int(state_bytes),
        },
        "flops_per_dev": flops,
        "bytes_per_dev": acc_bytes,
        "collective_bytes_per_dev": colls["total"],
        "collectives": {k: int(v) for k, v in colls.items()},
        "xla_cost_flops_unscaled": float(xla_cost.get("flops", 0.0)),
        "roofline": terms,
        "model_flops_per_dev": mflops,
        "useful_flops_ratio": (mflops / flops) if flops else 0.0,
    }


def _lower_train(cfg, shape, mesh, fsdp, sh_kw):
    state_abs = jax.eval_shape(
        lambda k: init_train_state(k, cfg, "xpeft"), jax.random.key(0))
    batch_abs = input_specs(cfg, shape)
    key_abs = jax.eval_shape(lambda: jax.random.key(0))

    state_specs = param_specs(state_abs, mesh, fsdp=fsdp, **sh_kw)
    state_sh = to_shardings(state_specs, mesh)
    batch_sh = to_shardings(
        batch_specs(batch_abs, mesh, shape.global_batch), mesh)
    key_sh = NamedSharding(mesh, P())
    state_bytes = sharded_bytes_per_device(state_abs, state_specs, mesh)

    step = make_train_step(cfg, "xpeft", lr=1e-5)
    jitted = jax.jit(step, in_shardings=(state_sh, batch_sh, key_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
    return jitted.lower(state_abs, batch_abs, key_abs), state_bytes


def _lower_serve(cfg, shape, mesh, fsdp, vspec, sh_kw):
    params_abs = jax.eval_shape(lambda k: MDL.init_lm(k, cfg),
                                jax.random.key(0))
    params_specs_ = param_specs(params_abs, mesh, fsdp=fsdp, **sh_kw)
    params_sh = to_shardings(params_specs_, mesh)
    state_bytes = sharded_bytes_per_device(params_abs, params_specs_, mesh)
    B, S = shape.global_batch, shape.seq_len
    S_cache = S + (cfg.num_prefix_tokens or 0)  # prefix lives in the cache
    inp = input_specs(cfg, shape)
    if vspec.get("precomputed"):
        inp["masks"] = _precomputed_masks(cfg, B)
    elif vspec.get("sparse_agg"):
        inp["masks"] = _sparse_masks(cfg, B)
    masks_sh = to_shardings(_mask_shardings(inp["masks"], mesh), mesh)
    ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
    nb = int(np.prod([mesh.shape[a] for a in ba]))
    tok_spec = P(ba) if B % nb == 0 and B >= nb else P(None)
    tok_sh = NamedSharding(mesh, P(*tok_spec, None))

    if shape.kind == "prefill":
        cache_abs = jax.eval_shape(lambda: MDL.init_cache(cfg, B, S_cache))
        cache_specs_ = cache_specs(cache_abs, mesh, cfg, B)
        cache_sh = to_shardings(cache_specs_, mesh)
        state_bytes += sharded_bytes_per_device(cache_abs, cache_specs_, mesh)
        prefill = make_prefill_step(cfg)

        def cell(params, tokens, cache, masks, prefix=None):
            logits, cache = prefill(params, tokens, cache,
                                    profile_masks=masks,
                                    prefix_embeds=prefix)
            return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

        args = [params_abs, inp["tokens"], cache_abs, inp["masks"]]
        in_sh = [params_sh, tok_sh, cache_sh, masks_sh]
        if "prefix_embeds" in inp:
            args.append(inp["prefix_embeds"])
            in_sh.append(NamedSharding(mesh, P(*tok_spec, None, None)))
        jitted = jax.jit(
            cell, in_shardings=tuple(in_sh),
            out_shardings=(NamedSharding(mesh, P(*tok_spec)), cache_sh),
            donate_argnums=(2,))
        return jitted.lower(*args), state_bytes

    # decode
    cache_abs = jax.eval_shape(lambda: MDL.init_cache(cfg, B, S_cache))
    cache_specs_ = cache_specs(cache_abs, mesh, cfg, B)
    cache_sh = to_shardings(cache_specs_, mesh)
    state_bytes += sharded_bytes_per_device(cache_abs, cache_specs_, mesh)
    decode = make_decode_step(cfg)

    def cell(params, tokens, cache, cache_pos, masks):
        logits, cache = decode(params, tokens, cache, cache_pos,
                               profile_masks=masks)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    jitted = jax.jit(
        cell,
        in_shardings=(params_sh, tok_sh, cache_sh,
                      NamedSharding(mesh, P()), masks_sh),
        out_shardings=(NamedSharding(mesh, P(*tok_spec)), cache_sh),
        donate_argnums=(2,))
    return jitted.lower(params_abs, inp["tokens"], cache_abs,
                        inp["cache_pos"], inp["masks"]), state_bytes


# ----------------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--xpeft-n", type=int, default=256)
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = [s.name for s in shapes_for(cfg)] \
            if args.shape == "all" else args.shape.split(",")
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch}_{shape_name}_{'multi' if multi else 'single'}" \
                      f"_{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = lower_cell(arch, shape_name, multi, args.variant,
                                     args.xpeft_n)
                    n_ok += 1
                    print(f"OK   {tag}: compile={rec['compile_s']}s "
                          f"dom={rec['roofline']['dominant']} "
                          f"flops/dev={rec['flops_per_dev']:.3e}")
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi else "16x16",
                           "variant": args.variant, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    n_fail += 1
                    print(f"FAIL {tag}: {type(e).__name__}: {e}")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
