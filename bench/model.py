"""A configuration file as the program runs it, and the weights and profiles
the benchmark makes for it from the seed.

The configuration files keep the published config's own keys
(``hidden_size``, ``num_hidden_layers``, ...); ``model_config`` maps them
onto the program's ``ModelConfig``. Weights are made here, not by the
program's initializers, in one jitted call, in the program's parameter
layout and in the dtype they are served in: the reference in
``bench/reference.py`` reads the same arrays, so nothing it compares
against was made by the code under test.
"""
from __future__ import annotations

import numpy as np

# The program's RMSNorm has a fixed epsilon (models/common.py).
PROGRAM_RMS_EPS = 1e-6


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` 32-bit words from any whole-number seed (SeedSequence takes
    seeds of any size, the driver's are over 2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent host stream ``stream`` of a seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


def sizes(conf: dict) -> dict:
    """The numbers every consumer needs, from a configuration file."""
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    xp = conf["xpeft"]
    return {"L": conf["num_hidden_layers"], "d": d, "H": H,
            "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim", d // H),
            "ff": conf["intermediate_size"], "V": conf["vocab_size"],
            "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"]),
            "tied": bool(conf["tie_word_embeddings"]),
            "qkv_bias": bool(conf["qkv_bias"]),
            "N": xp["num_adapters"], "k": xp["k"], "b": xp["bottleneck"],
            "adapter_activation": xp["adapter_activation"],
            "tau": float(xp["gumbel_tau"]), "nu": float(xp["gumbel_nu"]),
            "dtype": conf["torch_dtype"]}


def model_config(conf: dict, kernel_impl: str = "auto"):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, XPeftConfig

    s = sizes(conf)
    if conf["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: the program "
                         "runs SwiGLU blocks")
    if abs(s["eps"] - PROGRAM_RMS_EPS) > 1e-12:
        raise ValueError(f"rms_norm_eps {s['eps']}: the program's RMSNorm "
                         f"uses {PROGRAM_RMS_EPS}")
    xp = conf["xpeft"]
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["KV"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["V"],
        qkv_bias=s["qkv_bias"], rope_theta=s["theta"],
        max_seq_len=conf["max_position_embeddings"], act="silu",
        mlp_type="glu", norm="rmsnorm", tie_embeddings=s["tied"],
        dtype=s["dtype"], remat="none",
        xpeft=XPeftConfig(num_adapters=s["N"], bottleneck=s["b"], k=s["k"],
                          mask_type=xp["mask_type"],
                          adapter_activation=s["adapter_activation"],
                          aggregate="sparse", kernel_impl=kernel_impl,
                          max_profiles=xp["max_profiles"], tau=s["tau"],
                          nu=s["nu"]))


# Scales of the random weights. Matrices are fan-in normal (unit-RMS
# activations in, unit-RMS out); norm scales, biases and the profiles'
# adapter-LN affines are drawn away from their identity values so the
# comparison sees them. The bank's up-projection is drawn so that one
# profile's aggregated adapter moves the residual stream about as much as
# a block does: a wrong or missing aggregate then shows in the logits.
NORM_STD = 0.1
BIAS_STD = 0.1
ADAPTER_OUT_RMS = 0.5


def init_params(seed: int, conf: dict):
    """Program-layout parameters from the seed, one jitted call, on the
    device, in the configuration's dtype."""
    import jax
    import jax.numpy as jnp

    s = sizes(conf)
    dt = jnp.dtype(s["dtype"])
    L, d, H, KV, hd, ff, V = (s[k] for k in ("L", "d", "H", "KV", "hd",
                                             "ff", "V"))
    N, b, k = s["N"], s["b"], s["k"]

    def make(key):
        ks = iter(jax.random.split(key, 24))

        def w(shape, std, dtype=dt):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(dtype)

        attn = {"wq": w((L, d, H, hd), d ** -0.5),
                "wk": w((L, d, KV, hd), d ** -0.5),
                "wv": w((L, d, KV, hd), d ** -0.5),
                "wo": w((L, H, hd, d), (H * hd) ** -0.5)}
        if s["qkv_bias"]:
            attn.update(bq=w((L, H, hd), BIAS_STD, jnp.float32),
                        bk=w((L, KV, hd), BIAS_STD, jnp.float32),
                        bv=w((L, KV, hd), BIAS_STD, jnp.float32))
        params = {
            "embed": w((V, d), d ** -0.5),
            "blocks": {
                "attn": attn,
                "n1": {"scale": w((L, d), NORM_STD, jnp.float32)},
                "n2": {"scale": w((L, d), NORM_STD, jnp.float32)},
                "mlp": {"wg": w((L, d, ff), d ** -0.5),
                        "wu": w((L, d, ff), d ** -0.5),
                        "wd": w((L, ff, d), ff ** -0.5)}},
            "final_norm": {"scale": w((d,), NORM_STD, jnp.float32)},
            # B̂ is the mean of k rows: its entries have std
            # std_b / sqrt(k); h B̂ then has RMS ~ ADAPTER_OUT_RMS
            "xpeft_bank": {"bank_a": w((L, N, d, b), d ** -0.5),
                           "bank_b": w((L, N, b, d),
                                       ADAPTER_OUT_RMS * (k / b) ** 0.5)},
        }
        if not s["tied"]:
            params["lm_head"] = w((d, V), d ** -0.5)
        return params

    key = jax.random.key(int(seed_words(seed, 1)[0]))
    return jax.jit(make)(key)


def init_profiles(seed: int, conf: dict, count: int) -> dict:
    """``count`` profiles' trainables (mask logits, adapter-LN affine) on the
    host, ``[count, L, N]`` / ``[count, L, b]`` float32. The affines are
    fp16-representable, as the profile store keeps them."""
    s = sizes(conf)
    rng = host_rng(seed, 1)
    L, N, b = s["L"], s["N"], s["b"]
    f16 = lambda x: x.astype(np.float16).astype(np.float32)  # noqa: E731
    return {"mA": rng.standard_normal((count, L, N), np.float32),
            "mB": rng.standard_normal((count, L, N), np.float32),
            "ln_scale": f16(1.0 + NORM_STD * rng.standard_normal(
                (count, L, b), np.float32)),
            "ln_bias": f16(NORM_STD * rng.standard_normal(
                (count, L, b), np.float32))}


def topk_indices(logits: np.ndarray, k: int) -> np.ndarray:
    """[..., N] -> [..., k] indices of the k largest, ascending."""
    idx = np.argpartition(-logits, k - 1, axis=-1)[..., :k]
    return np.sort(idx, axis=-1)


def build_store(conf: dict, profiles: dict):
    """The program's ProfileStore holding every profile (hard masks)."""
    from repro.core.profiles import ProfileStore

    s = sizes(conf)
    store = ProfileStore(s["L"], s["N"], s["b"], "hard", s["k"])
    for pid in range(profiles["mA"].shape[0]):
        store.add_profile(pid, {key: profiles[key][pid]
                                for key in ("mA", "mB", "ln_scale",
                                            "ln_bias")})
    return store
