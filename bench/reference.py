"""The plain reference: the published dense decoder (Qwen2 / LLaMA block:
RMSNorm, rotary attention with optional QKV bias, SwiGLU MLP) with the
X-PEFT adapter after each block, in float32 at full matmul precision.

It imports nothing of the program. It reads the parameters the benchmark
made (``bench/model.py``) and the profiles' mask logits, selects each
layer's top-k adapters itself, and runs one sequence at a time through
the whole model: no cache, no kernels, no batching.

Notation as in the configuration files; ``norm scale`` is stored as the
offset from 1 (``y = x / rms(x) * (1 + scale)``), which is the published
``weight`` minus one. Departures from the published model: none in the
blocks; the adapter is X-PEFT's (arXiv:2401.16137) Pfeiffer bottleneck
with LayerNorm after the down-projection, ``x + B̂ act(LN(x Â))``, Â/B̂
the mean of the profile's k selected bank rows, with the configuration's
activation (exact-erf GELU here).

``fp8=True`` is the control: every weight matrix and bank row is
rounded through float8 e4m3 (one scale per output channel) before the
same float32 computation.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

Q_BLOCK = 512          # query rows per attention block (bounds memory)
F8_MAX = 448.0         # largest finite float8_e4m3fn


def fp8_round(w, axis):
    """Round through float8 e4m3 with one scale per slice along the axes
    other than ``axis`` (the contraction axis)."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _f32(w, fp8, axis):
    return fp8_round(w, axis) if fp8 else w.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention, q/k/v [T, H|KV, hd], computed block by block of
    queries."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    qb = min(Q_BLOCK, T)
    nb = T // qb
    if nb * qb != T:
        raise ValueError(f"sequence length {T} is not a multiple of "
                         f"{Q_BLOCK}")

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(jnp.float32(hd))
        ok = (jnp.arange(T)[None, :] <= (i * qb + jnp.arange(qb))[:, None])
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(T, H, hd)


def _gelu(x, name):
    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if name == "identity":
        return x
    raise ValueError(f"adapter activation {name!r}")


def _layernorm(x, scale, bias, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


@functools.partial(jax.jit, static_argnames=("fp8",))
def adapters(bank, idx_a, idx_b, *, fp8=False):
    """Â [L, d, b], B̂ [L, b, d]: the mean of the selected bank rows
    (``idx_*`` [L, k]), float32."""
    L = bank["bank_a"].shape[0]
    rows = jnp.arange(L)[:, None]
    a = _f32(bank["bank_a"][rows, idx_a], fp8, 2)           # [L, k, d, b]
    b = _f32(bank["bank_b"][rows, idx_b], fp8, 2)           # [L, k, b, d]
    return a.mean(1), b.mean(1)


def final_hidden(params, a_hat, b_hat, ln_s, ln_b, tokens, *, s,
                 fp8=False):
    """``tokens`` [T] through every block and the final norm: [T, d],
    float32. Adapters: Â [L, d, b], B̂ [L, b, d], LN affine [L, b]. Call
    under full matmul precision."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = _f32(params["embed"], fp8, 1)[tokens]
    blk = params["blocks"]

    def layer(x, xs):
        p, ah, bh, ls, lb = xs
        at = p["attn"]
        h = _rms(x, p["n1"]["scale"], s["eps"])
        q = jnp.einsum("td,dhk->thk", h, _f32(at["wq"], fp8, 0))
        k = jnp.einsum("td,dhk->thk", h, _f32(at["wk"], fp8, 0))
        v = jnp.einsum("td,dhk->thk", h, _f32(at["wv"], fp8, 0))
        if s["qkv_bias"]:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        o = _attention(q, k, v)
        x = x + jnp.einsum("thk,hkd->td", o, _f32(at["wo"], fp8, (0, 1)))
        h = _rms(x, p["n2"]["scale"], s["eps"])
        m = p["mlp"]
        g = h @ _f32(m["wg"], fp8, 0)
        u = h @ _f32(m["wu"], fp8, 0)
        x = x + (jax.nn.silu(g) * u) @ _f32(m["wd"], fp8, 0)
        a = _layernorm(x @ ah, ls, lb)
        x = x + _gelu(a, s["adapter_activation"]) @ bh
        return x, None

    x, _ = jax.lax.scan(layer, x, (blk, a_hat, b_hat, ln_s, ln_b))
    return _rms(x, params["final_norm"]["scale"], s["eps"])


def output_logits(params, x, *, s, fp8=False):
    """[T, d] final hidden -> [T, V] logits, float32."""
    if s["tied"]:
        return x @ _f32(params["embed"], fp8, 1).T
    return x @ _f32(params["lm_head"], fp8, 0)


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def logits_probe(params, a_hat, b_hat, ln_s, ln_b, tokens, probes, *, s,
                 fp8=False):
    """Run ``tokens`` [T] through the model. Returns, at every position,
    the best logit, the logits of ``probes`` [T, P] and the argmax."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(params, a_hat, b_hat, ln_s, ln_b, tokens, s=s,
                         fp8=fp8)
        logits = output_logits(params, x, s=s, fp8=fp8)
        best = logits.max(-1)
        at_probe = jnp.take_along_axis(logits, probes, axis=1)
        return best, at_probe, jnp.argmax(logits, -1).astype(jnp.int32)


def hashable_sizes(s: dict):
    """``s`` as a static jit argument."""
    return _Frozen(s)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def profile_adapters(params, profiles: dict, pid: int, s, fp8=False):
    """One profile's float32 adapters, selected from its mask logits."""
    from bench.model import topk_indices

    ia = topk_indices(profiles["mA"][pid], s["k"])
    ib = topk_indices(profiles["mB"][pid], s["k"])
    a_hat, b_hat = adapters(params["xpeft_bank"], jnp.asarray(ia),
                            jnp.asarray(ib), fp8=fp8)
    return (a_hat, b_hat, jnp.asarray(profiles["ln_scale"][pid]),
            jnp.asarray(profiles["ln_bias"][pid]))


def pad_to(tokens: np.ndarray, length: int) -> np.ndarray:
    out = np.zeros((length,), np.int32)
    out[:len(tokens)] = tokens
    return out
