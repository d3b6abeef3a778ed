"""The plain reference of X-PEFT mask training (arXiv:2401.16137, Algorithm
1) over a roster of slots: each slot is one profile's trainables (mask
logits ``mA``/``mB`` [L, N], adapter-LN affine [L, b]) learning from its
own rows, in float32 at full matmul precision.

It imports nothing of the program. Per step and slot:
- each row draws its own Gumbel noise for ``mA`` and ``mB`` (the step key
  split in two, one draw of [rows, L, N] each), and its mask weights are
  the top-k of softmax((logits + nu * noise) / tau), k-hot / k, with the
  softmax's gradient passed straight through;
- Â/B̂ are the bank rows weighted by the mask (the mean of the k
  selected), the model is the dense decoder of ``bench/reference.py``;
- the slot's loss is the mean over its rows of the mean next-token cross
  entropy, and the step's loss the mean over slots;
- the slot's gradient is clipped to norm ``clip`` over all its leaves,
  then AdamW (b1 0.9, b2 0.999, eps 1e-8, no weight decay) with its own
  step count updates it.
The step keys follow the training loop's: ``key, sub = split(key)`` per
step.

``fp8=True`` is the control (weights and bank rounded through float8 e4m3,
as in ``bench/reference.py``); ``rows_used`` < rows per slot plants the
fault of a step that leaves part of each slot's rows out and averages the
rest.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench import reference

B1, B2, EPS = 0.9, 0.999, 1e-8
LEAVES = ("mA", "mB", "ln_scale", "ln_bias")


def _mask(logits, noise, k, tau, nu):
    z = logits.astype(jnp.float32) + nu * noise
    soft = jax.nn.softmax(z / tau, axis=-1)
    _, idx = jax.lax.top_k(soft, k)
    hard = jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32) \
        .sum(-2) / k
    return hard - jax.lax.stop_gradient(soft) + soft


def _row_loss(row, params, tokens, labels, na, nb, *, s, fp8):
    """Mean next-token cross entropy of one row under one slot's
    trainables."""
    w_a = _mask(row["mA"], na, s["k"], s["tau"], s["nu"])       # [L, N]
    w_b = _mask(row["mB"], nb, s["k"], s["tau"], s["nu"])
    bank = params["xpeft_bank"]
    a_hat = jnp.einsum("ln,lndb->ldb", w_a,
                       reference._f32(bank["bank_a"], fp8, 2))
    b_hat = jnp.einsum("ln,lnbd->lbd", w_b,
                       reference._f32(bank["bank_b"], fp8, 2))
    x = reference.final_hidden(params, a_hat, b_hat, row["ln_scale"],
                               row["ln_bias"], tokens, s=s, fp8=fp8)
    logits = reference.output_logits(params, x, s=s, fp8=fp8)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def row_grad(row, params, tokens, labels, na, nb, *, s, fp8=False):
    """(loss, gradient) of one row with respect to its slot's trainables."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_row_loss)(row, params, tokens, labels,
                                             na, nb, s=s, fp8=fp8)


def step_noise(key, shape):
    """The step's Gumbel noise for ``mA`` and ``mB``: [rows, L, N] each."""
    ka, kb = jax.random.split(key)
    return jax.random.gumbel(ka, shape), jax.random.gumbel(kb, shape)


def run_steps(params, rows0: dict, batches: list, key, sizes, *, lr: float,
              clip: float, fp8: bool = False, rows_used=None) -> dict:
    """Train the roster ``rows0`` ({leaf: [S, ...]}, float32) for
    ``len(batches)`` steps; each batch is ``{"tokens", "labels"}`` [S, m,
    T]. Returns the step losses, the first step's clipped gradient and the
    trainables after every step (host arrays)."""
    S, m = batches[0]["tokens"].shape[:2]
    used = m if rows_used is None else rows_used
    L, N = rows0["mA"].shape[1:]
    rows = {k: jnp.asarray(v, jnp.float32) for k, v in rows0.items()}
    mom = {k: jnp.zeros_like(v) for k, v in rows.items()}
    vel = {k: jnp.zeros_like(v) for k, v in rows.items()}
    losses, grad1, after = [], None, []
    for t, batch in enumerate(batches, start=1):
        key, sub = jax.random.split(key)
        na, nb = step_noise(sub, (S * m, L, N))
        grads = {k: [] for k in rows}
        slot_losses = []
        for j in range(S):
            row = {k: v[j] for k, v in rows.items()}
            acc, tot = None, 0.0
            for i in range(used):
                e = j * m + i
                loss, g = row_grad(row, params,
                                   jnp.asarray(batch["tokens"][j, i]),
                                   jnp.asarray(batch["labels"][j, i]),
                                   na[e], nb[e], s=sizes, fp8=fp8)
                tot = tot + loss
                acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
            slot_losses.append(tot / used)
            for k in rows:
                grads[k].append(acc[k] / used)
        g = {k: jnp.stack(v) for k, v in grads.items()}
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(v), axis=tuple(
            range(1, v.ndim))) for v in g.values()))           # [S]
        scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
        g = {k: v * scale.reshape((S,) + (1,) * (v.ndim - 1))
             for k, v in g.items()}
        if t == 1:
            grad1 = {k: np.asarray(v) for k, v in g.items()}
        for k in rows:
            mom[k] = B1 * mom[k] + (1 - B1) * g[k]
            vel[k] = B2 * vel[k] + (1 - B2) * jnp.square(g[k])
            mhat = mom[k] / (1 - B1 ** t)
            vhat = vel[k] / (1 - B2 ** t)
            rows[k] = rows[k] - lr * mhat / (jnp.sqrt(vhat) + EPS)
        losses.append(float(jnp.mean(jnp.stack(slot_losses))))
        after.append({k: np.asarray(v) for k, v in rows.items()})
    return {"losses": losses, "grad1": grad1, "after": after,
            "rows0": {k: np.asarray(v, np.float32) for k, v in rows0.items()}}


def leaf_norms(tree: dict) -> dict:
    """Norm of each (leaf, slot): a slot's row of a leaf is one profile's
    parameter, trained on its own rows."""
    return {(k, j): float(np.linalg.norm(np.asarray(v[j], np.float64)))
            for k, v in tree.items() for j in range(v.shape[0])}


def leaf_gaps(got: dict, ref: dict, keep=None) -> dict:
    """Each leaf's gap between two norms, against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    g, r = leaf_norms(got), leaf_norms(ref)
    keys = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in keys]))
    return {k: abs(g[k] - r[k]) / max(r[k], med) for k in keys}


def norm_gap(got: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap (``leaf_gaps``)."""
    return max(leaf_gaps(got, ref, keep).values())


def by_leaf(gaps: dict) -> dict:
    """The worst gap of each leaf over its slots."""
    out = {}
    for (k, _), v in gaps.items():
        out[k] = max(out.get(k, 0.0), v)
    return out


def moved_leaves(grad1_ref: dict, share: float = 1e-3) -> set:
    """The (leaf, slot)s whose first reference gradient is not nought to
    rounding: at least ``share`` of the median leaf's."""
    n = leaf_norms(grad1_ref)
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= share * med}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's readings (``losses``,
    ``grad1``, ``rows0`` and ``rows3``) and the reference's run."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], ref["losses"]))
    keep = moved_leaves(ref["grad1"])
    d_prog = {k: prog["rows3"][k] - prog["rows0"][k] for k in LEAVES}
    d_ref = {k: ref["after"][2][k] - ref["rows0"][k] for k in LEAVES}
    g1 = leaf_gaps(prog["grad1"], ref["grad1"])
    return {"loss_gap": float(loss_gap),
            "grad1_gap": max(g1.values()),
            "change3_gap": norm_gap(d_prog, d_ref, keep),
            "leaves_compared": len(keep),
            "grad1_by_leaf": by_leaf(g1),
            "grad1_median_gap": float(np.median(list(g1.values())))}
