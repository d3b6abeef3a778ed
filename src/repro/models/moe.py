"""Mixture-of-Experts with sort-based capacity dispatch (dropless-ish).

FLOP-honest dispatch (DESIGN.md §5 EP): instead of the GShard one-hot
dispatch einsum — whose [T,E,C] contraction doubles HLO FLOPs — tokens are
argsorted by expert id, scattered into an [E, C, d] buffer (overflow dropped,
capacity_factor-controlled), run through batched expert GEMMs, and
scatter-added back with their router weights. Experts shard over the `model`
mesh axis (expert parallelism); GSPMD inserts the dispatch collectives.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.distributed import ctx
from repro.models.common import activation, dense_init


def init_moe(key, cfg, dtype) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), d, jnp.float32),
        "ew_g": dense_init(ks[1], (E, d, ff), d, dtype),
        "ew_u": dense_init(ks[2], (E, d, ff), d, dtype),
        "ew_d": dense_init(ks[3], (E, ff, d), ff, dtype),
    }


def capacity(tokens: int, cfg) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(cfg.top_k, min(tokens, c))


def moe_apply(params, x, cfg):
    """x: [B, T, d] -> ([B, T, d], aux). Dispatches to the shard_map EP path
    under a distributed mesh, else the local jnp path (smoke tests)."""
    from repro.distributed import ctx
    mesh = ctx.active_mesh()
    if (mesh is not None and "model" in mesh.shape
            and cfg.num_experts % mesh.shape["model"] == 0
            and cfg.moe_impl == "sort"):
        return _moe_shard_map(params, x, cfg, mesh)
    return _moe_local(params, x, cfg)


def _moe_local(params, x, cfg):
    B, T, d = x.shape
    x2 = x.reshape(B * T, d)
    n = B * T
    E, k = cfg.num_experts, cfg.top_k
    act = activation(cfg.act)

    gates = jnp.einsum("td,de->te", x2.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(gates, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                    # [n, k]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    if cfg.moe_impl == "dense":
        # Reference path (smoke tests / tiny E): compute every expert.
        g = jnp.einsum("td,edf->tef", x2, params["ew_g"])
        u = jnp.einsum("td,edf->tef", x2, params["ew_u"])
        y_all = jnp.einsum("tef,efd->ted", act(g) * u, params["ew_d"])
        comb = jnp.zeros((n, E), jnp.float32).at[
            jnp.arange(n)[:, None], topi].add(topw)
        y = jnp.einsum("te,ted->td", comb.astype(y_all.dtype), y_all)
        return y.reshape(B, T, d), aux_loss(probs, topi, E)

    C = capacity(n, cfg)
    eids = topi.reshape(-1)                                  # [n*k]
    tids = jnp.repeat(jnp.arange(n), k)
    wts = topw.reshape(-1)

    order = jnp.argsort(eids)                                # stable
    se, st, sw = eids[order], tids[order], wts[order]
    starts = jnp.searchsorted(se, jnp.arange(E))
    pos = jnp.arange(n * k) - starts[se]                     # rank in expert
    # out-of-capacity rows get an out-of-range index -> dropped by the scatter
    pos = jnp.where(pos < C, pos, C + 1)

    buf = jnp.zeros((E, C, d), x2.dtype)
    buf = buf.at[se, pos].set(x2[st], mode="drop")
    buf = ctx.hint(buf, "expert", None, None)

    g = jnp.einsum("ecd,edf->ecf", buf, params["ew_g"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["ew_u"])
    h = act(g) * u
    h = ctx.hint(h, "expert", None, "mlp")
    y = jnp.einsum("ecf,efd->ecd", h, params["ew_d"])

    contrib = y.at[se, jnp.minimum(pos, C - 1)].get(mode="fill", fill_value=0)
    contrib = contrib * (pos < C)[:, None] * sw[:, None].astype(y.dtype)
    out = jnp.zeros((n, d), y.dtype).at[st].add(contrib)
    return out.reshape(B, T, d), aux_loss(probs, topi, E)


def aux_loss(probs, topi, E):
    """Switch-style load-balance loss: E * sum(f_e * p_e)."""
    hot = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    f = hot.mean(0)
    p = probs.mean(0)
    return E * jnp.sum(f * p)


# ----------------------------------------------------------------------------
# Expert-parallel shard_map path (DESIGN.md §5 EP)
# ----------------------------------------------------------------------------
# Key observation: activations are batch-sharded over (pod, data) and
# REPLICATED over "model", while experts are sharded over "model". So every
# model peer already holds the tokens it needs: dispatch is a purely LOCAL
# sort/scatter onto the peer's expert slice, followed by ONE psum("model") to
# combine expert outputs — no all-to-all, no GSPMD scatter replication
# (which blew per-device temp memory up 20x; see EXPERIMENTS.md §Perf).
# FSDP-sharded expert weights are explicitly all-gathered over "data" first
# (pinned to the ff dim by the sharding rules).

def _moe_shard_map(params, x, cfg, mesh):
    from jax.sharding import PartitionSpec as P

    ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
    msize = mesh.shape["model"]
    dsize = mesh.shape.get("data", 1)
    E_loc = cfg.num_experts // msize
    ff = cfg.d_ff
    ff_fsdp = dsize if (ff % dsize == 0 and
                        cfg.num_experts * cfg.d_model * ff >= 2 ** 16) else 1

    x_spec = P(ba if x.shape[0] % max(1, np.prod([mesh.shape[a] for a in ba])) == 0
               else None, None, None)
    wg_spec = P("model", None, "data" if ff_fsdp > 1 else None)
    wd_spec = P("model", "data" if ff_fsdp > 1 else None, None)

    def inner(router, ew_g, ew_u, ew_d, x_loc):
        if ff_fsdp > 1:
            ew_g = jax.lax.all_gather(ew_g, "data", axis=2, tiled=True)
            ew_u = jax.lax.all_gather(ew_u, "data", axis=2, tiled=True)
            ew_d = jax.lax.all_gather(ew_d, "data", axis=1, tiled=True)
        B, T, d = x_loc.shape
        n = B * T
        k = cfg.top_k
        act = activation(cfg.act)
        x2 = x_loc.reshape(n, d)

        gates = jnp.einsum("td,de->te", x2.astype(jnp.float32), router)
        probs = jax.nn.softmax(gates, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

        j = jax.lax.axis_index("model")
        local = (topi >= j * E_loc) & (topi < (j + 1) * E_loc)
        eids = jnp.where(local, topi - j * E_loc, E_loc).reshape(-1)
        tids = jnp.repeat(jnp.arange(n), k)
        wts = (topw * local).reshape(-1)

        C = capacity(n, cfg)
        order = jnp.argsort(eids)
        se, st, sw = eids[order], tids[order], wts[order]
        starts = jnp.searchsorted(se, jnp.arange(E_loc + 1))
        pos = jnp.arange(n * k) - starts[jnp.minimum(se, E_loc)]
        pos = jnp.where((pos < C) & (se < E_loc), pos, C + 1)

        buf = jnp.zeros((E_loc, C, d), x2.dtype)
        buf = buf.at[se, pos].set(x2[st], mode="drop")
        g = jnp.einsum("ecd,edf->ecf", buf, ew_g)
        u = jnp.einsum("ecd,edf->ecf", buf, ew_u)
        y = jnp.einsum("ecf,efd->ecd", act(g) * u, ew_d)

        contrib = y.at[jnp.minimum(se, E_loc - 1),
                       jnp.minimum(pos, C - 1)].get(mode="fill", fill_value=0)
        contrib = contrib * ((pos < C)[:, None] * sw[:, None]).astype(y.dtype)
        out = jnp.zeros((n, d), y.dtype).at[st].add(contrib)
        out = jax.lax.psum(out, "model")
        aux = aux_loss(probs, topi, cfg.num_experts)
        aux = jax.lax.pmean(aux, ba) if ba else aux
        return out.reshape(B, T, d), aux

    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(P(None, None), wg_spec, wg_spec, wd_spec,
                                 x_spec),
                       out_specs=(x_spec, P()),
                       check_vma=False)
    return fn(params["router"], params["ew_g"], params["ew_u"],
              params["ew_d"], x)
