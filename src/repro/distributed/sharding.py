"""Sharding rules: param-name-based logical axes -> PartitionSpecs.

Scheme (DESIGN.md §5):
- TP over the "model" axis: heads / kv_heads / mlp / experts / vocab / the
  adapter bank's d_model dim (row+col parallel bottleneck).
- FSDP over the "data" axis: every parameter's largest still-unsharded dim,
  when divisible and large enough (ZeRO-3 via GSPMD all-gather-on-use).
- The "pod" axis never shards parameters (cross-pod = grad reduce only).

Divisibility-aware: a logical assignment that doesn't divide the dim (e.g.
MQA kv=1 on a 16-way model axis) silently stays replicated.

`overrides` lets the §Perf hillclimb re-map individual tensors without
touching model code.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils import map_with_path

# leaf-name (+ndim disambiguation) -> logical dims for the TRAILING dims.
# Leading stack dims (layers L, profile table P) are covered implicitly:
# unmatched leading dims get None (then FSDP may claim them).
_RULES: Dict[Tuple[str, int], Tuple] = {}


def _rule(name, *logical, ndim=None):
    _RULES[(name, ndim)] = tuple(logical)


# embeddings / heads
_rule("embed", "vocab", None)
_rule("pos_embed", None, None)
_rule("lm_head", None, "vocab")
# attention (rules align to TRAILING dims; leading stack dims get None)
_rule("wq", None, "heads", None)
_rule("wk", None, "kv_heads", None)
_rule("wv", None, "kv_heads", None)
_rule("wo", "heads", None, None)
_rule("bq", "heads", None)
_rule("bk", "kv_heads", None)
_rule("bv", "kv_heads", None)
# dense mlp
_rule("wg", None, "mlp")
_rule("wu", None, "mlp")
_rule("wd", "mlp", None)
_rule("w1", None, "mlp")
_rule("w2", "mlp", None)
_rule("b1", "mlp")
_rule("b2", None)
# moe — experts over model (EP); FSDP pinned to the ff dim so the
# shard_map dispatch knows where to all-gather (models/moe.py)
_rule("router", None, None)
_rule("ew_g", "expert", None, "mlp_fsdp")
_rule("ew_u", "expert", None, "mlp_fsdp")
_rule("ew_d", "expert", "mlp_fsdp", None)
# X-PEFT adapter bank [L, N, d, b] / [L, N, b, d]: d_model TP-sharded
_rule("bank_a", "adapter_n", "tp_d", None)
_rule("bank_b", "adapter_n", None, "tp_d")
# heterogeneous bank segments: LoRA pairs share the bottleneck bank's
# layout exactly (A [L, cnt, d, r], B [L, cnt, r, d]) so they keep bank
# TP on d_model; IA3 scale vectors [L, cnt, d] and prefix KV rows
# [L, cnt, P, kv_dim] are tiny — replicate them (explicit all-None rules
# so mesh parity is a declared contract, not fsdp-matcher fallthrough)
_rule("lora_a", "adapter_n", "tp_d", None)
_rule("lora_b", "adapter_n", None, "tp_d")
_rule("ia3_v", "adapter_n", None)
_rule("prefix_k", "adapter_n", None, None)
_rule("prefix_v", "adapter_n", None, None)
# quantized bank (quant/schemes.quantize_bank): the q payloads keep the
# bf16 bank's layout (int4 packs the LAST axis, which is never the
# TP-sharded d_model dim for bank_a and stays divisibility-guarded for
# bank_b), and the fp16 scale arrays ride along on their matching dims —
# int8 scales drop the quantized axis (ndim 3), int4 group scales keep a
# trailing group axis (ndim 4)
_rule("bank_a_q", "adapter_n", "tp_d", None)
_rule("bank_b_q", "adapter_n", None, "tp_d")
_rule("bank_a_scale", "adapter_n", "tp_d", ndim=3)
_rule("bank_a_scale", "adapter_n", "tp_d", None, ndim=4)
_rule("bank_b_scale", "adapter_n", None, ndim=3)
_rule("bank_b_scale", "adapter_n", None, "tp_d", ndim=4)
# quantized LoRA segments ride the same layout as the bottleneck bank
_rule("lora_a_q", "adapter_n", "tp_d", None)
_rule("lora_b_q", "adapter_n", None, "tp_d")
_rule("lora_a_scale", "adapter_n", "tp_d", ndim=3)
_rule("lora_a_scale", "adapter_n", "tp_d", None, ndim=4)
_rule("lora_b_scale", "adapter_n", None, ndim=3)
_rule("lora_b_scale", "adapter_n", None, "tp_d", ndim=4)
# rwkv (2D projections over flattened heads)
_rule("rwr", None, "tp_d")
_rule("rwk", None, "tp_d")
_rule("rwv", None, "tp_d")
_rule("rwg", None, "tp_d")
_rule("rwo", "tp_d", None)
_rule("cw_k", None, "mlp")
_rule("cw_v", "mlp", None)
_rule("cw_r", None, None)
_rule("dec_a", None, None)
_rule("dec_b", None, "tp_d")
# mamba
_rule("in_proj", None, "tp_d")
_rule("out_proj", "tp_d", None)

_LOGICAL_TO_MESH = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "expert": "model",
    "tp_d": "model",
    "mlp_fsdp": "data",
}

FSDP_MIN_SIZE = 2 ** 16


def _lookup(name: str, ndim: int):
    """Rules align to trailing dims; any leading stack dims are padded
    with None by spec_for — so (name, None) matches every rank."""
    if (name, ndim) in _RULES:
        return _RULES[(name, ndim)]
    if (name, None) in _RULES:
        return _RULES[(name, None)]
    return None


def spec_for(path: str, shape, mesh_axes: Dict[str, int], *, fsdp: bool,
             logical_map: Optional[dict] = None,
             overrides: Optional[dict] = None) -> P:
    """Build the PartitionSpec for one parameter."""
    name = path.rsplit("/", 1)[-1]
    ndim = len(shape)
    lmap = dict(_LOGICAL_TO_MESH)
    if logical_map:
        lmap.update(logical_map)

    logical = None
    if overrides:
        for pat, val in overrides.items():
            if pat in path:
                logical = val
                break
    if logical is None:
        logical = _lookup(name, ndim)
    if logical is None:
        logical = (None,) * ndim
    # left-pad to ndim (leading stack dims unassigned)
    logical = (None,) * (ndim - len(logical)) + tuple(logical)

    assigned = []
    used_axes = set()
    for dim, lg in zip(shape, logical):
        ax = lmap.get(lg) if lg else None
        if ax and ax in mesh_axes and dim % mesh_axes[ax] == 0 \
                and ax not in used_axes:
            assigned.append(ax)
            used_axes.add(ax)
        else:
            assigned.append(None)

    if fsdp and "data" in mesh_axes and "data" not in assigned \
            and int(np.prod(shape)) >= FSDP_MIN_SIZE:
        # shard the largest remaining dim over data
        cands = [(dim, i) for i, (dim, a) in enumerate(zip(shape, assigned))
                 if a is None and dim % mesh_axes["data"] == 0]
        if cands:
            _, i = max(cands)
            assigned[i] = "data"
    return P(*assigned)


def param_specs(abstract_params, mesh: Mesh, *, fsdp: bool = True,
                logical_map: Optional[dict] = None,
                overrides: Optional[dict] = None):
    mesh_axes = dict(mesh.shape)
    mesh_axes.pop("pod", None)  # never shard params over pods
    return map_with_path(
        lambda p, x: spec_for(p, x.shape, mesh_axes, fsdp=fsdp,
                              logical_map=logical_map, overrides=overrides),
        abstract_params)


def param_shardings(abstract_params, mesh: Mesh, **kw):
    specs = param_specs(abstract_params, mesh, **kw)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------------
# Activations / batch / cache
# ----------------------------------------------------------------------------

def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_specs(abstract_batch, mesh: Mesh, global_batch: int):
    """Shard the leading batch dim of every batch leaf over pod+data; falls
    back to sequence sharding (dim 1) when batch doesn't divide (batch=1
    long-context cells)."""
    ba = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in ba]))

    def one(x):
        if x.shape and x.shape[0] % n == 0 and x.shape[0] >= n:
            return P(ba, *([None] * (len(x.shape) - 1)))
        if len(x.shape) >= 2 and x.shape[1] % n == 0:
            return P(None, ba, *([None] * (len(x.shape) - 2)))
        return P(*([None] * len(x.shape)))

    return jax.tree.map(one, abstract_batch)


def cache_specs(abstract_cache, mesh: Mesh, cfg, batch: int):
    """KV/state cache sharding: batch over data when divisible, else the
    sequence dim (sequence parallelism for batch=1 long-context); kv_heads /
    state heads over model when divisible."""
    mesh_axes = dict(mesh.shape)
    dsize = mesh_axes.get("data", 1)
    msize = mesh_axes.get("model", 1)

    def one(path, x):
        name = path.rsplit("/", 1)[-1]
        nd = len(x.shape)
        spec = [None] * nd
        # leading L (stacked layers) never sharded; batch dim = 1
        bdim = 1
        if nd >= 2 and x.shape[bdim] % dsize == 0 and x.shape[bdim] >= dsize:
            spec[bdim] = "data"
        elif name in ("k", "v", "attn_k", "attn_v") and nd >= 3 \
                and x.shape[2] % dsize == 0:
            spec[2] = "data"  # sequence-parallel KV cache (batch=1 cells)
        if name in ("k", "v", "attn_k", "attn_v") and nd >= 4:
            if x.shape[3] % msize == 0:
                spec[3] = "model"          # kv heads over TP
            elif spec[2] is None and x.shape[2] % msize == 0:
                spec[2] = "model"          # context-parallel fallback
        if name in ("wkv", "ssd") and nd >= 3 and x.shape[2] % msize == 0:
            spec[2] = "model"  # recurrent state heads
        return P(*spec)

    return map_with_path(one, abstract_cache)


def paged_cache_specs(paged_cache, mesh: Mesh, cfg, n_slots: int):
    """Sharding for the continuous engine's block-paged cache
    (`serve/pages.py`): pool leaves are lane-dense
    ``[lead, n_pages, page, KV*hd]`` — the PAGE axis sits where the dense
    cache's slot axis sat, so `cache_specs` of the unfolded
    ``[lead, n_pages, page, KV, hd]`` applies (pages over "data", kv heads
    over "model" — whole heads of the lane axis —, recurrent resident
    leaves unchanged) and the PR-4 invariant "pages sharded like the slot
    axis" holds by construction. The page table shards its slot axis over
    "data" like every slot-packed array."""
    dsize = dict(mesh.shape).get("data", 1)
    KV = cfg.num_kv_heads

    def one(path, x):
        name = path.rsplit("/", 1)[-1]
        if name in ("k", "v", "attn_k", "attn_v") and len(x.shape) == 4:
            unfolded = jax.ShapeDtypeStruct(
                tuple(x.shape[:3]) + (KV, x.shape[3] // KV), x.dtype)
            spec = cache_specs({name: unfolded}, mesh, cfg, n_slots)[name]
            return P(*spec[:4])             # hd is never sharded
        return cache_specs({name: x}, mesh, cfg, n_slots)[name]

    data = map_with_path(one, paged_cache["data"])
    t = paged_cache["table"].shape
    lead = "data" if t[0] % dsize == 0 and t[0] >= dsize else None
    return {"data": data, "table": P(lead, None)}


def to_shardings(specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------------
# Slot-packed state (serve SlotState / mask buffers, train roster): every
# leaf carries a leading slot axis; shard it over "data" when divisible so
# per-slot work stays device-local (decode and gang-step numerics are then
# identical to the single-device path — no contraction is ever split).
# ----------------------------------------------------------------------------

def leading_axis_specs(abstract_tree, mesh: Mesh, axis: str = "data"):
    """Shard every leaf's leading dim over `axis` when divisible; replicate
    otherwise. The spec for SlotState arrays, engine mask buffers, and the
    training roster (all slot-packed on dim 0)."""
    n = dict(mesh.shape).get(axis, 1)

    def one(x):
        nd = len(x.shape)
        if nd >= 1 and n > 1 and x.shape[0] % n == 0 and x.shape[0] >= n:
            return P(axis, *([None] * (nd - 1)))
        return P(*([None] * nd))

    return jax.tree.map(one, abstract_tree)


def constrain_leading(tree, mesh: Optional[Mesh], axis: str = "data"):
    """with_sharding_constraint every leaf to its leading-axis spec (no-op
    without a mesh). Used inside jitted steps to pin slot-axis sharding so
    GSPMD never migrates or splits per-slot work."""
    if mesh is None:
        return tree
    specs = leading_axis_specs(tree, mesh, axis)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)), tree, specs)


def sharded_bytes_per_device(abstract_tree, specs, mesh) -> int:
    """Analytic per-device resident bytes of a sharded pytree.

    `mesh` may be a Mesh or a plain {axis: size} mapping. This number gates
    memory planning, so malformed inputs RAISE instead of under-reporting:
    the spec tree must have exactly one PartitionSpec per leaf, each spec
    must cover its leaf's full rank, and every named axis must exist in the
    mesh. (A silent zip over mismatched flats used to drop leaves.)
    """
    sizes = dict(mesh) if isinstance(mesh, dict) else dict(mesh.shape)

    flat_x = jax.tree.leaves(abstract_tree)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    if len(flat_x) != len(flat_s):
        raise ValueError(
            f"specs tree has {len(flat_s)} PartitionSpecs for "
            f"{len(flat_x)} leaves — every leaf needs exactly one spec")

    total = 0
    for x, spec in zip(flat_x, flat_s):
        if not isinstance(spec, P):
            raise ValueError(f"expected PartitionSpec, got {spec!r}")
        if len(spec) != len(x.shape):
            raise ValueError(
                f"spec {spec} has {len(spec)} entries for a rank-"
                f"{len(x.shape)} leaf of shape {tuple(x.shape)} — specs "
                "must cover the full rank")
        n = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                if a not in sizes:
                    raise ValueError(
                        f"spec {spec} names mesh axis {a!r} not in "
                        f"{sorted(sizes)}")
                n *= sizes[a]
        total += int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize // n
    return total
