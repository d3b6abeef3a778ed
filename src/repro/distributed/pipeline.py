"""GPipe pipeline parallelism over a mesh axis via shard_map +
collective_permute.

The at-scale alternative to cross-pod data parallelism (DESIGN.md §5 PP):
stage s holds layers [s*L/S, (s+1)*L/S); microbatches stream through the
pipeline with a (M + S - 1)-step schedule. collective_permute is
differentiable, so jax.grad through `pipeline_apply` yields the GPipe
backward schedule for free (activations of the schedule loop are rematerialized
per-stage via jax.checkpoint on the stage body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, mesh: Mesh,
                   axis: str = "pod"):
    """Run microbatches through pipeline stages laid out on `axis`.

    stage_fn(params_one_stage, x_mb) -> y_mb  (same shape)
    stage_params: pytree stacked on a leading S dim (S = mesh.shape[axis]).
    x_micro: [M, mb, ...] microbatches.
    Returns y_micro [M, mb, ...].
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    steps = M + S - 1
    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    pspec_x = P(None)  # microbatch stream replicated across stages

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspec_params, pspec_x),
        out_specs=pspec_x,
        check_vma=False)
    def run(params_local, xm):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        mb_shape = xm.shape[1:]
        body = jax.checkpoint(lambda p, x: stage_fn(p, x))

        def step(carry, t):
            send, outs = carry
            # ring-shift activations stage s -> s+1
            perm = [(i, (i + 1) % S) for i in range(S)]
            recv = jax.lax.ppermute(send, axis, perm)
            feed_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(xm, feed_idx, 0,
                                                    keepdims=False)
            x_in = jnp.where(idx == 0, first_in, recv)
            y = body(params_local, x_in)
            # last stage commits outputs for t >= S-1
            out_slot = jnp.clip(t - (S - 1), 0, M - 1)
            commit = (idx == S - 1) & (t >= S - 1)
            outs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_slot, 0),
                lambda o: o, outs)
            return (y, outs), None

        outs0 = jnp.zeros((M,) + mb_shape, xm.dtype)
        send0 = jnp.zeros(mb_shape, xm.dtype)
        (_, outs), _ = jax.lax.scan(step, (send0, outs0),
                                    jnp.arange(steps))
        # broadcast final outputs from the last stage to all stages
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run(stage_params, x_micro)


def stack_stages(layer_params, num_stages: int):
    """[L, ...] stacked layer params -> [S, L/S, ...] per-stage stacks."""
    def re(a):
        L = a.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return a.reshape(num_stages, L // num_stages, *a.shape[1:])
    return jax.tree.map(re, layer_params)
