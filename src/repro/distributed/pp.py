"""Pipeline parallelism: GPipe over a mesh axis via shard_map + ppermute.

Layers are grouped into ``n_stages`` contiguous stages; stage s holds layers
[s*L/S, (s+1)*L/S).  Microbatches stream through: at step t, stage s
processes microbatch (t - s) -- the classic GPipe schedule with S-1 bubble
steps on each side.  Activations move stage->stage with
``jax.lax.ppermute``; the loop runs inside ``shard_map`` so the schedule is
explicit (no XLA reordering).

This maps the 'pod' axis of the production mesh to pipeline stages: a
2-pod mesh runs 2 stages with inter-pod (DCN) hops only between layer
blocks, which is the standard multi-pod topology answer (TP inside a pod,
PP across pods).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(layer_fn: Callable, stacked_params, x_microbatched,
                  mesh: Mesh, stage_axis: str = "stage",
                  n_microbatches: int = None):
    """Run ``layer_fn`` stack as a GPipe pipeline.

    layer_fn: (params_slice, h) -> h  (one layer)
    stacked_params: leading axis = total layers (divisible by #stages)
    x_microbatched: (n_mb, batch_per_mb, ...) activations
    Returns activations with the same shape as x_microbatched.
    """
    n_stages = mesh.shape[stage_axis]
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert n_layers % n_stages == 0
    per_stage = n_layers // n_stages
    n_mb = x_microbatched.shape[0] if n_microbatches is None else n_microbatches
    assert x_microbatched.shape[0] == n_mb

    other_axes = [a for a in mesh.axis_names if a != stage_axis]

    def stage_fn(params_stage, xs):
        """Runs on ONE stage (params_stage: layers of this stage, with a
        leading singleton stage axis from shard_map)."""
        params_stage = jax.tree_util.tree_map(lambda p: p[0], params_stage)
        xs = xs[0]                                   # (n_mb, b, ...)
        sid = jax.lax.axis_index(stage_axis)

        def run_stage(h):
            def body(h, i):
                pl = jax.tree_util.tree_map(lambda p: p[i], params_stage)
                return layer_fn(pl, h), None
            h, _ = jax.lax.scan(body, h, jnp.arange(per_stage))
            return h

        total_steps = n_mb + n_stages - 1
        zero = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            outs, inflight = carry
            # stage 0 injects microbatch t (if any); others use inflight
            mb_idx = jnp.clip(t, 0, n_mb - 1)
            h_in = jnp.where(sid == 0, xs[mb_idx], inflight)
            h_out = run_stage(h_in)
            # last stage commits its finished microbatch (t - (S-1))
            done_idx = t - (n_stages - 1)
            commit = (sid == n_stages - 1) & (done_idx >= 0)
            outs = jax.lax.cond(
                commit,
                lambda o: o.at[jnp.clip(done_idx, 0, n_mb - 1)].set(h_out),
                lambda o: o, outs)
            # shift activations to the next stage
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            nxt = jax.lax.ppermute(h_out, stage_axis, perm)
            return (outs, nxt), None

        (outs, _), _ = jax.lax.scan(step, (outs, zero),
                                    jnp.arange(total_steps))
        # only the last stage holds (nonzero) outputs; psum over the stage
        # axis broadcasts them so every stage returns the final activations
        last = jax.lax.psum(outs, stage_axis)
        return last[None]

    pspec = jax.tree_util.tree_map(lambda _: P(stage_axis), stacked_params)
    fm = jax.shard_map(stage_fn, mesh=mesh,
                       in_specs=(pspec, P(stage_axis)),
                       out_specs=P(stage_axis),
                       check_vma=False)
    # reshape stacked params: (L, ...) -> (S, L/S, ...), x -> (S=1 bcast)
    sp = jax.tree_util.tree_map(
        lambda p: p.reshape((n_stages, per_stage) + p.shape[1:]),
        stacked_params)
    xb = jnp.broadcast_to(x_microbatched[None],
                          (n_stages,) + x_microbatched.shape)
    out = fm(sp, xb)
    return out[0]
