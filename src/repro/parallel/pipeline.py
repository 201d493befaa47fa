"""Pipeline parallelism over a mesh axis (GPipe schedule, ppermute ring).

Stages live on consecutive slices of the `stage` mesh axis (typically the
``pod`` axis: one stage per pod, DCN-friendly point-to-point activation
hand-off — the same ring the weight torrent uses).  Microbatches stream
through with the classic (M + L - 1)-step schedule; every step each stage
computes its resident microbatch and ``ppermute``s the activation to its
successor.  Bubble fraction = (L-1)/(M+L-1).

This is the optional PP dimension of the framework: the assigned 2-pod mesh
favours DP over pods (see DESIGN.md §9), but the combinator is exercised by
tests on a 4-stage host mesh so a deeper pod dimension is a config change,
not new code.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   mesh: Mesh, axis: str = "pod"):
    """Run `stage_fn(params_s, x) -> x` through L pipeline stages.

    stage_params: pytree with leading stage axis L (sharded over `axis`).
    x_microbatches: (M, ...) microbatch stack (replicated over `axis`).
    Returns (M, ...) outputs of the final stage (replicated over `axis`).
    """
    L = mesh.shape[axis]
    M = x_microbatches.shape[0]
    steps = M + L - 1
    fwd = [(i, i + 1) for i in range(L - 1)]

    def body(params_l, xs):
        s = jax.lax.axis_index(axis)
        params_stage = jax.tree_util.tree_map(lambda p: p[0], params_l)
        mb_shape = xs.shape[1:]
        recv = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)
        for t in range(steps):
            inject = xs[min(t, M - 1)]
            live_in = jnp.where(s == 0,
                                inject if t < M else jnp.zeros_like(inject),
                                recv)
            out = stage_fn(params_stage, live_in)
            # emit on the last stage once the wavefront arrives
            emit_idx = t - (L - 1)
            if 0 <= emit_idx < M:
                take = jnp.where(s == L - 1, out, jnp.zeros_like(out))
                outs = outs.at[emit_idx].set(take)
            if t < steps - 1:
                recv = jax.lax.ppermute(out, axis, fwd)
        # broadcast final-stage outputs to every stage (replicated result)
        return jax.lax.psum(outs, axis) if L > 1 else outs

    other = [a for a in mesh.axis_names if a != axis]
    pspec = [axis] + [None] * (
        len(jax.tree_util.tree_leaves(stage_params)[0].shape) - 1)
    in_param_specs = jax.tree_util.tree_map(
        lambda p: P(*( [axis] + [None] * (p.ndim - 1))), stage_params)
    x_spec = P(*([None] * x_microbatches.ndim))

    return shard_map(body, mesh=mesh,
                     in_specs=(in_param_specs, x_spec),
                     out_specs=x_spec,
                     check_vma=False)(stage_params, x_microbatches)


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
