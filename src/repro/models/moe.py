"""Mixture-of-Experts with expert parallelism over the `model` mesh axis.

Two dispatch strategies, both expressed with shard_map so the collective
schedule is explicit:

  * ``a2a``        — tokens are sequence-sharded over the model axis.  Each
                     chip routes its own tokens, builds a capacity-bounded
                     (E, C, d) dispatch buffer and ``all_to_all``s it so every
                     chip receives the slots of its local experts.  This is the
                     TPU-native analogue of the NCCL a2a dispatch used by GPU
                     MoE frameworks: ICI all-to-all instead of NVLink.
  * ``replicated`` — tokens are replicated over the model axis (decode / tiny
                     batches).  Every chip routes all tokens but only executes
                     its local experts, then a psum over the model axis
                     combines expert outputs.  Comm is O(tokens·d), optimal for
                     small N.

Routing is top-k softmax with probability renormalisation and the standard
load-balance auxiliary loss.  Capacity overflow drops tokens (the residual
path keeps them intact); decode-sized batches get dropless capacity.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.models.layers import mlp_specs, mlp_apply
from repro.parallel import sharding as shlib
from repro.parallel.sharding import ParamSpec, shard_act


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _a2a_int8(x: jax.Array, axis: str, split_axis: int, concat_axis: int
              ) -> jax.Array:
    """all_to_all with int8-quantized payload (per-row scale), halving ICI
    dispatch bytes vs bf16.  Straight-through gradient: the backward a2a
    moves full-precision cotangents (fwd-only compression)."""
    return _a2a_int8_fwd(x, axis, split_axis, concat_axis)[0]


def _a2a_int8_fwd(x, axis, split_axis, concat_axis):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    q = jax.lax.all_to_all(q, axis, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=True)
    s = jax.lax.all_to_all(scale, axis, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=True)
    out = (q.astype(jnp.float32) * s).astype(x.dtype)
    return out, None


def _a2a_int8_bwd(axis, split_axis, concat_axis, res, g):
    # transpose of all_to_all swaps split/concat axes
    gx = jax.lax.all_to_all(g, axis, split_axis=concat_axis,
                            concat_axis=split_axis, tiled=True)
    return (gx,)


_a2a_int8.defvjp(_a2a_int8_fwd, _a2a_int8_bwd)


def moe_specs(cfg: ModelConfig) -> dict:
    E, dff, d = cfg.num_experts, cfg.moe_d_ff, cfg.d_model
    specs = {
        "router": ParamSpec((d, E), ("embed", None), scale=1.0),
        "wi_gate": ParamSpec((E, d, dff), ("experts", "embed", None)),
        "wi_up": ParamSpec((E, d, dff), ("experts", "embed", None)),
        "wo": ParamSpec((E, dff, d), ("experts", None, "embed")),
    }
    if cfg.shared_expert:
        specs["shared"] = mlp_specs(cfg, d_ff=cfg.moe_d_ff)
    return specs


def _route(xf: jax.Array, router_w: jax.Array, k: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xf: (N, d) -> (gates (N,k), experts (N,k) int32, probs (N,E) f32)."""
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32), probs


def _aux_stats(probs: jax.Array, experts: jax.Array, E: int):
    """Per-shard (f_e, P_e) statistics for the load-balance loss."""
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)    # (N,k,E)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)             # fraction routed
    p = jnp.mean(probs, axis=0)
    return f, p


def _aux_loss(probs: jax.Array, experts: jax.Array, E: int) -> jax.Array:
    """Load-balance loss: E * sum_e f_e * P_e  (Switch Transformer)."""
    k = experts.shape[1]
    f, p = _aux_stats(probs, experts, E)
    return E * jnp.sum(f * p) / k


def _dispatch_compute(xf, gates, experts, keepers, wi_g, wi_u, wo, capacity,
                      e_base, e_count):
    """Scatter tokens into a (e_count, capacity, d) buffer, run experts,
    gather back.  `keepers` optionally masks assignments (replicated mode).

    Returns (out (N, d), dropped fraction proxy)."""
    N, d = xf.shape
    k = gates.shape[1]
    e_flat = experts.reshape(-1)                              # (N*k,)
    t_flat = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    g_flat = gates.reshape(-1)
    local = (e_flat >= e_base) & (e_flat < e_base + e_count)
    if keepers is not None:
        local &= keepers.reshape(-1)
    e_local = jnp.where(local, e_flat - e_base, e_count)      # e_count = trash
    order = jnp.argsort(e_local, stable=True)
    e_s = e_local[order]
    t_s = t_flat[order]
    g_s = g_flat[order]
    counts = jnp.bincount(e_s, length=e_count + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[e_s].astype(jnp.int32)
    keep = (pos < capacity) & (e_s < e_count)
    dest = jnp.where(keep, e_s * capacity + pos, e_count * capacity)
    x_s = jnp.take(xf, t_s, axis=0) * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e_count * capacity + 1, d), xf.dtype)
    buf = buf.at[dest].add(x_s)
    buf = buf[:-1].reshape(e_count, capacity, d)

    h_g = jnp.einsum("ecd,edf->ecf", buf, wi_g.astype(buf.dtype))
    h_u = jnp.einsum("ecd,edf->ecf", buf, wi_u.astype(buf.dtype))
    h = jax.nn.silu(h_g) * h_u
    y = jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))

    y_flat = jnp.concatenate(
        [y.reshape(e_count * capacity, d), jnp.zeros((1, d), y.dtype)], 0)
    y_tok = jnp.take(y_flat, dest, axis=0) * (
        g_s[:, None].astype(y.dtype) * keep[:, None].astype(y.dtype))
    out = jnp.zeros((N, d), y.dtype).at[t_s].add(y_tok)
    return out


def moe_block(params: dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    mesh = shlib.current_mesh()
    rules = shlib.current_rules()
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    if mesh is None or "model" not in mesh.shape:
        # single-device path (smoke tests): all experts local
        xf = x.reshape(B * S, d)
        gates, experts, probs = _route(xf, params["router"], k)
        N = B * S
        cap = N if N <= 512 else int(math.ceil(N * k / E * cfg.capacity_factor))
        out = _dispatch_compute(xf, gates, experts, None, params["wi_gate"],
                                params["wi_up"], params["wo"], cap, 0, E)
        aux = _aux_loss(probs, experts, E)
        out = out.reshape(B, S, d)
        if cfg.shared_expert:
            out = out + mlp_apply(params["shared"], x)
        return out, aux

    mp = mesh.shape["model"]
    assert E % mp == 0, (E, mp)
    E_loc = E // mp
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in data_axes]))
    bspec = data_axes[0] if len(data_axes) == 1 else data_axes

    batch_shardable = B % dp == 0
    seq_shardable = S % mp == 0 and S >= mp
    strategy = "a2a" if seq_shardable else "replicated"

    B_loc = B // dp if batch_shardable else B
    S_loc = S // mp if strategy == "a2a" else S
    N_loc = B_loc * S_loc
    cap = (N_loc if N_loc <= 256 else
           int(math.ceil(N_loc * k / E * cfg.capacity_factor)))
    cap = max(cap, 1)

    in_x_spec = P(bspec if batch_shardable else None,
                  "model" if strategy == "a2a" else None, None)

    def local_fn(x_l, router_w, wi_g, wi_u, wo):
        m_idx = jax.lax.axis_index("model")
        xf = x_l.reshape(-1, d)
        gates, experts, probs = _route(xf, router_w, k)
        # combine (f, P) across token shards BEFORE the product so the
        # sharded aux equals the global-batch aux exactly
        f_loc, p_loc = _aux_stats(probs, experts, E)
        stat_axes = (data_axes + ("model",) if strategy == "a2a"
                     else data_axes)
        f_g = jax.lax.pmean(f_loc, stat_axes) if stat_axes else f_loc
        p_g = jax.lax.pmean(p_loc, stat_axes) if stat_axes else p_loc
        aux = E * jnp.sum(f_g * p_g) / k
        if strategy != "a2a":
            aux = jax.lax.pmean(aux, ("model",))   # replicate across model
        if strategy == "a2a":
            # full-E buffer, then all_to_all expert dim -> local experts
            buf_out = _moe_a2a(xf, gates, experts, wi_g, wi_u, wo, cap, E,
                               E_loc, d, k)
        else:
            e_base = m_idx * E_loc
            buf_out = _dispatch_compute(xf, gates, experts, None, wi_g, wi_u,
                                        wo, cap, e_base, E_loc)
            buf_out = jax.lax.psum(buf_out, "model")
        return buf_out.reshape(x_l.shape), aux

    def _moe_a2a(xf, gates, experts, wi_g, wi_u, wo, cap, E, E_loc, d, k):
        N = xf.shape[0]
        e_flat = experts.reshape(-1)
        t_flat = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
        g_flat = gates.reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        e_s, t_s, g_s = e_flat[order], t_flat[order], g_flat[order]
        counts = jnp.bincount(e_s, length=E + 1)[:E]
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(N * k, dtype=jnp.int32) - starts[e_s].astype(jnp.int32)
        keep = pos < cap
        dest = jnp.where(keep, e_s * cap + pos, E * cap)
        x_s = jnp.take(xf, t_s, axis=0) * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((E * cap + 1, d), xf.dtype)
        buf = buf.at[dest].add(x_s).astype(xf.dtype)
        buf = buf[:-1].reshape(E, cap, d)
        # (E, cap, d) -> exchange: each peer gets its E_loc experts' slots
        if cfg.moe_a2a_int8:
            buf = _a2a_int8(buf, "model", 0, 1)               # (E_loc, mp*cap, d)
        else:
            buf = jax.lax.all_to_all(buf, "model", split_axis=0,
                                     concat_axis=1, tiled=True)
        h_g = jnp.einsum("ecd,edf->ecf", buf, wi_g.astype(buf.dtype))
        h_u = jnp.einsum("ecd,edf->ecf", buf, wi_u.astype(buf.dtype))
        h = jax.nn.silu(h_g) * h_u
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))
        if cfg.moe_a2a_int8:
            y = _a2a_int8(y, "model", 1, 0)                   # (E, cap, d)
        else:
            y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                                   tiled=True)
        y_flat = jnp.concatenate(
            [y.reshape(E * cap, d), jnp.zeros((1, d), y.dtype)], 0)
        y_tok = jnp.take(y_flat, dest, axis=0) * (
            g_s[:, None].astype(y.dtype) * keep[:, None].astype(y.dtype))
        return jnp.zeros((N, d), y.dtype).at[t_s].add(y_tok)

    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(in_x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(in_x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])

    if cfg.shared_expert:
        out = out + mlp_apply(params["shared"], x)
    return shard_act(out, "batch", "seq_act", None), aux
