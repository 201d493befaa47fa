"""Attention: GQA projections + three execution strategies.

  * ``full``   — materialised scores with mask; cheapest HLO for short train
                 sequences (TP over heads + remat keep it in budget).
  * ``brick``  — flop-exact blocked attention: a ``lax.scan`` over the
                 *statically enumerated* list of (q-chunk, kv-chunk) bricks that
                 are actually needed under the causal/sliding-window mask, with
                 online softmax.  Peak memory is O(S·D) + one brick.  This is
                 the jnp twin of the Pallas flash kernel.
  * ``decode`` — single-token attention against a KV cache.  When the cache's
                 sequence dim is sharded (long-context serving) the computation
                 runs as a shard_map flash-decode: each shard computes partial
                 (m, l, o) and combines with psum/pmax — no cache all-gather.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_mrope, apply_rope, norm_spec,
                                 rms_norm, row_parallel_proj as L_row_parallel)
from repro.parallel import sharding as shlib
from repro.parallel.sharding import ParamSpec, shard_act

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Parameter specs
# --------------------------------------------------------------------------- #
def attn_specs(cfg: ModelConfig, heads: Optional[int] = None,
               kv_heads: Optional[int] = None, cross: bool = False) -> dict:
    h = heads or cfg.num_heads
    kh = kv_heads or cfg.num_kv_heads
    d = cfg.head_dim
    specs = {
        "wq": ParamSpec((cfg.d_model, h, d), ("embed", "heads", None)),
        "wk": ParamSpec((cfg.d_model, kh, d), ("embed", "kv_heads", None)),
        "wv": ParamSpec((cfg.d_model, kh, d), ("embed", "kv_heads", None)),
        "wo": ParamSpec((h, d, cfg.d_model), ("heads", None, "embed")),
    }
    if cfg.qk_norm and not cross:
        specs["q_norm"] = norm_spec(d)
        specs["k_norm"] = norm_spec(d)
    return specs


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


# --------------------------------------------------------------------------- #
# full-scores attention (train path for short S)
# --------------------------------------------------------------------------- #
def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_offset: int = 0, softcap: float = 0.0) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D).  Returns (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    q5 = q.reshape(B, Sq, Hkv, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q5, k) / math.sqrt(D)
    scores = _softcap(scores, softcap).astype(jnp.float32)
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


# --------------------------------------------------------------------------- #
# brick-scan attention (flop-exact flash, jnp)
# --------------------------------------------------------------------------- #
def _brick_list(nq: int, nk: int, cq: int, ck: int, causal: bool,
                window: int, q_offset: int) -> list:
    """Statically enumerate needed (i, j) bricks under the mask."""
    pairs = []
    for i in range(nq):
        q_lo, q_hi = q_offset + i * cq, q_offset + (i + 1) * cq - 1
        for j in range(nk):
            k_lo, k_hi = j * ck, (j + 1) * ck - 1
            if causal and k_lo > q_hi:
                continue
            if window and k_hi <= q_lo - window:
                continue
            pairs.append((i, j))
    return pairs


def brick_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    cq: int = 1024, ck: int = 2048,
                    softcap: float = 0.0) -> jax.Array:
    """Blocked online-softmax attention via scan over needed bricks only."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    cq = min(cq, Sq)
    ck = min(ck, Skv)
    # pad seq lens to multiples of chunks
    pq = (-Sq) % cq
    pk = (-Skv) % ck
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pq, Skv + pk
    nq, nk = Sq_p // cq, Skv_p // ck
    pairs = _brick_list(nq, nk, cq, ck, causal, window, q_offset)
    # pad kv beyond Skv is masked via kpos >= Skv check below
    qc = q.reshape(B, nq, cq, Hkv, G, D)
    kc = k.reshape(B, nk, ck, Hkv, D)
    vc = v.reshape(B, nk, ck, Hkv, D)
    scale = 1.0 / math.sqrt(D)

    acc0 = jnp.zeros((nq, B, cq, Hkv, G, D), jnp.float32)
    m0 = jnp.full((nq, B, cq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, B, cq, Hkv, G), jnp.float32)

    iis = jnp.asarray([p[0] for p in pairs], jnp.int32)
    jjs = jnp.asarray([p[1] for p in pairs], jnp.int32)

    def body(carry, ij):
        acc, m, l = carry
        i, j = ij
        qi = jax.lax.dynamic_index_in_dim(qc, i, axis=1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qi, kj) * scale
        s = _softcap(s, softcap).astype(jnp.float32)
        qpos = q_offset + i * cq + jnp.arange(cq)[:, None]
        kpos = j * ck + jnp.arange(ck)[None, :]
        mask = kpos < Skv
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        ai = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        s_max = jnp.max(s, axis=-1)                       # (B, cq, Hkv, G)
        m_new = jnp.maximum(mi, jnp.transpose(s_max, (0, 1, 2, 3)))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(q.dtype), vj)
        a_new = ai * corr[..., None] + pv.astype(jnp.float32)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, 0)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (iis, jjs))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    out = jnp.transpose(out, (1, 0, 2, 3, 4, 5)).reshape(B, Sq_p, Hq, D)
    return out[:, :Sq].astype(q.dtype)


# --------------------------------------------------------------------------- #
# decode attention (flash-decode, seq-shard aware)
# --------------------------------------------------------------------------- #
def _decode_attn_local(q, k, v, kpos, t, window, softcap):
    """Partial attention on a local KV shard -> (o, m, l) un-normalised.

    kpos: (B, S_loc) global positions of cache slots; t: (B,) per-sequence
    current positions (continuous batching gives every slot its own).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    q5 = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqkgd,bskd->bqkgs", q5, k) / math.sqrt(D)
    s = _softcap(s, softcap).astype(jnp.float32)
    # kpos < 0 marks ring-buffer slots not yet written (pre-wrap)
    mask = (kpos <= t[:, None]) & (kpos >= 0)
    if window:
        mask &= kpos > (t[:, None] - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(q.dtype), v).astype(jnp.float32)
    return o, m, l


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     t: jax.Array, *, window: int = 0, ring: bool = False,
                     softcap: float = 0.0) -> jax.Array:
    """q: (B, 1, Hq, D); caches: (B, S_c, Hkv, D); t = per-seq positions (B,).

    If the cache sequence dim is sharded on the current mesh, runs as a
    shard_map flash-decode with psum/pmax combination across the seq axes.
    ``ring=True`` treats the cache as a ring buffer of size S_c (sliding
    window): global position of slot s is t - ((t - s) mod S_c).
    """
    B, Sc = k_cache.shape[0], k_cache.shape[1]
    mesh = shlib.current_mesh()
    rules = shlib.current_rules()
    t = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(t)), (B,))

    def kpos_of(slots, t_):
        # slots: (S_loc,); returns (B, S_loc) global positions
        if ring:
            return t_[:, None] - jnp.mod(t_[:, None] - slots[None, :], Sc)
        return jnp.broadcast_to(slots[None, :], (t_.shape[0], slots.shape[0]))

    if mesh is None:
        slots = jnp.arange(Sc)
        o, m, l = _decode_attn_local(q, k_cache, v_cache, kpos_of(slots, t),
                                     t, window, softcap)
        out = o / jnp.maximum(l[..., None], 1e-37)
        return out.reshape(q.shape).astype(q.dtype)

    cache_spec = shlib.logical_to_mesh_axes(
        mesh, k_cache.shape, ("batch", "kv_seq", "kv_heads", None), rules)
    seq_axes = cache_spec[1]
    seq_axes = () if seq_axes is None else (
        (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes))
    batch_axes = cache_spec[0]
    batch_axes = () if batch_axes is None else (
        (batch_axes,) if isinstance(batch_axes, str) else tuple(batch_axes))

    if not seq_axes:
        slots = jnp.arange(Sc)
        q = shard_act(q, "batch", None, "heads", None)
        k_cache = jax.lax.with_sharding_constraint(
            k_cache, jax.sharding.NamedSharding(mesh, cache_spec))
        v_cache = jax.lax.with_sharding_constraint(
            v_cache, jax.sharding.NamedSharding(mesh, cache_spec))
        o, m, l = _decode_attn_local(q, k_cache, v_cache, kpos_of(slots, t),
                                     t, window, softcap)
        out = o / jnp.maximum(l[..., None], 1e-37)
        return out.reshape(q.shape).astype(q.dtype)

    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    Sc_loc = Sc // n_seq
    bspec = (None if not batch_axes else
             (batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)))
    sspec = seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)

    from jax import shard_map

    def local_fn(q_l, k_l, v_l, t_l):
        # shard index along the flattened seq axes
        idx = jnp.int32(0)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        slots = idx * Sc_loc + jnp.arange(Sc_loc)
        o, m, l = _decode_attn_local(q_l, k_l, v_l, kpos_of(slots, t_l),
                                     t_l, window, softcap)
        m_g = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axes)
        o_g = jax.lax.psum(o * corr[..., None], seq_axes)
        return o_g / jnp.maximum(l_g[..., None], 1e-37)

    out = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, sspec, None, None),
                  P(bspec, sspec, None, None), P(bspec)),
        out_specs=P(bspec, None, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, t)
    B_, Sq_, Hkv_, G_, D_ = out.shape
    return out.reshape(B_, Sq_, Hkv_ * G_, D_).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Block-level glue: projections + rope + cache handling
# --------------------------------------------------------------------------- #
def cache_specs(cfg: ModelConfig, batch: int, cache_len: int,
                heads: Optional[int] = None, kv_heads: Optional[int] = None
                ) -> dict:
    kh = kv_heads or cfg.num_kv_heads
    return {
        "k": ParamSpec((batch, cache_len, kh, cfg.head_dim),
                       ("batch", "kv_seq", "kv_heads", None),
                       dtype=cfg.act_dtype, init="zeros"),
        "v": ParamSpec((batch, cache_len, kh, cfg.head_dim),
                       ("batch", "kv_seq", "kv_heads", None),
                       dtype=cfg.act_dtype, init="zeros"),
    }


def _q_col_parallel(x: jax.Array, wq: jax.Array):
    """Q projection with the seq all-gather inside shard_map (its transpose
    is psum_scatter, killing the backward dx all-reduce).  None = fallback."""
    import numpy as np
    mesh = shlib.current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return None
    mp = mesh.shape["model"]
    B, S = x.shape[0], x.shape[1]
    if mp == 1 or S % mp or wq.shape[1] % mp:
        return None
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if data_axes and B % dp:
        return None
    bsp = (None if not data_axes else
           (data_axes[0] if len(data_axes) == 1 else data_axes))
    from jax import shard_map

    def f(x_l, wq_l):
        xg = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        return jnp.einsum("bsd,dhe->bshe", xg, wq_l)

    return shard_map(f, mesh=mesh,
                     in_specs=(P(bsp, "model", None), P(None, "model", None)),
                     out_specs=P(bsp, None, "model", None),
                     check_vma=False)(x, wq)


def _project_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                 positions, apply_pos: bool = True, tp_sp: bool = False):
    dt = x.dtype
    q = None
    if tp_sp:
        q = _q_col_parallel(x, params["wq"].astype(dt))
    if q is None:
        q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"].astype(dt))
    if cfg.qk_norm and "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if apply_pos and cfg.head_dim % 2 == 0:
        if cfg.mrope:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            pos1 = positions if positions.ndim == 2 else positions[0]
            q = apply_rope(q, pos1, cfg.rope_theta)
            k = apply_rope(k, pos1, cfg.rope_theta)
    return q, k, v


def attention_block(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    local: bool = False, mode: str = "train",
                    positions: Optional[jax.Array] = None,
                    cache: Optional[dict] = None, causal: bool = True,
                    index=None) -> Tuple[jax.Array, Optional[dict]]:
    """Self-attention sub-block.  Returns (out, new_cache)."""
    B, S, _ = x.shape
    window = cfg.window_size if local else 0
    if positions is None:
        base = jnp.arange(S) if mode != "decode" else jnp.asarray(index)[None]
        positions = jnp.broadcast_to(base, (B, S))

    q, k, v = _project_qkv(params, x, cfg, positions,
                           tp_sp=cfg.tp_sp and mode != "decode")
    # GQA head padding: when Hq doesn't divide the TP axis (e.g. 40 heads on
    # TP=16), pad the per-kv-head group so attention heads shard instead of
    # replicating 16x (the dominant waste for qwen3-14b / llama4-scout).
    pad_g = None
    if cfg.pad_attn_heads:
        mesh = shlib.current_mesh()
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        Hq, Hkv = q.shape[2], k.shape[2]
        if tp > 1 and Hq % tp:
            G = Hq // Hkv
            g_pad = G
            while (Hkv * g_pad) % tp and g_pad < G + tp:
                g_pad += 1
            if (Hkv * g_pad) % tp == 0:
                q5 = q.reshape(B, q.shape[1], Hkv, G, cfg.head_dim)
                q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, 0), (0, g_pad - G),
                                  (0, 0)))
                q = q5.reshape(B, q.shape[1], Hkv * g_pad, cfg.head_dim)
                pad_g = (G, g_pad)
    q = shard_act(q, "batch", None, "heads", None)
    k = shard_act(k, "batch", None, "kv_heads", None)
    v = shard_act(v, "batch", None, "kv_heads", None)

    new_cache = None
    if mode == "decode":
        assert cache is not None
        Sc = cache["k"].shape[1]
        ring = bool(local and window and Sc <= window)
        idx_vec = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(index)), (B,))
        slot = jnp.mod(idx_vec, Sc) if ring else idx_vec
        k_cache = _cache_update(cache["k"], k, slot)
        v_cache = _cache_update(cache["v"], v, slot)
        out = decode_attention(q, k_cache, v_cache, index, window=window,
                               ring=bool(ring), softcap=cfg.attn_logit_softcap)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        impl = cfg.attn_impl
        if impl == "auto":
            impl = "flash" if S > 1024 else "full"
        if impl == "flash" and cfg.attn_logit_softcap:
            impl = "brick"   # flash path has no softcap support
        if impl == "flash":
            from repro.kernels.flash_attention.ops import flash_attention
            out = flash_attention(q, k, v, causal, window,
                                  min(cfg.attn_chunk_q, S),
                                  min(cfg.attn_chunk_kv, S),
                                  "pallas" if cfg.use_pallas else "jnp")
        elif impl == "brick":
            out = brick_attention(q, k, v, causal=causal, window=window,
                                  cq=cfg.attn_chunk_q, ck=cfg.attn_chunk_kv,
                                  softcap=cfg.attn_logit_softcap)
        else:
            out = full_attention(q, k, v, causal=causal, window=window,
                                 softcap=cfg.attn_logit_softcap)
        if mode == "prefill" and cache is not None:
            Sc = cache["k"].shape[1]
            if Sc >= S:
                k_cache = _cache_update(cache["k"], k, 0)
                v_cache = _cache_update(cache["v"], v, 0)
            else:  # ring (local window) cache keeps the last Sc tokens
                k_tail = k[:, -Sc:]
                v_tail = v[:, -Sc:]
                roll = jnp.mod(S - Sc + jnp.arange(Sc), Sc)
                k_cache = jnp.take(k_tail, jnp.argsort(roll), axis=1).astype(
                    cache["k"].dtype)
                v_cache = jnp.take(v_tail, jnp.argsort(roll), axis=1).astype(
                    cache["v"].dtype)
            new_cache = {"k": k_cache, "v": v_cache}

    out = shard_act(out, "batch", None, "heads", None)
    dt = x.dtype
    if pad_g:
        out = out.reshape(B, out.shape[1], -1, pad_g[1], out.shape[-1])
        out = out[:, :, :, :pad_g[0]].reshape(B, out.shape[1], -1,
                                              out.shape[-1])
    if cfg.tp_sp and mode != "decode":
        y = L_row_parallel(out.astype(dt), params["wo"].astype(dt),
                           "bshe,hed->bsd", h_model_dim=2)
        if y is not None:
            return shard_act(y, "batch", "seq_act", None), new_cache
    y = jnp.einsum("bshe,hed->bsd", out.astype(dt), params["wo"].astype(dt))
    return shard_act(y, "batch", "seq_act", None), new_cache


def _cache_update(cache: jax.Array, kv: jax.Array, slot) -> jax.Array:
    """Write kv at per-sequence slots.  slot: scalar or (B,) vector —
    continuous batching gives every sequence its own write position."""
    kv = kv.astype(cache.dtype)
    slot = jnp.asarray(slot)
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, kv, slot, axis=1)
    return jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, axis=0)
    )(cache, kv, slot)


# --------------------------------------------------------------------------- #
# Cross attention (encoder-decoder)
# --------------------------------------------------------------------------- #
def cross_attn_specs(cfg: ModelConfig) -> dict:
    return attn_specs(cfg, cross=True)


def cross_attention_block(params: dict, x: jax.Array, enc_kv: Tuple,
                          cfg: ModelConfig) -> jax.Array:
    """x: (B, St, d); enc_kv = (k, v) precomputed from encoder output."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(dt))
    k, v = enc_kv
    B, Sq = q.shape[0], q.shape[1]
    if Sq == 1:
        Hq = q.shape[2]
        Hkv = k.shape[2]
        G = Hq // Hkv
        q5 = q.reshape(B, 1, Hkv, G, q.shape[-1])
        s = jnp.einsum("bqkgd,bskd->bqkgs", q5, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(dt)
        out = jnp.einsum("bqkgs,bskd->bqkgd", p, v).reshape(q.shape)
    elif Sq * k.shape[1] <= 4096 * 4096:
        out = full_attention(q, k, v, causal=False)
    else:
        out = brick_attention(q, k, v, causal=False,
                              cq=cfg.attn_chunk_q, ck=cfg.attn_chunk_kv)
    y = jnp.einsum("bshe,hed->bsd", out.astype(dt), params["wo"].astype(dt))
    return shard_act(y, "batch", "seq_act", None)


def encode_cross_kv(params: dict, enc_out: jax.Array, cfg: ModelConfig):
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhe->bshe", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhe->bshe", enc_out, params["wv"].astype(dt))
    return k, v
