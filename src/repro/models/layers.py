"""Shared neural building blocks: norms, RoPE (incl. M-RoPE), embeddings, loss."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import ParamSpec, shard_act


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    # variance reduced in f32, but x itself is never materialised as an f32
    # tensor (XLA hoists full-size converts of remat-saved activations out of
    # backward loops otherwise — 4.5 GiB/device on a 48L model).
    dt = x.dtype
    # f32 accumulation without materialising an f32 copy of x
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    scale = (jax.lax.rsqrt(var + eps)).astype(dt)
    return x * scale * (1.0 + gamma.astype(dt))


def norm_spec(dim: int) -> ParamSpec:
    # stored as (gamma - 1) so zeros-init == identity
    return ParamSpec((dim,), (None,), init="zeros")


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                       # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Multimodal 3D RoPE (Qwen2-VL).

    x: (B, S, H, D); positions: (3, B, S) with (t, h, w) indices.  The D/2
    rotary frequencies are split into `sections` (sum == D/2); section k uses
    positions[k] as the rotation index.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                       # (half,)
    # angles per modality: (3, B, S, half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    parts = []
    start = 0
    for k, sec in enumerate(sections):
        parts.append(angles[k, ..., start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)                        # (B, S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Embedding / head
# --------------------------------------------------------------------------- #
def embed_specs(cfg: ModelConfig) -> dict:
    # the token embedding always exists: even embeds-input (VLM/audio) archs
    # embed generated tokens during decode.  fsdp_dim=-2 disables extra FSDP
    # sharding: the lookup runs in a shard_map over the vocab(model) axis and
    # the d_model dim must stay whole per shard.
    d = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                                ("vocab", "embed"), init="embed", scale=0.02,
                                fsdp_dim=-2)}
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                 ("embed", "vocab"), scale=1.0)
    d["final_norm"] = norm_spec(cfg.d_model)
    return d


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Vocab-sharded lookup via shard_map: each model-axis shard gathers the
    ids that fall in its vocab range and a psum combines — the gradient stays
    a (V/mp, d) local scatter instead of a full dense f32 (V, d) per device."""
    from repro.parallel import sharding as shlib
    emb = params["embedding"]
    mesh = shlib.current_mesh()
    V, D = emb.shape
    if mesh is None or "model" not in mesh.shape or V % mesh.shape["model"]:
        x = jnp.take(emb.astype(cfg.act_dtype), tokens, axis=0)
        return shard_act(x, "batch", "seq_act", None)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mp = mesh.shape["model"]
    V_loc = V // mp
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    import numpy as np
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    bsp = None
    if data_axes and tokens.shape[0] % dp == 0:
        bsp = data_axes[0] if len(data_axes) == 1 else data_axes

    def local(emb_l, tok_l):
        base = jax.lax.axis_index("model") * V_loc
        loc = tok_l - base
        ok = (loc >= 0) & (loc < V_loc)
        safe = jnp.clip(loc, 0, V_loc - 1)
        g = jnp.take(emb_l.astype(cfg.act_dtype), safe, axis=0)
        g = g * ok[..., None].astype(g.dtype)
        return jax.lax.psum(g, "model")

    x = shard_map(local, mesh=mesh,
                  in_specs=(P("model", None), P(bsp, None)),
                  out_specs=P(bsp, None, None))(emb, tokens)
    return shard_act(x, "batch", "seq_act", None)


def lm_logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and "lm_head" not in params:
        w = params["embedding"].astype(cfg.act_dtype).T
    else:
        w = params["lm_head"].astype(cfg.act_dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return shard_act(logits, "batch", None, "vocab")


def lm_head_loss(params: dict, x: jax.Array, labels: jax.Array,
                 cfg: ModelConfig,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Sequence-chunked softmax cross-entropy.

    Materialising (B, S, V) logits (plus their f32 shadow and the dW matmul
    layouts) costs several GiB/device at 4k x 92k vocab; scanning over seq
    chunks with a checkpointed body keeps the live set to one chunk.
    """
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and "lm_head" not in params:
        w = params["embedding"].astype(cfg.act_dtype).T
    else:
        w = params["lm_head"].astype(cfg.act_dtype)
    B, S, _ = x.shape
    c = cfg.loss_chunk
    if not c or S <= c:
        logits = shard_act(jnp.einsum("bsd,dv->bsv", x, w),
                           "batch", None, "vocab")
        return cross_entropy(logits, labels, mask)
    if S % c:
        c = S // (S // c)  # keep chunks equal; S is a power of two in practice
    n = S // c

    def body(carry, idx):
        tot, cnt = carry
        xs = jax.lax.dynamic_slice_in_dim(x, idx * c, c, axis=1)
        xs = shard_act(xs, "batch", None, None)
        lbl = jax.lax.dynamic_slice_in_dim(labels, idx * c, c, axis=1)
        logits = shard_act(jnp.einsum("bsd,dv->bsv", xs, w),
                           "batch", None, "vocab").astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if mask is not None:
            mk = jax.lax.dynamic_slice_in_dim(mask, idx * c, c, axis=1)
            mkf = mk.astype(jnp.float32)
            return (tot + jnp.sum(nll * mkf), cnt + jnp.sum(mkf)), None
        return (tot + jnp.sum(nll), cnt + jnp.float32(nll.size)), None

    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL; logits (B, S, V), labels (B, S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------- #
# Row-parallel projection with explicit reduce-scatter (TP-SP)
# --------------------------------------------------------------------------- #
def row_parallel_proj(h: jax.Array, w: jax.Array, eq: str,
                      h_model_dim: int) -> Optional[jax.Array]:
    """y = einsum(eq, h, w) with the contraction dim model-sharded, emitting
    ``psum_scatter`` over the sequence dim instead of XLA's all-reduce+slice
    (halves the dominant collective's bytes).  Returns None if the shapes
    don't divide the mesh (caller falls back to the einsum+constraint path).
    """
    from repro.parallel import sharding as shlib
    import numpy as np
    mesh = shlib.current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return None
    mp = mesh.shape["model"]
    B, S = h.shape[0], h.shape[1]
    if mp == 1 or S % mp or w.shape[0] * (w.shape[1] if w.ndim == 3 else 1) \
            % mp:
        return None
    if h.shape[h_model_dim] % mp:
        return None
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if data_axes and B % dp:
        return None
    bsp = (None if not data_axes else
           (data_axes[0] if len(data_axes) == 1 else data_axes))

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    h_spec = [bsp] + [None] * (h.ndim - 1)
    h_spec[h_model_dim] = "model"
    w_spec = ["model"] + [None] * (w.ndim - 1)

    def f(h_l, w_l):
        part = jnp.einsum(eq, h_l, w_l)
        return jax.lax.psum_scatter(part, "model", scatter_dimension=1,
                                    tiled=True)

    return shard_map(f, mesh=mesh,
                     in_specs=(P(*h_spec), P(*w_spec)),
                     out_specs=P(bsp, "model", None),
                     check_vma=False)(h, w)


# --------------------------------------------------------------------------- #
# Dense MLP (SwiGLU)
# --------------------------------------------------------------------------- #
def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    dff = d_ff or cfg.d_ff
    return {
        "wi_gate": ParamSpec((cfg.d_model, dff), ("embed", "mlp")),
        "wi_up": ParamSpec((cfg.d_model, dff), ("embed", "mlp")),
        "wo": ParamSpec((dff, cfg.d_model), ("mlp", "embed")),
    }


def col_parallel_mlp_in(x: jax.Array, wg: jax.Array, wu: jax.Array):
    """Column-parallel wi_gate/wi_up with the sequence all-gather INSIDE a
    shard_map, so its transpose lowers to psum_scatter (not all-reduce) and
    one gather feeds both matmuls.  Returns None if shapes don't divide."""
    from repro.parallel import sharding as shlib
    import numpy as np
    mesh = shlib.current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return None
    mp = mesh.shape["model"]
    B, S = x.shape[0], x.shape[1]
    if mp == 1 or S % mp or wg.shape[1] % mp:
        return None
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if data_axes and B % dp:
        return None
    bsp = (None if not data_axes else
           (data_axes[0] if len(data_axes) == 1 else data_axes))
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def f(x_l, wg_l, wu_l):
        xg = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        return (jnp.einsum("bsd,df->bsf", xg, wg_l),
                jnp.einsum("bsd,df->bsf", xg, wu_l))

    return shard_map(f, mesh=mesh,
                     in_specs=(P(bsp, "model", None), P(None, "model"),
                               P(None, "model")),
                     out_specs=(P(bsp, None, "model"), P(bsp, None, "model")),
                     check_vma=False)(x, wg, wu)


def mlp_apply(params: dict, x: jax.Array, tp_sp: bool = False) -> jax.Array:
    dt = x.dtype
    pair = (col_parallel_mlp_in(x, params["wi_gate"].astype(dt),
                                params["wi_up"].astype(dt))
            if tp_sp else None)
    if pair is not None:
        gate, up = pair
    else:
        gate = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(dt))
        up = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(dt))
    h = jax.nn.silu(gate) * up
    h = shard_act(h, "batch", None, "mlp")
    if tp_sp:
        out = row_parallel_proj(h, params["wo"].astype(dt), "bsf,fd->bsd",
                                h_model_dim=2)
        if out is not None:
            return shard_act(out, "batch", "seq_act", None)
    out = jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dt))
    return shard_act(out, "batch", "seq_act", None)
