"""Pallas TPU FlashAttention-2 forward kernel.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv axis sequential
("arbitrary") so the online-softmax state lives in VMEM scratch across kv
steps.  Blocks are MXU-aligned (block_q x head_dim and block_k x head_dim
tiles); GQA is handled in the k/v index_map (kv head = q head // group).

Causal/sliding-window masking is positional via iota; fully-masked kv blocks
are skipped with pl.when so the kernel does no dead MXU work beyond the
diagonal half-bricks.

Interpreted on CPU, compiled on TPU (`repro.kernels.pallas_on_platform`).
Validated on CPU against ref.mha_reference and against
the custom-vjp jnp implementation in ops.py (which is also the TPU-side
fallback when use_pallas=False).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_on_platform


NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int,
                block_q: int, block_k: int, n_kv: int, seq_kv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = i * block_q
    k_lo = j * block_k
    # skip blocks fully outside the mask
    live = True
    if causal:
        live = k_lo <= q_lo + block_q - 1
    if window:
        live = jnp.logical_and(live, k_lo + block_k - 1 > q_lo - window) \
            if causal else (k_lo + block_k - 1 > q_lo - window)

    @pl.when(live if not isinstance(live, bool) else True)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_kv
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_kv - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, :, 0, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_scr[...] + jnp.log(l)


def flash_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window: int = 0,
                     block_q: int = 128, block_k: int = 128
                     ) -> Tuple[jax.Array, jax.Array]:
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) -> (out, lse)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Skv + pad_k) // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=nk, seq_kv=Skv)

    def run(q, k, v, interpret):
        return pl.pallas_call(
            kernel,
            grid=(B, Hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, 1, D),
                             lambda b, h, i, j: (b, i, h, 0)),
                pl.BlockSpec((1, block_k, 1, D),
                             lambda b, h, i, j, G=G: (b, j, h // G, 0)),
                pl.BlockSpec((1, block_k, 1, D),
                             lambda b, h, i, j, G=G: (b, j, h // G, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, 1, D),
                             lambda b, h, i, j: (b, i, h, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, h, i, j: (b, i, h)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Sq + pad_q, Hq, D), q.dtype),
                jax.ShapeDtypeStruct((B, Sq + pad_q, Hq), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(q, k, v)

    out, lse = pallas_on_platform(run, q, k, v)
    return out[:, :Sq], lse[:, :Sq]
