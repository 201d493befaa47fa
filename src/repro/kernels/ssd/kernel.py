"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (batch, heads, chunks) with the chunk axis sequential ("arbitrary");
the (P, N) recurrent state lives in VMEM scratch across chunk steps.  Within
a chunk everything is dense matmul work for the MXU:

   y_diag = ((C B^T) .* decay_tril) (dt x)         intra-chunk
   y_off  = (C state_in) .* decay_from_start       inter-chunk
   state  = state_in * chunk_decay + (B dt x decay_to_end)

The hardware-adaptation choice (vs the paper-adjacent Triton kernel): TPU
prefers one sequential grid axis + VMEM-resident state over warp-level
pipelining, and L=chunk x N/P tiles sized to MXU multiples.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_on_platform


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref,
                state_scr, *, n_chunks: int, chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (L,)
    a = a_ref[0].astype(jnp.float32)                 # ()
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)

    dtA = dt * a                                     # (L,)
    cum = jnp.cumsum(dtA)                            # (L,)
    xdt = x * dt[:, None]                            # (L, P)

    # intra-chunk
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    diff = cum[:, None] - cum[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1)
    dec = jnp.where(li >= lj, jnp.exp(diff), 0.0)
    y = jax.lax.dot_general(cb * dec, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)

    # inter-chunk using incoming state
    state_in = state_scr[...]                        # (P, N)
    dec0 = jnp.exp(cum)                              # (L,)
    y = y + (jax.lax.dot_general(Cm, state_in, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             * dec0[:, None])

    # state update
    decT = jnp.exp(cum[-1] - cum)                    # (L,)
    upd = jax.lax.dot_general(xdt * decT[:, None], Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state_in * jnp.exp(cum[-1]) + upd

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _emit():
        fin_ref[0, 0, :, :] = state_scr[...]


def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, chunk: int = 128
               ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N) with G | H.

    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad dt with zeros => decay 1, no state contribution
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    kernel = functools.partial(_ssd_kernel, n_chunks=nc, chunk=chunk)

    def run(x, dt, A, Bm, Cm, interpret):
        return pl.pallas_call(
            kernel,
            grid=(Bsz, H, nc),
            in_specs=[
                pl.BlockSpec((1, chunk, 1, Pd),
                             lambda b, h, c: (b, c, h, 0)),
                pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
                pl.BlockSpec((1,), lambda b, h, c: (h,)),
                pl.BlockSpec((1, chunk, 1, N),
                             lambda b, h, c, rep=rep: (b, c, h // rep, 0)),
                pl.BlockSpec((1, chunk, 1, N),
                             lambda b, h, c, rep=rep: (b, c, h // rep, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, 1, Pd),
                             lambda b, h, c: (b, c, h, 0)),
                pl.BlockSpec((1, 1, Pd, N), lambda b, h, c: (b, h, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((Bsz, Sp, H, Pd), x.dtype),
                jax.ShapeDtypeStruct((Bsz, H, Pd, N), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(x, dt, A, Bm, Cm)

    y, fin = pallas_on_platform(run, x, dt, A, Bm, Cm)
    return y[:, :S], fin
