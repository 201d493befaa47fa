"""Jitted wrapper for the SSD kernel with jnp fallback."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
        Cm: jax.Array, chunk: int = 128, impl: str = "pallas"
        ) -> Tuple[jax.Array, jax.Array]:
    if impl == "pallas":
        from repro.kernels.ssd.kernel import ssd_pallas
        return ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    from repro.models.ssm import ssd_scan
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
