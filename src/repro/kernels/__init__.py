"""Pallas kernels, and how they are lowered.

Every Pallas kernel in the repo goes through `pallas_on_platform`: the
program runs the Pallas interpreter where it is lowered for the CPU and the
Mosaic-compiled kernel everywhere else (the TPU).  The choice follows the
platform the program is lowered for, not the host's default backend, so a
program compiled for a described TPU from a CPU-only machine carries the
real kernel (`tpu_custom_call`).
"""
from __future__ import annotations

import functools

import jax


def pallas_on_platform(call, *args, **kwargs):
    """``call(*args, interpret=..., **kwargs)``: interpreted on CPU,
    compiled on every other platform."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(call, interpret=True, **kwargs),
        default=functools.partial(call, interpret=False, **kwargs))
