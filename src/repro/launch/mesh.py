"""Mesh construction and per-chip hardware peaks.

A TPU v5e pod is a 16x16 chip torus; multi-pod jobs add a leading ``pod``
axis connected over DCN.  Functions, not module constants, so importing this
module never touches jax device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """`jax.make_mesh` with every axis `Auto`: the sharding rules place
    activations with `with_sharding_constraint`, which only accepts Auto
    axes (jax's default axis type is Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Published per-chip peaks, keyed by jax's `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e" (ICI/DCN per-link figures as modelled by
# the roofline).
V5E = "TPU v5 lite"
HARDWARE = {
    V5E: {
        "peak_flops_bf16": 197e12,     # FLOP/s
        "hbm_bandwidth": 819e9,        # B/s
        "ici_link_bandwidth": 50e9,    # B/s per link (~ per direction)
        "dcn_bandwidth": 25e9,         # B/s per host aggregate (cross-pod)
        "hbm_bytes": 16e9,
    },
}


def hardware(kind: str) -> dict:
    """Peaks of one chip of `kind`; an unknown kind is an error."""
    try:
        return HARDWARE[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(HARDWARE)}") from None
