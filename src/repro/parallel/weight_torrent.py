"""Torrent-style weight distribution along the pod axis, in JAX collectives.

The paper's seeder/leecher duality applied to checkpoint restore: instead of
every pod hammering the blob store (N x bytes of egress), pod 0 reads once
and the pods exchange *pieces* peer-to-peer.  On a torus the optimal plan is
the classic two-phase broadcast, which is exactly a torrent swarm with a
deterministic schedule:

  phase 1 (scatter): the seeder sends piece j to pod j          (ring hops)
  phase 2 (ring all-gather): every pod forwards the piece it owns around the
  ring until all pods hold all pieces; every pod uploads in every round —
  total time ~ 2 * bytes / link_bw, independent of pod count.

Both phases are ``lax.ppermute`` steps inside one ``shard_map`` over the
``pod`` axis — no host round-trips; the pytree flavour runs them one leaf
at a time so a full-width model fits next to its own replicas.
``core/swarm.py`` provides the host-level (file) variant and the
rarest-first plan used when pods hold disjoint initial pieces.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring(local_pieces: jax.Array, axis: str, n: int,
          seeder: int) -> jax.Array:
    """Per-device body: every device ends with the seeder's pieces
    (P, ...) — piece t is ``local_pieces[t]``, of any shape.  Pipelined
    ring: the seeder emits piece t at step t; a node at distance d >= 1
    receives piece (t - d + 1) at step t and forwards what it received
    last step."""
    idx = jax.lax.axis_index(axis)
    is_seeder = idx == seeder
    d = jnp.mod(idx - seeder, n)            # ring distance from the seeder
    fwd = [(i, (i + 1) % n) for i in range(n)]
    P_ = local_pieces.shape[0]

    received = jnp.zeros_like(local_pieces)
    cur = jnp.zeros(local_pieces.shape[1:], local_pieces.dtype)
    for t in range(P_ + n - 2):
        inject = local_pieces[min(t, P_ - 1)]
        send = jnp.where(is_seeder, inject, cur)
        cur = jax.lax.ppermute(send, axis, fwd)
        p = t - (d - 1)
        ok = (p >= 0) & (p < P_) & (d >= 1)
        p_safe = jnp.clip(p, 0, P_ - 1)
        old = jax.lax.dynamic_slice_in_dim(received, p_safe, 1, axis=0)
        upd = jnp.where(ok, cur[None], old)
        received = jax.lax.dynamic_update_slice_in_dim(
            received, upd, p_safe, axis=0)
    return jnp.where(is_seeder, local_pieces, received)


def torrent_broadcast_pieces(local_views: jax.Array, mesh: Mesh,
                             axis: str = "pod", seeder: int = 0) -> jax.Array:
    """Broadcast the seeder pod's pieces to all pods.

    local_views: (n_pods, P, L), sharded over `axis` on dim 0 — each pod's
    slice is its local buffer (only the seeder's is meaningful, e.g. freshly
    read from the checkpoint store).  Returns the same shape with every pod
    holding the seeder's pieces.  Pipelined ring: 2P-ish ppermute steps, the
    seeder uploads each piece exactly once (vs (n-1)x for naive fan-out).
    """
    n = mesh.shape[axis]
    if n == 1:
        return local_views
    spec = P(axis, None, None)
    return shard_map(lambda view: _ring(view[0], axis, n, seeder)[None],
                     mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(local_views)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "seeder",
                                             "rows"))
def _broadcast_leaf(views, mesh: Mesh, axis: str, seeder: int, rows: int):
    """Ring one leaf's pieces and hand each device its own received copy
    as its shard of a mesh-replicated leaf (out_specs P(): the devices'
    copies are identical by construction, so no collective re-checks).
    ``views`` is (pods, P, rows / P, *leaf.shape[1:]): pieces are slabs
    of the leaf's leading axis, so no step changes the tiled layout of
    its trailing dims (a flattened leaf made the TPU compiler emit code
    in proportion to the leaf's size)."""
    n = mesh.shape[axis]
    spec = P(axis, *([None] * (views.ndim - 1)))

    def body(view):
        pieces = _ring(view[0], axis, n, seeder)
        return pieces.reshape((-1,) + pieces.shape[2:])[:rows]

    return shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=P(),
                     check_vma=False)(views)


def torrent_broadcast(tree, mesh: Mesh, axis: str = "pod", seeder: int = 0,
                      n_pieces: int = 0):
    """Pytree flavour, one leaf at a time: the seeder pod's devices load
    the leaf's bytes, every other device starts from zeros, the ring
    delivers the pieces, and the leaf comes back replicated over `mesh`
    with each device holding the copy it received.  One leaf is in flight
    at a time, so the transient device memory is a few copies of the
    largest leaf rather than of the whole tree.
    """
    n = mesh.shape[axis]
    if n == 1:
        return tree
    n_pieces = n_pieces or n

    def leaf(x):
        host = np.asarray(x)
        if host.ndim == 0:
            return leaf(host[None])[0]
        rows = host.shape[0]
        pad = (-rows) % n_pieces
        if pad:
            host = np.concatenate(
                [host, np.zeros((pad,) + host.shape[1:], host.dtype)])
        pieces = host.reshape((1, n_pieces, -1) + host.shape[1:])
        shape = (n,) + pieces.shape[1:]
        views_sharding = NamedSharding(
            mesh, P(axis, *([None] * (len(shape) - 1))))
        bufs = []
        for dev, index in views_sharding.addressable_devices_indices_map(
                shape).items():
            pod = index[0].start or 0
            bufs.append(jax.device_put(pieces, dev) if pod == seeder
                        else jnp.zeros(pieces.shape, pieces.dtype,
                                       device=dev))
        views = jax.make_array_from_single_device_arrays(
            shape, views_sharding, bufs)
        return _broadcast_leaf(views, mesh, axis, seeder, rows)

    return jax.tree_util.tree_map(leaf, tree)


def broadcast_cost_model(bytes_total: float, n_pods: int,
                         link_Bps: float = 25e9) -> dict:
    """Analytic cost: torrent (scatter+allgather) vs naive seeder fan-out."""
    torrent_s = 2.0 * bytes_total * (n_pods - 1) / n_pods / link_Bps
    naive_s = bytes_total * (n_pods - 1) / link_Bps
    return {"torrent_s": torrent_s, "naive_s": naive_s,
            "speedup": naive_s / max(torrent_s, 1e-12)}


def cold_start_cost_model(bytes_total: float, n_replicas: int,
                          link_Bps: float = 12.5e6,
                          n_pieces: int = 128) -> dict:
    """Analytic replica cold-start: origin-only vs swarm flash crowd.

    Origin-only serialises R full images through the origin's uplink
    (time ~ R * bytes / link, origin egress R * bytes).  A piece-wise
    swarm needs the origin to upload each piece roughly once; the last
    replica finishes after its own download plus the pipeline ramp of
    ~log2(R) piece-times, and origin egress collapses to ~1 image —
    the bounds Scenario XI's simulated runs should approach.
    """
    piece_s = bytes_total / max(n_pieces, 1) / link_Bps
    origin_s = n_replicas * bytes_total / link_Bps
    swarm_s = bytes_total / link_Bps \
        + piece_s * max(1, n_replicas).bit_length()
    return {"origin_s": origin_s, "swarm_s": swarm_s,
            "origin_egress_bytes": n_replicas * bytes_total,
            "swarm_origin_egress_bytes": bytes_total,
            "speedup": origin_s / max(swarm_s, 1e-12)}
