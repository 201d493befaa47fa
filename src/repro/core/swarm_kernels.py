"""Batched swarm decision kernels: rarest-first scoring and choke ranking
for ALL nodes in one vectorized pass (ROADMAP: "N=2000+ flash crowds via
batched, array-native simulation").

The scalar `PieceExchange` engine makes every decision one Python call at
a time: `rarest_first_order_np` sorts one node's missing pieces, and
`_rechoke_app` ranks one holder's candidates.  At N=2000 those calls —
not the protocol — dominate the simulation wall-clock.  This module
computes the same decisions for a whole swarm as array programs over the
`SwarmState` layout (core/swarm_arrays.py):

  * `rarest_keys` / `rarest_orders`  — per-(node, piece) composite sort
    keys reproducing `rarest_first_order_np`'s lexsort order
    ``(counts, (p + offset) % n, p)`` exactly, argsorted per row;
  * `choke_order` — per-holder candidate ranking reproducing
    `_rechoke_app`'s ``sorted(cands, key=(-rate_from, -rate_to, name))``
    via a chain of stable argsorts.

Three interchangeable backends hide behind the same API, mirroring the
repo's kernel discipline (`repro.kernels.ssd.ops`: reference impl +
differential tests + selectable fast path):

  * ``numpy``  — the host reference and the default;
  * ``jax``    — jitted `jnp` version of the same math, on JAX's default
    device;
  * ``pallas`` — `rarest_keys` and `island_has` as Pallas kernels
    (interpreted on CPU, Mosaic-compiled on TPU); the other kernels share
    the jax path.

`set_backend` / the ``REPRO_SWARM_BACKEND`` env var select globally;
every function also takes an explicit ``backend=``.  An unknown backend
raises: a run never silently degrades to numpy.  The jax/pallas wrappers
pad every varying dimension to a power-of-two bucket (pad rows and
candidates are inert), so a run compiles each kernel once per bucket, not
once per tick.  Differential tests (tests/test_swarm_batch.py) assert all
backends reproduce the scalar decisions bit-for-bit.

Each jitted kernel compiles to a module named ``jit_swarm_<kernel>``
(`_device_kernel`), the name a device trace is keyed on.  Every device
call goes through `_call`, which times its dispatch and its fetch as
`core.trace` spans and counts its bytes and its call.
"""
from __future__ import annotations

import collections
import os
from functools import partial, wraps
from typing import List, Optional, Sequence

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels import pallas_on_platform

# sentinel key for pieces a row must not request (held, pending, invalid):
# larger than any real composite key so they argsort to the back
KEY_INF = np.int64(2 ** 62)

# int32-safe sentinel for the fused request-matching / endgame top-k
# kernels (the jax backend runs without x64): holder keys there are
# cost * 2^20 + rank < 2^27, so 2^30 is strictly above any real key
KEY_INF32 = np.int32(2 ** 30)

_backend = os.environ.get("REPRO_SWARM_BACKEND", "numpy")

# (kernel, platform) -> calls whose output came back from that platform:
# the proof that a jax/pallas run put its kernels on the device it claims
DEVICE_CALLS: collections.Counter = collections.Counter()


def available_backends() -> List[str]:
    return ["numpy", "jax", "pallas"]


def _check_backend(name: str) -> str:
    if name not in available_backends():
        raise ValueError(f"unknown or unavailable swarm kernel backend "
                         f"{name!r}; choose one of {available_backends()}")
    return name


def set_backend(name: str) -> str:
    """Select the default backend; raises on an unknown one."""
    global _backend
    _backend = _check_backend(name)
    return _backend


def get_backend(backend: Optional[str] = None) -> str:
    return _check_backend(backend if backend is not None else _backend)


def _bucket(n: int) -> int:
    """Padded extent of a varying kernel dimension: the next power of
    two, at least 8 (one sublane tile)."""
    return max(8, 1 << max(int(n) - 1, 0).bit_length())


def _pad(a: np.ndarray, shape, fill) -> np.ndarray:
    """`a` in the top-left corner of a `shape` array filled with `fill`."""
    a = np.asarray(a)
    if a.shape == tuple(shape):
        return a
    out = np.full(shape, fill, dtype=a.dtype)
    out[tuple(slice(0, d) for d in a.shape)] = a
    return out


def _device_kernel(name: str, **jit_kw):
    """Jit a kernel body under a stable name: the compiled module reads
    ``jit_swarm_<name>`` and its operations sit in the ``swarm.<name>``
    named scope, whatever the Python function is called."""
    def wrap(body):
        @wraps(body)
        def scoped(*args, **kw):
            with jax.named_scope(f"swarm.{name}"):
                return body(*args, **kw)
        scoped.__name__ = scoped.__qualname__ = f"swarm_{name}"
        return jax.jit(scoped, **jit_kw)
    return wrap


def _call(kernel, *operands: np.ndarray, **static) -> np.ndarray:
    """Run a `_device_kernel` on padded host operands and bring its
    result back: the ``swarm.kernel.<name>.dispatch`` span covers the
    call until it returns, the ``.fetch`` span the wait for the result
    and its copy to the host.  Counts the bytes each way and the call
    in `DEVICE_CALLS`, under the device's platform."""
    name = kernel.__name__[len("swarm_"):]
    trace.count(f"swarm.h2d_bytes.{name}", sum(a.nbytes for a in operands))
    with trace.span(f"swarm.kernel.{name}.dispatch"):
        out = kernel(*operands, **static)
    DEVICE_CALLS[name, next(iter(out.devices())).platform] += 1
    with trace.span(f"swarm.kernel.{name}.fetch"):
        host = np.asarray(out)
    trace.count(f"swarm.d2h_bytes.{name}", host.nbytes)
    return host


# ====================== rarest-first scoring ============================ #
# The scalar order (swarm.rarest_first_order_np) is
#     np.lexsort((m, (m + offset) % n, counts[m]))
# i.e. sort missing piece ids by (availability, rotated id, raw id).  With
# counts < COUNT_CAP and piece ids < n the three keys pack losslessly into
# one int64:  key = (counts * n + rot) * n + p  — one argsort per row then
# reproduces the lexsort order for ALL rows at once.

def rarest_keys_np(counts: np.ndarray, offsets: np.ndarray,
                   n_pieces: int) -> np.ndarray:
    """(R, P) int64 composite keys; rows are nodes, columns pieces."""
    n = max(int(n_pieces), 1)
    p = np.arange(n, dtype=np.int64)
    rot = (p[None, :] + np.asarray(offsets, dtype=np.int64)[:, None]) % n
    return (counts.astype(np.int64)[None, :] * n + rot) * n + p[None, :]


# rows per Pallas grid step (a multiple of the 8-row sublane tile)
_ROW_BLOCK = 256


def _rarest_keys_kernel(counts_ref, off_ref, out_ref, *, n: int):
    """One (rows, n) block of composite keys.  ``off`` arrives reduced
    mod n, so the rotation is one conditional subtract (no vector rem)."""
    c = counts_ref[...]                                  # (1, n)
    off = off_ref[...]                                   # (rows, 1)
    p = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    rot = p + off
    rot = jnp.where(rot >= n, rot - n, rot)
    out_ref[...] = (c * n + rot) * n + p


def _rarest_keys_pallas(counts, offsets, n: int, interpret: bool):
    rows = offsets.shape[0]
    blk = min(rows, _ROW_BLOCK)
    return pl.pallas_call(
        partial(_rarest_keys_kernel, n=n),
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.int32),
        interpret=interpret,
    )(counts, offsets)


@_device_kernel("rarest_keys", static_argnames=("n_pieces", "impl"))
def _rarest_keys_jax(counts, offsets, n_pieces: int, impl: str = "jnp"):
    # int32 throughout (jax runs without x64): the composite key needs
    # counts * n^2 < 2^31, which holds for every simulated swarm
    # (counts <= N, N * P^2 < 2^31 up to N=2000, P=1024)
    n = max(int(n_pieces), 1)
    c = counts.astype(jnp.int32)[None, :]
    off = offsets.astype(jnp.int32)[:, None]          # already mod n
    if impl == "pallas":
        return pallas_on_platform(_rarest_keys_pallas, c, off, n=n)
    p = jnp.arange(n, dtype=jnp.int32)[None, :]
    rot = (p + off) % n
    return (c * n + rot) * n + p


def rarest_keys(counts: np.ndarray, offsets: np.ndarray, n_pieces: int,
                backend: Optional[str] = None) -> np.ndarray:
    """Composite rarest-first sort keys for many nodes at once.

    ``counts``  — (P,) availability counts (partial holders; a uniform
                  full-seeder constant cannot change the order);
    ``offsets`` — (R,) per-node tie-break rotations (the scalar engine's
                  ``sum(ord(c) for c in node_id + app_id)``).
    Returns (R, P) int64 keys; ``argsort(keys[r])`` is exactly
    ``rarest_first_order_np(range(P), counts, offsets[r], P)``.
    """
    b = get_backend(backend)
    if b == "numpy":
        return rarest_keys_np(counts, offsets, n_pieces)
    offsets = np.asarray(offsets, dtype=np.int64)
    rows = offsets.shape[0]
    # reduce on the host: int64 rotations would wrap in int32 on device
    off = _pad(offsets % max(int(n_pieces), 1), (_bucket(rows),), 0)
    out = _call(_rarest_keys_jax,
                np.asarray(counts, dtype=np.int32), off.astype(np.int32),
                n_pieces=int(n_pieces),
                impl="pallas" if b == "pallas" else "jnp")
    return out[:rows].astype(np.int64)


def rarest_orders(missing: np.ndarray, counts: np.ndarray,
                  offsets: np.ndarray, n_pieces: int,
                  backend: Optional[str] = None) -> np.ndarray:
    """Batched `rarest_first_order_np`: full piece order per node.

    ``missing`` is (R, P) bool — True where the node may request the
    piece.  Returns (R, P) int32 piece ids; row r's first
    ``missing[r].sum()`` entries are that node's missing pieces in
    rarest-first order (non-missing pieces sort to the back via KEY_INF).
    """
    keys = rarest_keys(counts, offsets, n_pieces, backend=backend)
    keys = np.where(np.asarray(missing, dtype=bool), keys, KEY_INF)
    return np.argsort(keys, axis=1, kind="stable").astype(np.int32)


# ================== topology-aware (P4P) scoring ======================== #
# Cost-aware piece selection (ISSUE 7): each node ranks its missing pieces
# by (network cost of the cheapest holder island, rarity, rotated id, id).
# Cost is PRIMARY: a piece held on the node's own island always beats one
# only available across an ISP boundary, which is what cuts cross-ISP
# bytes.  When every piece has the same cheapest-holder cost — one island,
# or all same-island holders starved away — the cost plane is uniform and
# the order degrades to exactly `rarest_orders` (the decay-to-rarity
# property the chaos overlay test pins).
#
# The backend-differentiated work is `island_has`: a (K, P) island-level
# availability reduction over the (N, P) have-matrix, computed as a
# onehot(K, N) @ have(N, P) matmul (MXU-shaped on TPU).  The final
# cost ⊕ rarity combine happens host-side in int64 over the backend's
# int32/int64 base keys — same discipline as the masking + argsort in
# `rarest_orders`, and it sidesteps the int32 headroom the jax/pallas
# base keys already exhaust (counts * P^2 < 2^31 leaves no room for a
# cost multiplier).

# sentinel "no holder anywhere" cost: above any real ALTO cost (<= 15)
COST_NONE = np.int64(64)


def island_has_np(have: np.ndarray, member: np.ndarray) -> np.ndarray:
    """(K, P) bool: does any alive node of island k hold piece p?

    ``have``   — (N, P) bool/int piece-holding matrix (alive holders only;
                 the caller zeroes dead/irrelevant rows);
    ``member`` — (K, N) bool island membership (onehot of island index).
    """
    m = np.asarray(member, dtype=np.int32)
    h = np.asarray(have, dtype=np.int32)
    return (m @ h) > 0


# have-matrix rows per Pallas grid step of the island reduction
_ISLAND_BLOCK = 512


def _island_has_kernel(member_ref, have_ref, out_ref):
    """Accumulate one (K, block) x (block, P) slice of the membership x
    have product; the output block stays resident across the N axis."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(member_ref[...], have_ref[...],
                            preferred_element_type=jnp.float32
                            ).astype(jnp.int32)


def _island_has_pallas(have, member, interpret: bool):
    """Island availability counts as an MXU reduction over bounded
    (block, P) have-tiles (0/1 operands: the f32 sums are exact)."""
    k, n = member.shape
    p = have.shape[1]
    blk = min(n, _ISLAND_BLOCK)
    return pl.pallas_call(
        _island_has_kernel,
        grid=(n // blk,),
        in_specs=[pl.BlockSpec((k, blk), lambda i: (0, i)),
                  pl.BlockSpec((blk, p), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((k, p), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, p), jnp.int32),
        interpret=interpret,
    )(member, have)


@_device_kernel("island_has", static_argnames=("impl",))
def _island_has_jax(have, member, impl: str = "jnp"):
    if impl == "pallas":
        cnt = pallas_on_platform(_island_has_pallas,
                                 have.astype(jnp.float32),
                                 member.astype(jnp.float32))
    else:
        cnt = member.astype(jnp.int32) @ have.astype(jnp.int32)
    return cnt > 0


def island_has(have: np.ndarray, member: np.ndarray,
               backend: Optional[str] = None) -> np.ndarray:
    """Backend-selectable island-level availability reduction."""
    b = get_backend(backend)
    if b == "numpy":
        return island_has_np(have, member)
    k, n = np.shape(member)
    nb = _bucket(n)
    # island count padded to a sublane tile, node count to a bucket that
    # the Pallas have-tile divides; pad rows/islands are all-False
    kb = -(-max(k, 1) // 8) * 8
    hv = _pad(np.asarray(have, dtype=bool), (nb, np.shape(have)[1]), False)
    mb = _pad(np.asarray(member, dtype=bool), (kb, nb), False)
    out = _call(_island_has_jax, hv, mb,
                impl="pallas" if b == "pallas" else "jnp")
    return out[:k].astype(bool)


def min_island_cost(avail: np.ndarray, cost: np.ndarray) -> np.ndarray:
    """(K, P) per-source-island cheapest-holder cost plane.

    ``avail`` — (K, P) bool island availability (from `island_has`);
    ``cost``  — (K, K) ALTO cost matrix (row = source island).
    Entry [s, p] is the minimum cost from island s to any island holding
    piece p; pieces nobody holds get COST_NONE (they are masked out of
    requests anyway, but the sentinel keeps the key finite and uniform).
    Plain numpy on purpose: K x K x P is tiny next to the N x P reduction,
    and sharing one implementation keeps every backend bit-identical.
    """
    a = np.asarray(avail, dtype=bool)                       # (K, P)
    c = np.asarray(cost, dtype=np.int64)                    # (K, K)
    # broadcast: plane[s, k, p] = cost[s, k] where island k holds p
    plane = np.where(a[None, :, :], c[:, :, None], COST_NONE)
    return plane.min(axis=1)                                # (K, P)


def cost_rarest_keys(counts: np.ndarray, offsets: np.ndarray,
                     piece_cost: np.ndarray, n_pieces: int,
                     backend: Optional[str] = None) -> np.ndarray:
    """Cost-primary composite keys: (R, P) int64
    ``key = piece_cost * span + rarest_key`` with
    ``span = (max_count + 1) * n^2`` so the cost strictly dominates and
    the within-cost order is exactly the rarest-first order.

    ``piece_cost`` — (R, P) per-(node, piece) cheapest-holder cost (the
    node's island row of the `min_island_cost` plane).  A uniform cost
    plane shifts every key by the same amount: ordering identical to
    `rarest_keys` (decay-to-rarity, differential-tested).
    """
    base = rarest_keys(counts, offsets, n_pieces, backend=backend)
    n = max(int(n_pieces), 1)
    max_count = int(np.asarray(counts).max()) if np.asarray(counts).size \
        else 0
    span = np.int64(max_count + 1) * n * n
    return np.asarray(piece_cost, dtype=np.int64) * span \
        + base.astype(np.int64)


def cost_orders(missing: np.ndarray, counts: np.ndarray,
                offsets: np.ndarray, piece_cost: np.ndarray,
                n_pieces: int,
                backend: Optional[str] = None) -> np.ndarray:
    """Batched cost-aware piece order per node (the P4P `rarest_orders`).

    Same contract as `rarest_orders` plus ``piece_cost`` (R, P): row r's
    first ``missing[r].sum()`` entries are node r's missing pieces ordered
    by (cheapest-holder cost, rarity, rotated id, id).
    """
    keys = cost_rarest_keys(counts, offsets, piece_cost, n_pieces,
                            backend=backend)
    keys = np.where(np.asarray(missing, dtype=bool), keys, KEY_INF)
    return np.argsort(keys, axis=1, kind="stable").astype(np.int32)


# ========================= choke ranking ================================ #
def choke_order_np(recv: np.ndarray, sent: np.ndarray, cand: np.ndarray,
                   ranks: np.ndarray) -> np.ndarray:
    """Rank every holder's unchoke candidates in one pass.

    Reproduces `_rechoke_app`'s ``sorted(cands, key=lambda p:
    (-rate_from[p], -rate_to[p], p))`` for all holders at once via a
    chain of stable argsorts (last key applied last is primary).
    ``ranks`` maps column -> lexicographic rank of the node name, which
    is what the scalar string tie-break sorts by; a 2-D (H, C) ranks
    matrix gives every holder row its own tie-break key (P4P mode packs
    the ALTO cost above the name rank).  Non-candidate columns are
    pushed to the back.  Returns (H, C) int32 column indices.
    """
    cand = np.asarray(cand, dtype=bool)
    # non-candidates must lose every comparison: real rates are >= 0
    r1 = np.where(cand, recv, -1.0)
    r2 = np.where(cand, sent, -1.0)
    rk = np.asarray(ranks)
    if rk.ndim == 1:
        rk = rk[None, :]
    nm = np.where(cand, rk, rk.max() + 1 if rk.size
                  else 1).astype(np.int64)
    # stable multi-key sort: name (tie-break), then -sent, then -recv
    order = np.argsort(nm, axis=1, kind="stable")
    for key in (-r2, -r1):
        k = np.take_along_axis(key, order, axis=1)
        order = np.take_along_axis(order,
                                   np.argsort(k, axis=1, kind="stable"),
                                   axis=1)
    return order.astype(np.int32)


@_device_kernel("choke_order")
def _choke_order_jax(recv, sent, cand, ranks):
    r1 = jnp.where(cand, recv, -1.0)
    r2 = jnp.where(cand, sent, -1.0)
    # int32 keys (jax runs without x64): callers packing cost above
    # the name rank must keep cost * shift + rank < 2^31
    rk = ranks if ranks.ndim == 2 else ranks[None, :]
    maxr = jnp.max(rk) + 1 if rk.size else 1
    nm = jnp.where(cand, rk, maxr).astype(jnp.int32)
    order = jnp.argsort(nm, axis=1, stable=True)
    for key in (-r2, -r1):
        k = jnp.take_along_axis(key, order, axis=1)
        order = jnp.take_along_axis(
            order, jnp.argsort(k, axis=1, stable=True), axis=1)
    return order.astype(jnp.int32)


def choke_order(recv: np.ndarray, sent: np.ndarray, cand: np.ndarray,
                ranks: np.ndarray,
                backend: Optional[str] = None) -> np.ndarray:
    b = get_backend(backend)
    if b == "numpy":
        return choke_order_np(recv, sent, cand, ranks)
    # the pallas backend shares the jax ranking path: sorts stay in XLA.
    # Pad holders and columns are non-candidates: they sort behind every
    # real column, so the leading (H, C) block is the unpadded ranking.
    h, c = np.shape(cand)
    shape = (_bucket(h), _bucket(c))
    rk = np.asarray(ranks, dtype=np.int32)
    rk = _pad(rk, shape if rk.ndim == 2 else shape[1:], 0)
    out = _call(
        _choke_order_jax,
        _pad(np.asarray(recv, dtype=np.float32), shape, 0.0),
        _pad(np.asarray(sent, dtype=np.float32), shape, 0.0),
        _pad(np.asarray(cand, dtype=bool), shape, False), rk)
    return out[:h, :c]


# ==================== fused request matching ============================ #
# The array-native ledger (ISSUE 10) lets the hub's pump stage stop
# walking per-node dicts: every selected row's holder choice becomes one
# greedy walk over its piece order, executed for ALL rows at once
# (independent of N — the "host time sublinear in N" property).  At
# order position k the walk picks, for every still-active row, the
# lowest-keyed usable candidate holding that row's k-th rarest piece,
# marks the holder busy (one in-flight request per holder) and burns one
# pipeline-budget unit — exactly the scalar `_match_row` walk.
#
# The numpy reference walks order POSITIONS: at most P vectorized steps.
# The device kernel walks PICKS: between two picks of a row neither its
# busy holders nor its budget change, so its next pick is at the first
# position at or after the last one where an unbusy candidate holds the
# piece.  One pass finds that position for every row and picks there,
# so a call takes one pass per pick of its busiest row plus one that
# finds nothing — at most min(budget, C) + 1 passes, whatever P — and
# makes the same decisions as the position walk, bit for bit.  It works
# in piece space: one sort of each row's order gives every piece's
# position, and each pass is elementwise work and reductions over the
# (R, C, P) holdings, with no gather.
#
# Keys are the int32-safe encoding ``cost * 2^20 + rank`` (< 2^27): it
# orders identically to the scalar engine's ``rank + cost * 2^32`` —
# both are the lexicographic (cost, rank) order, since rank < 2^20 —
# but fits the x64-less jax backend.  Rows with shunned or banned
# holders stay on the scalar `_match_row` slow path, so the kernel never
# needs the shun plane.

def match_requests_np(orders: np.ndarray, n_walk: np.ndarray,
                      budgets: np.ndarray, cand: np.ndarray,
                      cand_ok: np.ndarray, cand_key: np.ndarray,
                      have: np.ndarray, full: np.ndarray) -> np.ndarray:
    """Greedy holder-match for many rows at once.

    ``orders``   — (R, P) int piece ids, each row's request order;
    ``n_walk``   — (R,) how many order positions row r may walk
                   (its missing-piece count);
    ``budgets``  — (R,) pipeline budget (requests row r may issue);
    ``cand``     — (R, C) int32 candidate holder rows, -1 padded;
    ``cand_ok``  — (R, C) bool: candidate is usable (valid, alive,
                   holder-ish, not self, not already busy for the row);
    ``cand_key`` — (R, C) int32 preference key, lower wins
                   (``cost * 2^20 + name_rank``);
    ``have``     — (N, P) bool piece-holding matrix; ``full`` — (N,) bool.

    Returns (R, P) int32 picks: ``picks[r, k]`` is the holder row chosen
    for piece ``orders[r, k]``, or -1.  A row stops when its budget is
    exhausted, its walk ends, or all its candidates are busy.
    """
    orders = np.asarray(orders)
    R, P = orders.shape
    picks = np.full((R, P), -1, dtype=np.int32)
    C = cand.shape[1] if cand.ndim == 2 else 0
    if R == 0 or C == 0:
        return picks
    safe = np.where(cand >= 0, cand, 0)
    hv = np.asarray(have, dtype=bool)[safe] \
        | np.asarray(full, dtype=bool)[safe][:, :, None]     # (R, C, P)
    taken = ~np.asarray(cand_ok, dtype=bool)
    budget = np.asarray(budgets, dtype=np.int64).copy()
    walk = np.asarray(n_walk, dtype=np.int64)
    key = np.asarray(cand_key, dtype=np.int64)
    ridx = np.arange(R)
    kmax = int(min(max(int(walk.max(initial=0)), 0), P))
    for k in range(kmax):
        act = (budget > 0) & (k < walk) & ~taken.all(axis=1)
        if not act.any():
            break
        p = orders[:, k].astype(np.int64)
        okk = ~taken & hv[ridx, :, p] & act[:, None]         # (R, C)
        sel = okk.any(axis=1)
        c = np.argmin(np.where(okk, key, np.int64(KEY_INF32)), axis=1)
        picks[sel, k] = cand[sel, c[sel]]
        taken[sel, c[sel]] = True
        budget[sel] -= 1
    return picks


@_device_kernel("match_requests")
def _match_requests_jax(orders, n_walk, budgets, cand, cand_ok,
                        cand_key, have, full):
    R, P = orders.shape
    C = cand.shape[1]
    safe = jnp.where(cand >= 0, cand, 0)
    hv = have[safe] | full[safe][:, :, None]             # (R, C, P)
    # at[r, p]: the order position of piece p in row r (rows of
    # `orders` are permutations); a per-element gather into order
    # positions costs the chip more than the whole walk
    at = jnp.argsort(orders, axis=1).astype(jnp.int32)
    inf = jnp.int32(KEY_INF32)
    key0 = jnp.where(cand_ok, cand_key.astype(jnp.int32), inf)
    kpos = jnp.arange(P, dtype=jnp.int32)[None, :]
    cpos = jnp.arange(C, dtype=jnp.int32)[None, :]
    inwalk = at < n_walk[:, None]

    def more(carry):
        return carry[4]

    def body(carry):
        picks, taken, budget, pos, _ = carry
        # each candidate's first usable position at or after pos; the
        # row's next pick is at the least of them
        win = inwalk & (at >= pos[:, None]) & (budget > 0)[:, None]
        usable = ~taken[:, :, None] & hv & win[:, None, :]
        first = jnp.min(jnp.where(usable, at[:, None, :], P), axis=2)
        k = jnp.min(first, axis=1)
        sel = k < P
        c = jnp.argmin(jnp.where(first == k[:, None], key0, inf), axis=1)
        hit = (cpos == c[:, None]) & sel[:, None]
        val = jnp.max(jnp.where(hit, cand, -1), axis=1)
        picks = jnp.where((kpos == k[:, None]) & sel[:, None],
                          val[:, None], picks)
        return (picks, taken | hit, budget - sel.astype(budget.dtype),
                jnp.where(sel, k + 1, P), jnp.any(sel))

    picks0 = jnp.full((R, P), -1, dtype=jnp.int32)
    picks = jax.lax.while_loop(
        more, body,
        (picks0, ~cand_ok, budgets.astype(jnp.int32),
         jnp.zeros((R,), jnp.int32), jnp.bool_(True)))[0]
    return picks


def match_requests(orders: np.ndarray, n_walk: np.ndarray,
                   budgets: np.ndarray, cand: np.ndarray,
                   cand_ok: np.ndarray, cand_key: np.ndarray,
                   have: np.ndarray, full: np.ndarray,
                   backend: Optional[str] = None) -> np.ndarray:
    b = get_backend(backend)
    if b == "numpy" or np.asarray(orders).shape[0] == 0 \
            or cand.shape[1] == 0:
        return match_requests_np(orders, n_walk, budgets, cand,
                                 cand_ok, cand_key, have, full)
    # the pallas backend shares the jax walk: its row gather, sort and
    # argmin stay in XLA.  The walk needs each row of `orders` to be a
    # permutation of the pieces, as `rarest_orders` and `cost_orders`
    # return.  Pad rows get no walk and no budget, pad candidates are -1
    # and unusable, pad holder rows hold nothing: none of them can be
    # picked.
    r, p = np.shape(orders)
    c = cand.shape[1]
    n = np.shape(have)[0]
    rb, cb, nb = _bucket(r), _bucket(c), _bucket(n)
    out = _call(
        _match_requests_jax,
        _pad(np.asarray(orders, dtype=np.int32), (rb, p), 0),
        _pad(np.asarray(n_walk, dtype=np.int32), (rb,), 0),
        _pad(np.asarray(budgets, dtype=np.int32), (rb,), 0),
        _pad(np.asarray(cand, dtype=np.int32), (rb, cb), -1),
        _pad(np.asarray(cand_ok, dtype=bool), (rb, cb), False),
        _pad(np.asarray(cand_key, dtype=np.int32), (rb, cb), KEY_INF32),
        _pad(np.asarray(have, dtype=bool), (nb, p), False),
        _pad(np.asarray(full, dtype=bool), (nb,), False))
    return out[:r]


# ===================== endgame holder top-k ============================= #
# The fused endgame stage ranks, per piece, the K cheapest eligible
# holders once per tick, then every endgame row selects its duplicate
# targets from that shared shortlist with pure array ops.  K =
# 2 * endgame_cap + 1 guarantees the shortlist is never exhausted: a row
# excludes at most endgame_cap already-asked holders plus itself, and
# needs at most endgame_cap picks — so whenever more eligible holders
# exist than the shortlist shows, the shortlist still covers the need.

def holder_topk_np(keys: np.ndarray, k: int) -> np.ndarray:
    """(K, P) int32 row indices of the K smallest keys per column.

    ``keys`` is (N, P); invalid holders carry KEY_INF32.  Output entries
    whose key is KEY_INF32 (or beyond N) are -1.  Ordered by ascending
    key; keys are unique per column among valid holders (they embed the
    unique name rank), so the result is deterministic.
    """
    keys = np.asarray(keys)
    n, p = keys.shape
    kk = min(int(k), n)
    if kk <= 0 or p == 0:
        return np.full((max(int(k), 0), p), -1, dtype=np.int32)
    if kk < n:
        part = np.argpartition(keys, kk - 1, axis=0)[:kk]
    else:
        part = np.tile(np.arange(n)[:, None], (1, p))
    vals = np.take_along_axis(keys, part, axis=0)
    order = np.argsort(vals, axis=0, kind="stable")
    top = np.take_along_axis(part, order, axis=0)
    tv = np.take_along_axis(keys, top, axis=0)
    out = np.where(tv < np.int64(KEY_INF32), top, -1).astype(np.int32)
    if kk < int(k):
        pad = np.full((int(k) - kk, p), -1, dtype=np.int32)
        out = np.concatenate([out, pad], axis=0)
    return out


@_device_kernel("holder_topk", static_argnames=("k",))
def _holder_topk_jax(keys, k: int):
    n, p = keys.shape
    kk = min(int(k), n)
    # top_k takes the LARGEST along the last axis; negate + transpose
    vals, idx = jax.lax.top_k(-keys.astype(jnp.int32).T, kk)
    valid = -vals < jnp.int32(KEY_INF32)
    out = jnp.where(valid, idx, -1).astype(jnp.int32).T   # (kk, P)
    if kk < int(k):
        pad = jnp.full((int(k) - kk, p), -1, dtype=jnp.int32)
        out = jnp.concatenate([out, pad], axis=0)
    return out


def holder_topk(keys: np.ndarray, k: int,
                backend: Optional[str] = None) -> np.ndarray:
    b = get_backend(backend)
    if b == "numpy":
        return holder_topk_np(keys, k)
    # the pallas backend shares the jax path (same discipline as
    # choke_order: selection/sort primitives stay in XLA).  Pad holder
    # rows carry KEY_INF32, so they only ever surface as -1.
    n, p = np.shape(keys)
    return _call(
        _holder_topk_jax,
        _pad(np.asarray(keys, dtype=np.int32), (_bucket(n), p), KEY_INF32),
        k=int(k))


# ===================== scalar-compatible wrappers ======================= #
def rarest_order_single(missing: Sequence[int], counts: np.ndarray,
                        offset: int, n_pieces: int,
                        backend: Optional[str] = None) -> List[int]:
    """One-node convenience wrapper with `rarest_first_order_np`'s exact
    signature semantics — the differential tests' bridge between the
    scalar engine and the batched kernels."""
    m = np.zeros(n_pieces, dtype=bool)
    idx = np.asarray(list(missing), dtype=np.int64)
    if idx.size == 0:
        return []
    m[idx] = True
    order = rarest_orders(m[None, :], np.asarray(counts),
                          np.asarray([offset]), n_pieces, backend=backend)
    return order[0, : idx.size].tolist()
