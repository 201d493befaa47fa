"""Compile, before the window, every kernel shape a cell can reach.

The jax and Pallas kernel wrappers pad each varying dimension to a
power-of-two bucket (at least 8), so the programs a run needs are the
bucket combinations its shapes can reach.  The fleet fixes the hub's row
count; the traffic mix bounds the rest (``warm`` in its file):

- ``fetching_rows``: rows pumped and matched in one tick (``"fleet"``:
  up to the whole fleet);
- ``choke_candidates``: one holder's rechoke shortlist.

Each wrapper is called once per combination with inert inputs, which
compiles (or loads from the persistent cache) the program and fills the
in-process dispatch cache.  Warming is a service to the measurement, not
part of it: a kernel the program no longer offers under the same name
and arguments is skipped, and any compile that then falls into the
window shows in the run's ``window_compiles``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.core import swarm_kernels as sk

# the hub groups matched rows by their count of unchoking holders and
# pads a group's width to its widest row
MATCH_WIDTHS = (8, 32, 128, 512, 2048, 8192, 1 << 30)


def bucket(n: int) -> int:
    """The power-of-two bucket (at least 8) a dimension of `n` pads to."""
    b = 8
    while b < n:
        b *= 2
    return b


def buckets(lo: int, hi: int) -> List[int]:
    """Every bucket from the one holding `lo` to the one holding `hi`."""
    out = []
    b = bucket(lo)
    while b <= bucket(hi):
        out.append(b)
        b *= 2
    return out


def _match_plan(fleet, fetch: int, rows: int):
    """`match_requests` combinations.  Every holder grants at most
    ``upload_slots`` rows, so a group whose rows each have more than
    ``lo`` holders holds at most ``upload_slots * rows / (lo + 1)``
    rows."""
    slots = int(fleet.agent_cfg["upload_slots"])
    out = []
    lo = 0
    for hi in MATCH_WIDTHS:
        if lo >= rows:
            break
        width = buckets(lo + 1, min(hi, rows))
        most = fetch if lo == 0 else min(fetch, slots * rows // (lo + 1))
        if most >= 1:
            out += [(r, c) for r in buckets(1, most) for c in width]
        lo = hi
    return out


def plan(fleet) -> Dict[str, list]:
    """The shape combinations to compile for this fleet and mix."""
    warm = fleet.traffic["warm"]
    rows = 1 + fleet.n_volunteers
    fetch = rows if warm["fetching_rows"] == "fleet" \
        else int(warm["fetching_rows"])
    out = {
        "rarest_keys": buckets(1, fetch),
        "match_requests": _match_plan(fleet, fetch, rows),
        "choke_order": [(h, c) for h in buckets(1, rows)
                        for c in buckets(1, warm["choke_candidates"])],
        "holder_topk": [rows],
    }
    if fleet.topology is not None:
        out["island_has"] = [rows]
    return out


def compile_all(fleet) -> int:
    """Run every planned kernel shape once; returns how many ran."""
    backend = fleet.hub.backend
    P = int(fleet.n_pieces)
    n = 1 + fleet.n_volunteers
    shapes = plan(fleet)
    k = 2 * int(fleet.agent_cfg["endgame_dup"]) + 1
    counts = np.zeros(P, dtype=np.int32)
    no_key = np.int32(2 ** 30)
    calls = {
        "rarest_keys": lambda r: sk.rarest_keys(
            counts, np.zeros(r, dtype=np.int64), P, backend=backend),
        "match_requests": lambda rc: sk.match_requests(
            np.zeros((rc[0], P), dtype=np.int32),
            np.zeros(rc[0], dtype=np.int64),
            np.zeros(rc[0], dtype=np.int64),
            np.full(rc, -1, np.int32), np.zeros(rc, dtype=bool),
            np.zeros(rc, np.int32), np.zeros((n, P), dtype=bool),
            np.zeros(n, dtype=bool), backend=backend),
        "choke_order": lambda hc: sk.choke_order(
            np.zeros(hc, dtype=np.float32), np.zeros(hc, dtype=np.float32),
            np.zeros(hc, dtype=bool), np.zeros(hc, dtype=np.int64),
            backend=backend),
        "holder_topk": lambda r: sk.holder_topk(
            np.full((r, P), no_key, dtype=np.int32), k, backend=backend),
        "island_has": lambda r: sk.island_has(
            np.zeros((r, P), dtype=bool),
            np.zeros((len(fleet.cost), r), dtype=bool), backend=backend),
    }
    ran = 0
    for name, combos in shapes.items():
        for combo in combos:
            try:
                calls[name](combo)
            except (AttributeError, TypeError):
                break
            ran += 1
    return ran
