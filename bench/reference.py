"""Plain reference of the swarm's per-tick decisions, and its control.

A tick is judged by what every volunteer sees of it: the requests it
issued and the upload slots it granted.  The harness snapshots each live
node's own records just before a tick and just after (`Snapshot`); the
reference derives from the first snapshot, by the configuration's rules
alone, what the tick had to do, and compares that with what the second
shows.  It shares no code with the program.

The rules, in the order a tick applies them:

1. grants: a holder with a free upload slot fills it with the fetching
   volunteers it does not grant yet, cheapest island first, then by name
   (grants to volunteers that finished or left are gone first); a
   volunteer granted a slot is served the endgame requests it had queued
   at that holder;
2. rechoke, every ``rechoke_interval_s``: a holder with more fetching
   volunteers than slots keeps the ``slots - 1`` that sent it the most
   bytes in the rolling rate window (then: it sent the most bytes to,
   cheapest island, name) and one optimistic slot; one with fewer grants
   every fetching volunteer.  The optimistic slot stays while it is
   valid; every ``optimistic_every``-th rechoke, or once it is not, the
   holder's rotation counter steps on and picks that position of the
   name-ordered rest.  The rate window tumbles at a rechoke once
   ``rate_window_s`` has passed since the last, so a rechoke ranks by the
   bytes of the current and the previous window.  A volunteer choked
   away re-routes a request that was pending at that holder alone;
3. requests: every fetching volunteer fills its pipeline
   (``piece_pipeline`` pieces in flight) by walking its missing pieces
   rarest first (cheapest holder island first under P4P), availability
   ties broken by a per-volunteer rotation, and asks for each the
   cheapest-named holder that grants it a slot, holds the piece, is not
   busy with another request of its own and was never caught corrupt;
   holders whose request for the piece went stale come last;
4. endgame: a volunteer whose every missing piece is in flight asks up
   to ``endgame_dup`` holders for each, granting or not, cheapest first.

The control is the same reference with one guarantee broken: the
rarest-first order loses its per-volunteer rotated tie-break, so
volunteers break availability ties alike and converge on the same
pieces.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

import numpy as np

# the cost of a piece no island holds (it is never matched anyway)
COST_NONE = 64


@dataclass
class Snapshot:
    """Live swarm nodes' own records at one instant, rows in name order
    (a row's index is its name's rank).  A snapshot taken for a rechoke
    alone leaves the piece-level fields None."""
    names: List[str]
    fetching: np.ndarray                  # (n,) bool
    holder: np.ndarray                    # (n,) bool: holds a piece
    unchoked: List[FrozenSet[str]]        # whom the node grants a slot
    queued: Optional[List[Dict[str, FrozenSet[int]]]] = None
    bytes_from: Optional[List[Dict[str, int]]] = None
    bytes_to: Optional[List[Dict[str, int]]] = None
    have: Optional[np.ndarray] = None     # (n, P) bool: pieces held
    full: Optional[np.ndarray] = None     # (n,) bool: holds the image
    pending: Optional[List[Dict[int, Dict[str, float]]]] = None
    unchoked_by: Optional[List[FrozenSet[str]]] = None
    bad: Optional[List[FrozenSet[str]]] = None
    stalled: Optional[List[Dict[int, FrozenSet[str]]]] = None


@dataclass
class Rules:
    """The configuration's decision rules, from its own files."""
    app_id: str
    n_pieces: int
    piece_bytes: int
    pipeline: int
    slots: int
    endgame_dup: int
    optimistic_every: int
    rate_window_s: float
    cost: Optional[np.ndarray] = None     # (K, K) ALTO cost map
    island: Dict[str, int] = field(default_factory=dict)


@dataclass
class Verdict:
    """What the checks found."""
    ticks: int = 0               # ticks whose requests were checked
    rows: int = 0                # fetching volunteers checked
    requests: int = 0            # requests the reference expected
    request_mismatches: int = 0  # volunteers whose requests differ
    rechokes: int = 0            # rechokes checked
    holders: int = 0             # holders whose grants were checked
    grant_mismatches: int = 0    # holders whose grants differ
    control_mismatches: int = 0  # volunteers the control decides otherwise

    def add(self, other: "Verdict") -> None:
        for k in self.__dataclass_fields__:
            setattr(self, k, getattr(self, k) + getattr(other, k))


def offset(name: str, app_id: str) -> int:
    """A volunteer's rarest-first rotation."""
    return sum(ord(c) for c in name + app_id)


class _View:
    """The reference's view of one snapshot."""

    def __init__(self, snap: Snapshot, rules: Rules):
        self.snap = snap
        self.rules = rules
        self.n = len(snap.names)
        self.row = {name: i for i, name in enumerate(snap.names)}
        self.cands = set(np.nonzero(snap.fetching)[0].tolist())
        self.glist = sorted(self.cands)            # name order
        if rules.cost is not None:
            self.isl = np.array([rules.island.get(nm, 0)
                                 for nm in snap.names], dtype=np.int64)
        else:
            self.isl = None
        self._by_cost: Dict[int, np.ndarray] = {}
        if snap.have is not None:
            self.holds = snap.have | snap.full[:, None]        # (n, P)
            self.counts = self.holds.sum(axis=0)
        self._plane = None

    def cost(self, i: int, j: int) -> int:
        if self.isl is None:
            return 0
        return int(self.rules.cost[self.isl[i], self.isl[j]])

    def by_cost(self, i: int) -> np.ndarray:
        """Every row ordered by (island cost from row i, name)."""
        if self.isl is None:
            return np.arange(self.n)
        k = int(self.isl[i])
        got = self._by_cost.get(k)
        if got is None:
            got = np.lexsort((np.arange(self.n),
                              self.rules.cost[k][self.isl]))
            self._by_cost[k] = got
        return got

    def rows(self, names) -> Set[int]:
        return {self.row[x] for x in names if x in self.row}

    # ------------------------------ grants ------------------------------ #
    def fill(self, h: int) -> Set[int]:
        """Holder h's grants once its free slots are filled."""
        keep = self.rows(self.snap.unchoked[h]) & self.cands
        if not self.snap.holder[h]:
            return keep
        free = self.rules.slots - len(keep)
        out = set(keep)
        order = self.by_cost(h)
        k = 0
        while free > 0 and k < self.n:
            j = int(order[k])
            k += 1
            if j != h and j in self.cands and j not in out:
                out.add(j)
                free -= 1
        return out

    def served(self, h: int, granted: Set[int]) -> Dict[str, int]:
        """Bytes holder h serves to the volunteers of `granted` that it
        did not grant before: their queued endgame requests."""
        snap = self.snap
        names = snap.names
        out = {}
        new = granted - self.rows(snap.unchoked[h])
        for j in new:
            q = snap.queued[h].get(names[j])
            if q:
                out[names[j]] = len(q) * self.rules.piece_bytes
        return out

    # ----------------------------- requests ----------------------------- #
    def piece_cost(self, i: int) -> np.ndarray:
        """(P,) cost from row i's island to the cheapest island holding
        each piece."""
        if self._plane is None:
            rules = self.rules
            k = rules.cost.shape[0]
            on = np.zeros((k, rules.n_pieces), dtype=bool)
            for kk in range(k):
                rows = self.isl == kk
                if rows.any():
                    on[kk] = self.holds[rows].any(axis=0)
            self._plane = np.where(on[None, :, :],
                                   rules.cost[:, :, None],
                                   COST_NONE).min(axis=1)      # (K, P)
        return self._plane[int(self.isl[i])]

    def requests(self, i: int, pending: Dict[int, Dict[str, float]],
                 usable: Set[int], rotate: bool = True) \
            -> List[Tuple[int, int]]:
        """The (piece, holder row) requests row i issues."""
        rules = self.rules
        P = rules.n_pieces
        budget = rules.pipeline - len(pending)
        if budget <= 0 or not usable:
            return []
        missing = np.nonzero(~self.holds[i])[0]
        if pending:
            missing = missing[~np.isin(missing, list(pending))]
        if missing.size == 0:
            return []
        c = self.counts[missing].astype(np.int64)
        if rotate:
            rot = (missing + offset(self.snap.names[i], rules.app_id)) % P
            keys = [rot, c]
        else:
            keys = [missing, c]
        if self.isl is not None:
            keys.append(self.piece_cost(i)[missing])
        order = missing[np.lexsort(keys)]
        stalled = self.snap.stalled[i]
        names = self.snap.names
        usable = set(usable)
        out = []
        for p in order.tolist():
            if budget <= 0 or not usable:
                break
            got = [j for j in usable if self.holds[j, p]]
            if not got:
                continue
            shun = stalled.get(p, ())
            j = min(got, key=lambda j: (names[j] in shun, self.cost(i, j), j))
            out.append((p, j))
            usable.discard(j)
            budget -= 1
        return out

    def endgame(self, i: int, pending: Dict[int, Set[str]]) \
            -> List[Tuple[int, int]]:
        """Row i's endgame duplicates, when every missing piece is in
        flight."""
        have_n = int(self.snap.have[i].sum())
        if have_n == 0 or not pending \
                or self.rules.n_pieces - have_n != len(pending):
            return []
        names = self.snap.names
        bad = self.snap.bad[i]
        stalled = self.snap.stalled[i]
        order = self.by_cost(i)
        out = []
        for p, asked in pending.items():
            room = self.rules.endgame_dup - len(asked)
            if room <= 0:
                continue
            shun = stalled.get(p, ())
            for j in order[self.holds[order, p]].tolist():
                if room <= 0:
                    break
                nm = names[j]
                if j == i or nm in asked or nm in bad or nm in shun:
                    continue
                out.append((p, j))
                room -= 1
        return out


# ============================== rechokes ================================ #
class Choker:
    """Replays every rechoke of a run in order, from its first, keeping
    each holder's optimistic slot and rotation counter and the rate
    windows' byte counters as the rules define them."""

    def __init__(self, rules: Rules):
        self.rules = rules
        self.round = 0
        self.window_start = 0.0
        # byte counters of each node at the last two tumbles
        self.bases: List[Dict[str, Tuple[Dict[str, int], Dict[str, int]]]] \
            = []
        self.idx: Dict[str, int] = {}
        self.opt: Dict[str, str] = {}

    def _key(self, v: _View, h: int, sent: Dict[str, int]):
        names = v.snap.names
        got_from = v.snap.bytes_from[h]
        nm_h = names[h]
        base_from, base_to = self.bases[-2].get(nm_h, ({}, {})) \
            if len(self.bases) >= 2 else ({}, {})

        def key(j):
            nm = names[j]
            return (-(got_from.get(nm, 0) - base_from.get(nm, 0)),
                    -(sent.get(nm, 0) - base_to.get(nm, 0)),
                    v.cost(h, j), j)
        return key

    def top(self, v: _View, h: int, sent: Dict[str, int]) -> List[int]:
        """The ``slots - 1`` best reciprocators among the fetching
        volunteers.  Volunteers that moved no bytes either way in the
        window tie on rate and go by (cost, name), so only the first
        ``slots - 1`` of them can make the cut."""
        slots = self.rules.slots
        key = self._key(v, h, sent)
        row = v.row
        moved = {row[nm] for nm in
                 set(v.snap.bytes_from[h]) | set(sent)
                 if nm in row and row[nm] in v.cands and row[nm] != h}
        short = set(j for j in moved if key(j)[:2] != (0, 0))
        order = v.by_cost(h)
        k = 0
        zero = 0
        while zero < slots - 1 and k < v.n:
            j = int(order[k])
            k += 1
            if j != h and j in v.cands and j not in short:
                short.add(j)
                zero += 1
        return sorted(short, key=key)[: slots - 1]

    def step(self, now: float, pre: Snapshot, after: List[FrozenSet[str]]) \
            -> Tuple[Verdict, Dict[int, Set[int]]]:
        """Check one rechoke: `pre` is the snapshot before its tick and
        `after` every node's grants after it.  Returns the verdict and,
        per volunteer row, the holder rows that choked it away."""
        rules = self.rules
        self.round += 1
        rotate = self.round % max(rules.optimistic_every, 1) == 0
        v = _View(pre, rules)
        names = pre.names
        slots = rules.slots
        out = Verdict(rechokes=1)
        choked: Dict[int, Set[int]] = {}
        sent_all: Dict[int, Dict[str, int]] = {}
        for h in range(v.n):
            if not pre.holder[h]:
                continue
            nm_h = names[h]
            filled = v.fill(h)
            sent = dict(pre.bytes_to[h])
            for nm, b in v.served(h, filled).items():
                sent[nm] = sent.get(nm, 0) + b
            rest_n = len(v.cands - {h})
            if rest_n <= slots:
                want = v.cands - {h}
                self.opt.pop(nm_h, None)
            else:
                top = self.top(v, h, sent)
                want = set(top)
                opt = self.opt.get(nm_h)
                j = v.row.get(opt) if opt is not None else None
                valid = j is not None and j != h and j in v.cands \
                    and j not in want
                if rotate or not valid:
                    self.idx[nm_h] = self.idx.get(nm_h, 0) + 1
                    skip = want | {h}
                    rest = [x for x in v.glist if x not in skip]
                    j = rest[self.idx[nm_h] % len(rest)]
                    self.opt[nm_h] = names[j]
                want.add(j)
            got = v.rows(after[h])
            out.holders += 1
            out.grant_mismatches += int(got != want)
            for i in filled - got:
                choked.setdefault(i, set()).add(h)
            for nm, b in v.served(h, got - filled).items():
                sent[nm] = sent.get(nm, 0) + b
            sent_all[h] = sent
        if now - self.window_start >= rules.rate_window_s:
            self.window_start = now
            self.bases = self.bases[-1:] + [{
                names[h]: (pre.bytes_from[h], sent_all.get(h, pre.bytes_to[h]))
                for h in range(v.n)}]
        return out, choked


# ============================== requests ================================ #
def _issued(pending: Dict[int, Dict[str, float]], now: float) \
        -> Set[Tuple[int, str]]:
    return {(p, h) for p, asked in pending.items()
            for h, t in asked.items() if t == now}


def _entries(pending: Dict[int, Dict[str, float]]) \
        -> Set[Tuple[int, str, float]]:
    return {(p, h, t) for p, asked in pending.items()
            for h, t in asked.items()}


def check_tick(pre: Snapshot, post: Snapshot, now: float, rules: Rules,
               choked: Optional[Dict[int, Set[int]]] = None,
               control: bool = False) -> Verdict:
    """Compare the tick that led from `pre` to `post` at virtual time
    `now` with the reference: every holder's grants (on a tick without a
    rechoke; a rechoke is `Choker`'s) and every fetching volunteer's
    requests.  `choked` holds, on a rechoke tick, the holders that choked
    each volunteer away.  With `control`, also count the volunteers whose
    requests the control decides otherwise."""
    v = _View(pre, rules)
    out = Verdict(ticks=1)
    names = pre.names
    prow = {name: k for k, name in enumerate(post.names)}
    if choked is None:
        choked = {}
        for h in range(v.n):
            if not pre.holder[h]:
                continue
            k = prow.get(names[h])
            got = v.rows(post.unchoked[k]) if k is not None else set()
            out.holders += 1
            out.grant_mismatches += int(got != v.fill(h))
    for i in v.glist:
        k = prow.get(names[i])
        if k is None:
            continue
        out.rows += 1
        before = pre.pending[i]
        gone = {names[h] for h in choked.get(i, ())}

        def sole_at_gone(asked):
            return len(asked) == 1 and next(iter(asked)) in gone

        removed = {(p, h, t) for p, asked in before.items()
                   if sole_at_gone(asked) for h, t in asked.items()}
        pending = {p: dict(asked) for p, asked in before.items()
                   if not sole_at_gone(asked)}
        busy = {h for asked in pending.values() for h in asked}
        usable = {j for j in v.rows(post.unchoked_by[k])
                  if j != i and pre.holder[j]
                  and names[j] not in busy and names[j] not in pre.bad[i]}
        picks = v.requests(i, pending, usable)
        grown = {p: set(asked) for p, asked in pending.items()}
        for p, j in picks:
            grown[p] = {names[j]}
        want = {(p, names[j]) for p, j in picks + v.endgame(i, grown)}
        out.requests += len(want)
        got = _issued(post.pending[k], now)
        kept = _entries(post.pending[k])
        went = {e for e in _entries(before) if e not in kept}
        out.request_mismatches += int(got != want or went != removed)
        if control:
            alt = v.requests(i, pending, usable, rotate=False)
            out.control_mismatches += int(alt != picks)
    return out
