"""Spans and counters at the batched swarm's layer boundaries.

`span(name)` times its body with one `perf_counter` pair.  The duration
is added to the span's total and to its parent's child time (a stack
per thread), so a span's self time is its total less the time its
child spans cover.  After `enable()` a span also opens a
`jax.profiler.TraceAnnotation` of the same name, which puts it on the
profiler's clock, the one the device trace uses; before it, no
annotation is made.  `count(name, n)` adds to a counter.  `snapshot()`
returns every total, self time, call count and counter since the
process started; `delta` of two snapshots is what a window did.

Span names:

  swarm.drain                         one event burst of `SimRuntime.run_batched`
  swarm.tick                          `SwarmHub.tick`, metadata ``tick=<n>``
  swarm.tick.<phase>                  its phases: release, grants, rechoke,
                                      pump, endgame
  swarm.kernel.<wrapper>              one `SwarmHub._kernel` call; its self time
                                      is the wrapper's host numpy
  swarm.kernel.<kernel>.dispatch      the jitted call, until it returns
  swarm.kernel.<kernel>.fetch         the wait for the result and its copy back

Counters: ``swarm.drain.events`` (events drained),
``swarm.h2d_bytes.<kernel>`` (operand bytes as padded),
``swarm.d2h_bytes.<kernel>`` (result bytes), and per `match_requests`
call of `SwarmHub._match_fast` ``swarm.match.steps`` (the device walk's
passes: the most picks of any row, plus the pass that finds nothing)
and ``swarm.match.picks`` (picks made).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

Snapshot = Dict[str, Dict[str, float]]


class _Book:
    """One thread's open spans (their child seconds so far) and sums."""

    def __init__(self):
        self.stack: List[float] = []
        self.total: Dict[str, float] = collections.defaultdict(float)
        self.child: Dict[str, float] = collections.defaultdict(float)
        self.calls: Dict[str, int] = collections.Counter()
        self.counts: Dict[str, int] = collections.Counter()


class Span:
    """A timed region; ``seconds`` holds its duration once it has
    closed."""

    __slots__ = ("_rec", "name", "tick", "seconds", "_t0", "_book", "_ann")

    def __init__(self, rec: "Recorder", name: str, tick: Optional[int]):
        self._rec = rec
        self.name = name
        self.tick = tick
        self._ann = None

    def __enter__(self) -> "Span":
        rec = self._rec
        if rec.enabled:
            self._ann = (TraceAnnotation(self.name) if self.tick is None
                         else TraceAnnotation(self.name, tick=self.tick))
            self._ann.__enter__()
        self._book = rec._book()
        self._book.stack.append(0.0)
        self._t0 = rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = dt = self._rec.clock() - self._t0
        book = self._book
        name = self.name
        book.child[name] += book.stack.pop()
        book.total[name] += dt
        book.calls[name] += 1
        if book.stack:
            book.stack[-1] += dt
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Recorder:
    """Span and counter sums of one process, kept per thread and merged
    by `snapshot`."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.enabled = False
        self._local = threading.local()
        self._lock = threading.Lock()
        self._books: List[_Book] = []

    def _book(self) -> _Book:
        try:
            return self._local.book
        except AttributeError:
            book = self._local.book = _Book()
            with self._lock:
                self._books.append(book)
            return book

    def span(self, name: str, tick: Optional[int] = None) -> Span:
        return Span(self, name, tick)

    def count(self, name: str, n: int) -> None:
        self._book().counts[name] += n

    def snapshot(self) -> Snapshot:
        """``total_s``, ``self_s`` and ``calls`` per span name and
        ``counts`` per counter, summed over threads."""
        out: Snapshot = {k: collections.Counter()
                         for k in ("total_s", "self_s", "calls", "counts")}
        with self._lock:
            books = list(self._books)
        for b in books:
            # dict.copy is atomic: another thread may be adding names
            total, child = b.total.copy(), b.child.copy()
            out["total_s"].update(total)
            out["self_s"].update({n: t - child.get(n, 0.0)
                                  for n, t in total.items()})
            out["calls"].update(b.calls.copy())
            out["counts"].update(b.counts.copy())
        return {k: dict(v) for k, v in out.items()}


def delta(before: Snapshot, after: Snapshot) -> Snapshot:
    """What happened between two snapshots."""
    return {k: {n: v - before[k].get(n, 0) for n, v in after[k].items()
                if v != before[k].get(n, 0)} for k in after}


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
snapshot = RECORDER.snapshot


def enable(on: bool = True) -> None:
    """Write every span into the profiler's trace as well."""
    RECORDER.enabled = on
