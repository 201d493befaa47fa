"""Reduce a profiler trace of the window to device busy time, the
device's largest operations and its idle gaps.

`load` reads the ``.xplane.pb`` file that `jax.profiler` writes into
two plain lists on one clock: the operations that ran on the device
(``(name, start_ns, end_ns)`` from the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named ``<jitted program>/<HLO instruction>``
after the ``XLA Modules`` event they ran in) and the harness's own host
spans
(``TraceAnnotation`` names from `SPANS`).  `reduce` works on those lists
alone, so a recorded or synthetic trace checks it.

Busy time is the union of the operation intervals inside the window,
averaged over the devices used.  Idle time is the rest of the window;
it is split by what the host was doing meanwhile: ``kernel_call``
(inside a kernel wrapper: dispatch, copies, the wait for the result),
``hub_tick`` (the rest of `SwarmHub.tick`), ``drain`` (the runtime's
event drain) or ``other`` (the harness between them).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Sequence, Tuple

# host spans the harness writes; kernel calls nest inside the tick
SPANS = ("kernel_call", "hub_tick", "drain")
WINDOW = "window"
TOP = 10

Event = Tuple[str, int, int]


def load(trace_dir: str) -> Dict[str, object]:
    """Device operations per device and host spans from the newest
    ``.xplane.pb`` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((int(ev.start_ns), _module(ev.name))
                             for ev in lines.get("XLA Modules", ()))
            starts = [a for a, _ in modules]
            ops: List[Event] = []
            for ev in lines.get("XLA Ops", ()):
                a = int(ev.start_ns)
                k = bisect.bisect_right(starts, a) - 1
                mod = modules[k][1] if k >= 0 else "?"
                ops.append((f"{mod}/{ev.name.split(' ', 1)[0]}", a,
                            int(ev.start_ns + ev.duration_ns)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name == WINDOW:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return {"devices": devices, "host": host}


def _module(name: str) -> str:
    """``jit__match_requests_jax(1234)`` -> ``jit__match_requests_jax``."""
    return name.split("(", 1)[0]


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def _covered(spans: List[Tuple[int, int]], starts: List[int],
             a: int, b: int) -> int:
    """Length of [a, b) covered by the disjoint sorted `spans`."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    total = 0
    while i < len(spans) and spans[i][0] < b:
        lo, hi = max(spans[i][0], a), min(spans[i][1], b)
        if hi > lo:
            total += hi - lo
        i += 1
    return total


def reduce(devices: Dict[str, List[Event]], host: List[Event],
           window: Tuple[int, int]) -> Dict[str, object]:
    """Busy seconds (mean over devices), window seconds, the device
    operations that took most time, and the idle time split by what the
    host was doing (mean over devices)."""
    lo, hi = window
    per_op: Dict[str, float] = collections.Counter()
    busy = []
    gaps: List[Tuple[int, int]] = []
    for ops in devices.values():
        ops = _clip(ops, lo, hi)
        for name, a, b in ops:
            per_op[name] += (b - a) / 1e9
        merged = _union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k])
    n_dev = max(len(devices), 1)
    spans = {name: _union([(a, b) for n, a, b in _clip(host, lo, hi)
                           if n == name]) for name in SPANS}
    starts = {name: [a for a, _ in s] for name, s in spans.items()}
    idle: Dict[str, float] = collections.Counter()
    for a, b in gaps:
        cov = {name: _covered(spans[name], starts[name], a, b)
               for name in SPANS}
        # kernel calls run inside the tick: the tick's share is its self
        # time; the drain lies between ticks
        cov["hub_tick"] -= cov["kernel_call"]
        cov["other"] = (b - a) - sum(cov.values())
        for name, ns in cov.items():
            idle[name] += ns / 1e9 / n_dev
    named = [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
             if v > 0]
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n_dev / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v] for k, v in ops_top],
            "idle_gaps": named,
            "n_devices": len(devices)}


def window_of(host: List[Event]) -> Tuple[int, int]:
    """The traced window, as the harness's ``window`` span marks it."""
    marks = [(a, b) for n, a, b in host if n == WINDOW]
    if not marks:
        raise ValueError("the trace has no window span")
    return marks[-1]
