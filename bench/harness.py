"""One benchmark run: build a cell's fleet, warm it, measure a window of
`SimRuntime.run_batched` + `SwarmHub.tick`, check the run's rechokes and
a seeded sample of the window's ticks against the plain reference, and
print the result line.

A cell ``<config>.<traffic>`` resolves by name: the configuration file
is the one `BENCHMARK.json` lists for it, the traffic mix is
``traffic/<traffic>.json`` and each per-layer metric is read by
``metrics/<metric>.py`` (a function ``read(run)`` returning a number or
None), all under the benchmark's directory.

`correct` is decided at the boundary every volunteer sees: the requests
and upload-slot grants of a tick, read from each node's own records
before and after it (`snapshot`), against `bench/reference.py`.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import random
import sys
import tempfile
import time
from contextlib import ExitStack, nullcontext
from typing import List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# share of the window's ticks whose requests are checked, drawn from the
# seed, and the most of them kept (a reservoir): ordinary ticks, and
# ticks on which the configuration's rechoke is due (every rechoke's
# grants are checked)
TICK_SHARE = 1.0 / 25
RECHOKE_SHARE = 1.0 / 4
MAX_TICKS = 12
MAX_RECHOKES = 4
# a window with no checked tick, or no rechoke, made no decisions
MIN_TICKS = 1
MIN_RECHOKES = 1
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILES = {"backend": 0}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Cell:
    """A workload of `BENCHMARK.json` with its files loaded."""

    def __init__(self, name: str, root: str = ROOT):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        self.bench_dir = os.path.join(root, spec["paths"][0])
        try:
            self.workload = next(w for w in spec["workloads"]
                                 if w["name"] == name)
        except StopIteration:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = next(c for c in spec["configs"]
                     if c["name"] == self.workload["config"])
        with open(os.path.join(root, entry["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(self.bench_dir, "traffic",
                               self.workload["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in spec["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in spec["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def reader(self, metric: str):
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


# ============================ snapshots ================================= #
def snapshot(fleet, part: str):
    """Every live swarm node's own records for the benchmark's app:
    ``"choke"`` what a rechoke reads, ``"full"`` all that the reference
    reads before a tick, ``"after"`` what a tick can change."""
    from bench.fleet import APP_ID
    from bench.reference import Snapshot
    agents = fleet.live()
    names = [a.node_id for a in agents]
    pxs = [a.px for a in agents]
    n = len(agents)
    unchoked = [frozenset(px.unchoked.get(APP_ID, ())) for px in pxs]
    fetching = np.zeros(n, dtype=bool)
    holder = np.zeros(n, dtype=bool)
    if part == "after":
        return Snapshot(names, fetching, holder, unchoked, pending=[
            {p: dict(asked) for p, asked in
             px.pending.get(APP_ID, {}).items()} for px in pxs],
            unchoked_by=[frozenset(px.unchoked_by.get(APP_ID, ()))
                         for px in pxs])
    full = np.zeros(n, dtype=bool)
    invs = [px.inventories.get(APP_ID) for px in pxs]
    for k, px in enumerate(pxs):
        full[k] = APP_ID in px.complete
        fetching[k] = APP_ID in px.fetching
        holder[k] = full[k] or bool(invs[k] is not None and invs[k].have)
    snap = Snapshot(
        names, fetching, holder, unchoked,
        queued=[{peer: frozenset(q) for peer, q in
                 px.queued_reqs.get(APP_ID, {}).items()} for px in pxs],
        bytes_from=[dict(px.bytes_from) for px in pxs],
        bytes_to=[dict(px.bytes_to) for px in pxs])
    if part == "choke":
        return snap
    have = np.zeros((n, fleet.n_pieces), dtype=bool)
    for k, inv in enumerate(invs):
        if inv is not None and inv.have:
            have[k, list(inv.have)] = True
    snap.have = have
    snap.full = full
    after = snapshot(fleet, "after")
    snap.pending = after.pending
    snap.unchoked_by = after.unchoked_by
    snap.bad = [frozenset(px.bad_peers.get(APP_ID, ())) for px in pxs]
    snap.stalled = [{p: frozenset(s) for p, s in
                     px.stalled_holders.get(APP_ID, {}).items()}
                    for px in pxs]
    return snap


class Ticker:
    """The `on_tick` hook: times one `SwarmHub.tick` call (which ends
    with its results on the host) and snapshots every node around the
    ticks the reference checks: each tick on which the configuration's
    rechoke is due (a rechoke is due once ``rechoke_interval_s`` has
    passed since the last), from the run's first, and a seeded sample of
    the window's ticks."""

    def __init__(self, fleet, seed: int, span=None):
        self.fleet = fleet
        self.span = span
        self.rng = random.Random(seed * 7919 + 17)
        self.rechoke_s = float(fleet.agent_cfg["rechoke_interval_s"])
        self.last_rechoke = 0.0
        self.recording = False
        self.ticks: List[float] = []
        self.rechokes: List[tuple] = []
        self.samples: List[tuple] = []
        self.rechoke_samples: List[tuple] = []
        self._seen = [0, 0]
        self._drain = None

    def _slot(self, rechoke: bool) -> Optional[int]:
        """Reservoir slot of this tick among the sampled ones of its
        kind (None: not kept)."""
        share = RECHOKE_SHARE if rechoke else TICK_SHARE
        if self.rng.random() >= share:
            return None
        pool, cap = ((self.rechoke_samples, MAX_RECHOKES) if rechoke
                     else (self.samples, MAX_TICKS))
        k = self._seen[int(rechoke)]
        self._seen[int(rechoke)] += 1
        if len(pool) < cap:
            pool.append(None)
            return len(pool) - 1
        j = self.rng.randrange(k + 1)
        return j if j < cap else None

    def __call__(self, now: float) -> None:
        if self._drain is not None:
            self._drain.__exit__(None, None, None)
            self._drain = None
        rechoke = now - self.last_rechoke >= self.rechoke_s
        slot = self._slot(rechoke) if self.recording else None
        pre = None
        if slot is not None:
            pre = snapshot(self.fleet, "full")
        elif rechoke:
            pre = snapshot(self.fleet, "choke")
        with (self.span("hub_tick") if self.span else nullcontext()):
            t0 = time.perf_counter()
            self.fleet.hub.tick(now)
            dt = time.perf_counter() - t0
        if self.recording:
            self.ticks.append(dt)
        if pre is not None:
            post = snapshot(self.fleet, "after")
            if rechoke:
                self.last_rechoke = now
                self.rechokes.append((now, self.recording, pre,
                                      post.unchoked))
            if slot is not None:
                pool = self.rechoke_samples if rechoke else self.samples
                pool[slot] = (now, rechoke, pre, post)
        self.open_drain()

    def open_drain(self) -> None:
        """Mark the drain that runs until the next tick."""
        if self.span is not None:
            self._drain = self.span("drain")
            self._drain.__enter__()

    def close(self) -> None:
        if self._drain is not None:
            self._drain.__exit__(None, None, None)
            self._drain = None


def check(fleet, ticker: Ticker, control: bool = False):
    """Replay every rechoke of the run and compare it, and every sampled
    tick of the window, with the plain reference."""
    from bench import reference
    rules = fleet.rules()
    choker = reference.Choker(rules)
    total = reference.Verdict()
    chokes = {}
    for now, in_window, pre, after in ticker.rechokes:
        v, chokes[now] = choker.step(now, pre, after)
        v.rechokes = int(in_window)
        total.add(v)
    for now, rechoke, pre, post in ticker.samples + ticker.rechoke_samples:
        total.add(reference.check_tick(
            pre, post, now, rules, chokes[now] if rechoke else None,
            control=control))
    return total


class KernelSpans:
    """Marks each kernel call of the hub with a ``kernel_call`` span in a
    traced run, so the trace's idle gaps can be told apart.  A hub with
    no single kernel entry gets no such span; nothing else depends on
    it."""

    def __init__(self, hub, span):
        self.inner = getattr(hub, "_kernel", None)
        self.span = span
        if self.inner is not None:
            hub._kernel = self

    def __call__(self, fn, *args, **kw):
        with self.span("kernel_call"):
            return self.inner(fn, *args, **kw)


# ============================== the run ================================= #
def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of every value (no interpolation)."""
    s = sorted(values)
    return s[max(int(math.ceil(q / 100.0 * len(s))) - 1, 0)]


def summarize(ticks: List[float], pieces: int, window_s: float) -> dict:
    """The end-to-end metrics of a window: all pieces over all of its
    wall time, and the 95th percentile over every tick in it."""
    return {"pieces_per_s": pieces / window_s,
            "tick_ms_p95": 1000.0 * percentile(ticks, 95.0)}


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']}")
    if require_chip and info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def device_peaks(kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The chip's published peaks from ``peaks.json``; a device kind the
    table does not list is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _on_event(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        COMPILES["backend"] += 1


_LISTENING = []


def _listen_compiles() -> None:
    import jax
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _LISTENING.append(True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, require_chip: bool = True,
        control: bool = False, log=None) -> dict:
    """Measure one window of `cell`; returns the result object."""
    t_start = time.monotonic() if t_start is None else t_start
    log = log or sys.stderr
    dev = device_info(cell.chips, require_chip)
    peaks = device_peaks(dev["kind"], cell.bench_dir) if require_chip \
        else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # keep every program, however quick to compile: a run warms dozens of
    # small kernel buckets, and only a cache of all of them keeps set-up
    # free of compiles after a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _listen_compiles()
    from bench import devtrace, warm
    from bench.fleet import Fleet

    span = jax.profiler.TraceAnnotation if trace else None
    fleet = Fleet(cell.config, cell.traffic, seed)
    if trace:
        KernelSpans(fleet.hub, span)
    ticker = Ticker(fleet, seed, span=span)
    tick_s = float(cell.config["tick_s"])
    warmed = warm.compile_all(fleet)
    with ExitStack() as stack:
        fleet.rt.run_batched(until=float(cell.traffic["warmup_virtual_s"]),
                             tick_s=tick_s, on_tick=ticker)
        ticker.close()
        rt, hub = fleet.rt, fleet.hub
        trace_dir = None
        if trace:
            trace_dir = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench_trace_"))
            # the harness's spans and the device operations only: the
            # Python tracer would slow every call of the drain and the
            # tick, and the runtime's own host events only fill the file
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the fleet built in set-up lives through the window: keep the
        # collector's full passes off it
        gc.collect()
        gc.freeze()
        c0 = COMPILES["backend"]
        p0 = fleet.verified_pieces()
        d0 = fleet.outcomes()
        drain0, tick0, kern0 = (rt.batched_drain_s, hub.prof_tick_s,
                                hub.prof_kernel_s)
        v0 = rt.now()
        ticker.recording = True
        setup_s = time.monotonic() - t_start
        w0 = time.perf_counter()
        deadline = w0 + float(seconds)
        with (span(devtrace.WINDOW) if span else nullcontext()):
            ticker.open_drain()
            rt.run_batched(until=None, tick_s=tick_s, on_tick=ticker,
                           stop_when=lambda: time.perf_counter() >= deadline)
            ticker.close()
        window_s = time.perf_counter() - w0
        ticker.recording = False
        compiles = COMPILES["backend"] - c0
        pieces = fleet.verified_pieces() - p0
        d1 = fleet.outcomes()
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            loaded = devtrace.load(trace_dir)
            reduced = devtrace.reduce(loaded["devices"], loaded["host"],
                                      devtrace.window_of(loaded["host"]))
    gc.unfreeze()
    ticks = ticker.ticks
    counters = {"drain_s": rt.batched_drain_s - drain0,
                "hub_tick_s": hub.prof_tick_s - tick0,
                "hub_kernel_s": hub.prof_kernel_s - kern0}
    record = {"ticks": ticks, "pieces": pieces, "window_s": window_s,
              "counters": counters, "trace": reduced, "peaks": peaks}
    device = dict(dev, memory_peak_bytes=memory_peak(cell.chips))
    stats = {"cell": cell.name, "seed": seed, "window_s": window_s,
             "ticks": len(ticks), "virtual_s": rt.now() - v0,
             "window_compiles": compiles, "warmed_shapes": warmed,
             "live_nodes": len(fleet.live()), **counters}
    print(json.dumps({"stats": stats}), flush=True)

    checked = check(fleet, ticker, control=control)
    del fleet, rt, hub, ticker
    checks = {
        "request_mismatches": {"value": checked.request_mismatches,
                               "limit": "<= 0"},
        "grant_mismatches": {"value": checked.grant_mismatches,
                             "limit": "<= 0"},
        "ticks_checked": {"value": checked.ticks,
                          "limit": f">= {MIN_TICKS}"},
        "window_rechokes_checked": {"value": checked.rechokes,
                                    "limit": f">= {MIN_RECHOKES}"},
    }
    correct = (checked.request_mismatches == 0
               and checked.grant_mismatches == 0
               and checked.ticks >= MIN_TICKS
               and checked.rechokes >= MIN_RECHOKES and len(ticks) > 0)
    print(json.dumps({"checked": vars(checked)}), flush=True)
    if control:
        checks["control_mismatches"] = {
            "value": checked.control_mismatches, "limit": "not compared"}

    metrics = {}
    if not trace:
        e2e = dict(summarize(ticks, pieces, window_s), setup_s=setup_s) \
            if ticks else {"setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for m in cell.per_layer:
            value = cell.reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = (d1["corrupt"] - d0["corrupt"]) + d1["overdue"]
    result = {"correct": bool(correct),
              "attempted": d1["delivered"] - d0["delivered"] + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=log)
    return result
