"""Spans and counters of `core.trace`: nesting and self time, no profiler
annotation while tracing is off, the program clocks and counts they
feed on a small batched swarm (jax backend on the CPU), and the spans'
place in a `jax.profiler` trace."""
import glob
import os

import numpy as np
import pytest

from repro.core import (Agent, AgentConfig, SimRuntime, SwarmHub,
                        TrackerConfig, TrackerServer, make_prime_app, trace)
from repro.core import swarm_kernels as sk
from repro.core.runtime import LinkModel


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def test_nesting_and_self_time_on_a_fake_clock():
    clock = FakeClock()
    rec = trace.Recorder(clock=clock)
    with rec.span("tick") as tick:
        clock.now += 1.0
        with rec.span("tick.pump"):
            clock.now += 2.0
            with rec.span("kernel.match"):
                clock.now += 4.0
            clock.now += 0.5
        with rec.span("kernel.match"):
            clock.now += 8.0
        rec.count("events", 3)
    before = rec.snapshot()
    with rec.span("tick"):
        clock.now += 16.0
    rec.count("events", 2)
    after = rec.snapshot()

    assert tick.seconds == 15.5
    assert before["total_s"] == {"tick": 15.5, "tick.pump": 6.5,
                                 "kernel.match": 12.0}
    assert before["self_s"] == {"tick": 1.0, "tick.pump": 2.5,
                                "kernel.match": 12.0}
    assert before["calls"] == {"tick": 1, "tick.pump": 1, "kernel.match": 2}
    assert before["counts"] == {"events": 3}
    assert trace.delta(before, after) == {
        "total_s": {"tick": 16.0}, "self_s": {"tick": 16.0},
        "calls": {"tick": 1}, "counts": {"events": 2}}


def _swarm(n_leechers: int = 12):
    """A small batched flash crowd on the jax backend; rechokes every
    virtual second so every kernel of a flat swarm runs."""
    hub = SwarmHub(backend="jax")
    rt = SimRuntime(link=LinkModel(uplink_Bps=12.5e6, downlink_Bps=12.5e6))
    rt.crash_hooks.append(hub.node_gone)
    rt.add_node(TrackerServer(config=TrackerConfig(ping_interval_s=2.0)))
    cfg = dict(work_timeout_s=600.0, rechoke_interval_s=1.0)
    host = Agent("host", config=AgentConfig(**cfg), hub=hub)
    rt.add_node(host)
    host.host_app(make_prime_app(
        "tr-app", "host", 3, 6_000, n_parts=6, sim_time_per_number=1e-4,
        swarm=True, app_bytes=262_144, piece_bytes=16_384))
    for i in range(n_leechers):
        rt.add_node(Agent(f"L{i}", config=AgentConfig(**cfg), hub=hub))
    return rt, hub


def test_no_annotation_while_tracing_is_off(monkeypatch):
    def refuse(*args, **kw):
        raise AssertionError("TraceAnnotation made with tracing off")

    monkeypatch.setattr(trace, "TraceAnnotation", refuse)
    rt, hub = _swarm()
    rt.run_batched(until=2.0, tick_s=0.5, on_tick=hub.tick)
    ticks = hub.ticks
    assert ticks > 0

    made = []

    class Note:
        def __init__(self, name, **meta):
            made.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "TraceAnnotation", Note)
    monkeypatch.setattr(trace.RECORDER, "enabled", True)
    rt.run_batched(until=5.0, tick_s=0.5, on_tick=hub.tick)
    assert [meta for n, meta in made if n == "swarm.tick"] \
        == [{"tick": k} for k in range(ticks + 1, hub.ticks + 1)]
    assert {"swarm.drain", "swarm.tick.pump", "swarm.kernel.rarest_orders",
            "swarm.kernel.rarest_keys.dispatch"} <= {n for n, _ in made}
    assert all(meta == {} for n, meta in made if n != "swarm.tick")


def _kernel_spans(names):
    """``swarm.kernel.<wrapper>`` names: no dispatch or fetch part."""
    return [n for n in names if n.startswith("swarm.kernel.")
            and n.count(".") == 2]


def test_spans_feed_the_program_clocks_and_counts():
    rt, hub = _swarm()
    rt.run_batched(until=1.0, tick_s=0.5, on_tick=hub.tick)
    s0 = trace.snapshot()
    calls0 = sum(sk.DEVICE_CALLS.values())
    clocks0 = (hub.prof_tick_s, hub.prof_kernel_s, rt.batched_drain_s)
    events0, ticks0 = rt.events_processed, hub.ticks
    rt.run_batched(until=6.0, tick_s=0.5, on_tick=hub.tick)
    d = trace.delta(s0, trace.snapshot())
    tick_s, kernel_s, drain_s = (b - a for a, b in zip(
        clocks0, (hub.prof_tick_s, hub.prof_kernel_s, rt.batched_drain_s)))

    total, self_s, calls = d["total_s"], d["self_s"], d["calls"]
    wrappers = _kernel_spans(total)
    assert {"swarm.kernel.rarest_orders", "swarm.kernel.match_requests",
            "swarm.kernel.choke_order"} <= set(wrappers)
    assert sum(total[n] for n in wrappers) == pytest.approx(kernel_s,
                                                            rel=1e-9)
    assert total["swarm.tick"] == pytest.approx(tick_s, rel=1e-9)
    assert total["swarm.drain"] == pytest.approx(drain_s, rel=1e-9)
    assert calls["swarm.tick"] == calls["swarm.tick.pump"] \
        == hub.ticks - ticks0 > 0
    # every device round trip is one fetch span, one DEVICE_CALLS count
    fetches = [n for n in calls if n.endswith(".fetch")]
    assert sum(calls[n] for n in fetches) \
        == sum(sk.DEVICE_CALLS.values()) - calls0
    # a wrapper's self time is its host numpy: total less its children
    children = [n for n in total if n.endswith((".dispatch", ".fetch"))]
    assert all(self_s[n] >= 0.0 for n in wrappers)
    assert sum(self_s[n] for n in wrappers) + sum(
        total[n] for n in children) == pytest.approx(kernel_s, rel=1e-9)
    assert d["counts"]["swarm.drain.events"] == rt.events_processed - events0


def test_match_requests_counts_padded_operand_bytes():
    rng = np.random.default_rng(5)
    r, c, p, n = 3, 5, 16, 10
    orders = np.argsort(rng.random((r, p)), axis=1).astype(np.int32)
    cand = rng.integers(0, n, (r, c)).astype(np.int32)
    s0 = trace.snapshot()
    picks = sk.match_requests(
        orders, np.full(r, p), np.full(r, 4), cand, np.ones((r, c), bool),
        np.arange(r * c, dtype=np.int32).reshape(r, c),
        rng.random((n, p)) < 0.5, np.zeros(n, bool), backend="jax")
    d = trace.delta(s0, trace.snapshot())
    rb, cb, nb = sk._bucket(r), sk._bucket(c), sk._bucket(n)
    operands = [np.zeros((rb, p), np.int32), np.zeros(rb, np.int32),
                np.zeros(rb, np.int32), np.zeros((rb, cb), np.int32),
                np.zeros((rb, cb), bool), np.zeros((rb, cb), np.int32),
                np.zeros((nb, p), bool), np.zeros(nb, bool)]
    assert picks.shape == (r, p)
    assert d["counts"]["swarm.h2d_bytes.match_requests"] \
        == sum(a.nbytes for a in operands)
    assert d["counts"]["swarm.d2h_bytes.match_requests"] == rb * p * 4
    assert d["calls"]["swarm.kernel.match_requests.dispatch"] == 1
    assert d["calls"]["swarm.kernel.match_requests.fetch"] == 1


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    return out


def test_spans_nest_in_a_profiler_trace(tmp_path):
    """With tracing on, each ``swarm.tick`` lands inside the ``hub_tick``
    annotation a benchmark puts round the call, on the same clock, with
    its pump phase and kernel calls inside it."""
    import jax

    rt, hub = _swarm()
    rt.run_batched(until=1.0, tick_s=0.5, on_tick=hub.tick)
    ticks0 = hub.ticks

    def on_tick(now):
        with jax.profiler.TraceAnnotation("hub_tick"):
            hub.tick(now)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace.enable()
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            rt.run_batched(until=4.0, tick_s=0.5, on_tick=on_tick)
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.enable(False)
    ev = _host_events(str(tmp_path))

    def inside(inner, outer):
        return all(any(a <= x and y <= b for a, b in ev[outer])
                   for x, y in ev[inner])

    assert len(ev["swarm.tick"]) == len(ev["hub_tick"]) \
        == hub.ticks - ticks0 > 0
    assert len(ev["swarm.drain"]) >= len(ev["swarm.tick"])
    assert inside("swarm.tick", "hub_tick")
    assert inside("swarm.tick.pump", "swarm.tick")
    kernels = _kernel_spans(ev)
    assert "swarm.kernel.match_requests" in kernels
    for name in kernels:
        assert inside(name, "swarm.tick")
    assert inside("swarm.kernel.match_requests.fetch",
                  "swarm.kernel.match_requests")
