"""The end-to-end summary, the trace reduction and the per-layer
readers compute what their names say."""
from __future__ import annotations

import statistics

import pytest

from bench import devtrace
from bench.harness import Cell, percentile, summarize


def test_pieces_per_s_is_all_work_over_all_time():
    m = summarize([0.01] * 10, pieces=1234, window_s=2.5)
    assert m["pieces_per_s"] == pytest.approx(1234 / 2.5)


def test_tick_p95_is_over_every_tick_not_chunk_medians():
    # 100 ticks: 94 fast, 6 slow, every slow one in its own chunk of 10
    ticks = [0.001] * 100
    for k in (5, 15, 25, 35, 45, 55):
        ticks[k] = 0.1
    got = summarize(ticks, pieces=1, window_s=1.0)["tick_ms_p95"]
    assert got == pytest.approx(100.0)
    medians = [statistics.median(ticks[i:i + 10]) for i in range(0, 100, 10)]
    assert percentile(medians, 95.0) * 1000.0 == pytest.approx(1.0)
    assert percentile([3.0, 1.0, 2.0], 50.0) == 2.0


def _synthetic():
    ms = 1_000_000
    # two device ops, one overlapping a third; window 0..100 ms
    devices = {"/device:TPU:0": [("fusion.1", 10 * ms, 20 * ms),
                                 ("while.2", 15 * ms, 30 * ms),
                                 ("fusion.1", 90 * ms, 95 * ms),
                                 ("early", -5 * ms, 2 * ms)]}
    host = [("window", 0, 100 * ms),
            ("drain", 0, 40 * ms),
            ("hub_tick", 40 * ms, 100 * ms),
            ("kernel_call", 50 * ms, 60 * ms)]
    return devices, host


def test_trace_reduction_on_a_synthetic_trace():
    devices, host = _synthetic()
    r = devtrace.reduce(devices, host, devtrace.window_of(host))
    # busy: [0,2) + [10,30) + [90,95) = 27 ms of a 100 ms window
    assert r["busy_s"] == pytest.approx(0.027)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    idle = dict(r["idle_gaps"])
    # idle 73 ms: drain [2,10)+[30,40) = 18, kernel_call 10,
    # hub_tick self [40,50)+[60,90)+[95,100) = 45
    assert idle["drain"] == pytest.approx(0.018)
    assert idle["kernel_call"] == pytest.approx(0.010)
    assert idle["hub_tick"] == pytest.approx(0.045)
    assert sum(idle.values()) == pytest.approx(0.073)


def test_trace_reduction_averages_devices():
    devices, host = _synthetic()
    devices["/device:TPU:1"] = []
    r = devtrace.reduce(devices, host, (0, 100_000_000))
    assert r["busy_s"] == pytest.approx(0.0135)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(0.0865)


def test_readers(tiny_root):
    cell = Cell("flat-n2000.crowd", root=tiny_root)
    devices, host = _synthetic()
    run = {"ticks": [0.01] * 4, "pieces": 10, "window_s": 0.1,
           "counters": {"drain_s": 0.2, "hub_tick_s": 0.12,
                        "hub_kernel_s": 0.04},
           "trace": devtrace.reduce(devices, host, (0, 100_000_000)),
           "peaks": None}
    got = {m["name"]: cell.reader(m["name"])(run) for m in cell.per_layer}
    assert got["drain_ms_per_step"] == pytest.approx(50.0)
    assert got["hub_host_ms_per_tick"] == pytest.approx(20.0)
    assert got["kernel_call_ms_per_tick"] == pytest.approx(10.0)
    assert got["device_busy_ms_per_tick"] == pytest.approx(27.0 / 4)
    assert got["device_idle_share"] == pytest.approx(73.0)
    # an untraced run, or one with no device operation, reads nothing
    for trace in (None, dict(run["trace"], busy_s=0.0)):
        quiet = dict(run, trace=trace)
        assert cell.reader("device_idle_share")(quiet) is None
        assert cell.reader("device_busy_ms_per_tick")(quiet) is None


def test_peaks_know_the_v5e_and_refuse_other_kinds():
    from bench.harness import device_peaks
    assert device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device_peaks("cpu")
