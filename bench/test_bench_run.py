"""Whole runs at the tiny size on the CPU, with the look for a chip
skipped: a sound run is correct, the control and each fault planted in
the program's tick are caught, a cell added as files alone resolves and
reports, and the command refuses without a TPU."""
from __future__ import annotations

import itertools
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from repro.core import swarm_arrays
from repro.core.swarm_arrays import SwarmHub

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["flat-n2000.crowd", "p4p-n2000-i8.crowd"]


def _run(root, cell="flat-n2000.crowd", seed=3, trace=False,
         control=False):
    return harness.run(harness.Cell(cell, root=root), seed, 1.0, trace,
                       require_chip=False, control=control)


@pytest.fixture
def every_tick(monkeypatch):
    """Check every tick of the short test windows."""
    monkeypatch.setattr(harness, "TICK_SHARE", 1.0)
    monkeypatch.setattr(harness, "RECHOKE_SHARE", 1.0)
    monkeypatch.setattr(harness, "MAX_TICKS", 400)
    monkeypatch.setattr(harness, "MAX_RECHOKES", 100)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(tiny_root, cell,
                                                     every_tick):
    res = _run(tiny_root, cell=cell, control=True)
    assert res["correct"] is True
    checks = res["checks"]
    assert checks["request_mismatches"]["value"] == 0
    assert checks["grant_mismatches"]["value"] == 0
    assert checks["ticks_checked"]["value"] >= harness.MIN_TICKS
    assert checks["window_rechokes_checked"]["value"] >= harness.MIN_RECHOKES
    assert checks["control_mismatches"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"pieces_per_s", "tick_ms_p95",
                                   "setup_s"}


def _control_orders(missing, counts, offsets, *rest, **kw):
    """The control in the program's place: availability ties broken by
    piece id alone, whatever the volunteer."""
    P = missing.shape[1]
    key = np.where(missing, counts[None, :].astype(np.int64) * P
                   + np.arange(P)[None, :], np.int64(2) ** 60)
    if len(rest) == 2:                      # cost_orders: cost first
        key = np.where(missing, rest[0].astype(np.int64) * P * P * 4096
                       + key, key)
    return np.argsort(key, axis=1, kind="stable").astype(np.int32)


def _half_batch(monkeypatch):
    """The pump serves half of the rows due and leaves the rest."""
    inner = SwarmHub._pump

    def pump(self, st, now):
        rows = sorted(st.dirty)
        st.dirty = set(rows[: len(rows) // 2])
        inner(self, st, now)

    monkeypatch.setattr(SwarmHub, "_pump", pump)


def _no_rechoke(monkeypatch):
    monkeypatch.setattr(SwarmHub, "_rechoke", lambda self, st, now: None)


def _skipped_grants(monkeypatch):
    """The grant pass runs on every other tick only."""
    inner = SwarmHub._grants
    calls = itertools.count()

    def grants(self, st):
        if next(calls) % 2 == 0:
            inner(self, st)

    monkeypatch.setattr(SwarmHub, "_grants", grants)


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(SwarmHub, "tick", lambda self, now: None)


def _altered(monkeypatch):
    """One volunteer's piece order reversed where it is produced."""
    for name in ("rarest_orders", "cost_orders"):
        inner = getattr(swarm_arrays, name)

        def wrong(*a, inner=inner, **kw):
            out = np.array(inner(*a, **kw), copy=True)
            n = int(np.asarray(a[0])[0].sum())
            out[0, :n] = out[0, :n][::-1]
            return out

        monkeypatch.setattr(swarm_arrays, name, wrong)


def _control_in_place(monkeypatch):
    monkeypatch.setattr(swarm_arrays, "rarest_orders", _control_orders)
    monkeypatch.setattr(swarm_arrays, "cost_orders", _control_orders)


FAULTS = {"half_batch": _half_batch, "no_rechoke": _no_rechoke,
          "skipped_grants": _skipped_grants, "state_unchanged": _state_unchanged,
          "altered": _altered, "control_in_place": _control_in_place}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tiny_root, every_tick, monkeypatch,
                                      cell, fault):
    FAULTS[fault](monkeypatch)
    res = _run(tiny_root, cell=cell)
    assert res["correct"] is False
    bad = {k: c["value"] for k, c in res["checks"].items()}
    assert bad["request_mismatches"] + bad["grant_mismatches"] > 0


def test_added_files_make_a_cell(tiny_root):
    """A configuration, a mix and a per-layer metric added as new files
    plus new BENCHMARK.json entries resolve by name and report."""
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "configs", "flat-n2000.json")) as f:
        cfg = json.load(f)
    cfg.update(name="flat-n30", n_volunteers=30)
    with open(os.path.join(bench, "configs", "flat-n30.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "crowd.json")) as f:
        mix = json.load(f)
    mix["warmup_virtual_s"] = 5.0
    with open(os.path.join(bench, "traffic", "early.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "ticks_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return float(len(run['ticks']))\n")
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "flat-n30", "source": "test",
                            "file": "bench/configs/flat-n30.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "flat-n30.early",
                              "config": "flat-n30", "traffic": "early",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "hub tick host Python",
                              "moves": "tick_ms_p95",
                              "workloads": ["flat-n30.early"]})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    res = _run(tiny_root, cell="flat-n30.early", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["ticks_in_window"]["value"] > 0
    assert "drain_ms_per_step" in res["metrics"]
    assert "breakdown" in res
    # the existing cells do not list the new metric
    other = harness.Cell("flat-n2000.crowd", root=tiny_root)
    assert "ticks_in_window" not in [m["name"] for m in other.per_layer]


def test_command_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "flat-n2000.crowd", "--seed", str(2 ** 31 + 5),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "flat-n2000.crowd", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout
