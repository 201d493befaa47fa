"""Fixtures of the benchmark's own tests: a copy of the benchmark at a
size a CPU test run holds (60 volunteers, 16 pieces, numpy kernels)."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"n_volunteers": 60, "piece_bytes": 200_000, "image_bytes": 3_200_000,
        "backend": "numpy"}


def shrink(root: str) -> None:
    """Cut every configuration of the benchmark under `root` to the tiny
    size, in place."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(TINY)
        with open(path, "w") as f:
            json.dump(cfg, f)


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's data files, tiny."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        tmp_path / "bench" / sub)
    shrink(str(tmp_path))
    return str(tmp_path)
