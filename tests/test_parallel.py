"""Sharded-vs-single-device equivalence, via 8-host-device subprocesses
(the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.jax_slow

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def _mesh_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


CHECKS = [
    "train_step_sharded_matches_single",
    "moe_sharded_matches_single",
    "embed_sharded_matches_take",
    "decode_flash_sharded",
    "torrent_broadcast",
    "torrent_broadcast_tree",
    "chip_smoke_fanout_tiny",
    "dryrun_cell_small",
    "tp_sp_and_pad_match_baseline",
    "moe_int8_a2a_close_to_exact",
    "pipeline_parallel_matches_sequential",
]


@pytest.mark.parametrize("check", CHECKS)
def test_mesh_check(check):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "mesh_checks.py"), check],
        capture_output=True, text=True, timeout=900, env=_mesh_env())
    assert proc.returncode == 0, \
        f"{check} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
    assert "OK" in proc.stdout
