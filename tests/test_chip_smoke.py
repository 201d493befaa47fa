"""chip_smoke.py on the CPU: its phases at tiny sizes, and its refusals.

The script itself only runs on a TPU; these tests drive its phase
functions on the CPU backend so a change that breaks the on-chip smoke
path is caught here, and check that it refuses to report without a chip.
"""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu(tmp_path):
    proc = _run(SMOKE, ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    proc = _run(str(alone), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def smoke():
    sys.path.insert(0, ROOT)
    import chip_smoke
    return chip_smoke


def test_swarm_phase_backends_agree_tiny(smoke):
    out = smoke.phase_swarm(n_volunteers=24, n_pieces=16, image_mb=2.0)
    assert out["jax"] == out["pallas"] == out["numpy"]
    assert out["numpy"]["replicas"] == 24


def test_serve_phase_restores_and_answers_tiny(smoke, tmp_path):
    from repro.configs.base import get_config, reduced_config
    cfg = reduced_config(get_config("qwen2-vl-2b"))
    out = smoke.phase_serve(cfg=cfg, workdir=str(tmp_path / "w"),
                            n_requests=5, prompt_len=4, max_new=3)
    assert [len(t) for t in out["tokens"]] == [3] * 5
    assert not (tmp_path / "w").exists()
