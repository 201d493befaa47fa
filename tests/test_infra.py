"""Checkpoint store, data pipeline, compression, HLO analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.jax_slow

from repro.checkpoint.store import CheckpointStore, async_save
from repro.data.pipeline import (LeasedBatchPipeline, SyntheticTokens,
                                 TokenFileStore)
from repro.launch import hlo_analysis
from repro.optim.compression import (CompressionConfig, compress_tree,
                                     compression_ratio)


# ------------------------------ checkpoint ----------------------------- #
def test_checkpoint_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path), piece_bytes=1024)
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"c": np.ones((100,), np.int32),
                  "d": np.float32(3.5)}}
    store.save(3, tree, extra={"note": "hi"})
    out, extra = store.restore(tree)
    assert extra["note"] == "hi"
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_latest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep_last=2)
    tree = {"x": np.zeros(4, np.float32)}
    for s in (1, 2, 3, 4):
        store.save(s, tree)
    assert store.steps() == [3, 4]
    assert store.latest_step() == 4


def test_checkpoint_async_and_uncommitted_ignored(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = {"x": np.ones(8, np.float32)}
    th = async_save(store, 7, tree)
    th.join(30)
    assert store.latest_step() == 7
    # a torn write (no COMMITTED marker) must be invisible
    os.makedirs(tmp_path / "step_00000009")
    assert store.latest_step() == 7


# ------------------------------ data ----------------------------------- #
def test_synthetic_tokens_deterministic():
    src = SyntheticTokens(vocab_size=100, seed=1)
    a = src.piece(5, 2, 8)
    b = src.piece(5, 2, 8)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_token_file_store_roundtrip(tmp_path):
    store = TokenFileStore(str(tmp_path))
    toks = np.arange(1000, dtype=np.uint32)
    store.write_shard(0, toks)
    out = store.read_shard(0)
    np.testing.assert_array_equal(toks, out)
    piece = store.piece(0, 2, 8, vocab_size=500)
    assert piece["tokens"].shape == (2, 8)


def test_pipeline_resume_no_replay():
    src = SyntheticTokens(vocab_size=50)
    p1 = LeasedBatchPipeline(src, batch=2, seq=8)
    seen = []
    for _ in range(5):
        iid, b = p1.next_batch()
        seen.append(b["tokens"][0, 0])
        p1.complete(iid)
    sd = p1.state_dict()
    p2 = LeasedBatchPipeline(src, batch=2, seq=8)
    p2.load_state_dict(sd)
    iid, b6 = p2.next_batch()
    # continues from piece 5, not replaying piece 0
    ref = src.piece(5, 2, 8)
    np.testing.assert_array_equal(b6["tokens"], ref["tokens"])


# ------------------------------ compression ---------------------------- #
def test_int8_compression_error_feedback_converges():
    cfg = CompressionConfig(scheme="int8")
    g = jnp.asarray(np.random.RandomState(0).randn(64, 64), jnp.float32)
    err = None
    acc_true = np.zeros_like(g)
    acc_comp = np.zeros_like(g)
    for _ in range(20):
        comp, err = compress_tree(g, err, cfg)
        acc_true += np.asarray(g)
        acc_comp += np.asarray(comp)
    # with error feedback the accumulated sums track closely
    rel = np.max(np.abs(acc_true - acc_comp)) / np.max(np.abs(acc_true))
    assert rel < 0.02, rel
    assert compression_ratio(cfg) == 4.0


def test_topk_compression_keeps_largest():
    cfg = CompressionConfig(scheme="topk", topk_frac=0.1,
                            error_feedback=False)
    g = jnp.asarray(np.random.RandomState(1).randn(100), jnp.float32)
    comp, _ = compress_tree(g, None, cfg)
    comp = np.asarray(comp)
    kept = np.nonzero(comp)[0]
    assert 5 <= len(kept) <= 15
    thresh = np.sort(np.abs(np.asarray(g)))[-len(kept)]
    assert np.all(np.abs(np.asarray(g))[kept] >= thresh - 1e-6)


# ------------------------------ hlo analyzer --------------------------- #
def test_hlo_trip_count_aware_flops():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ x), None
        y, _ = jax.lax.scan(body, x, None, length=9)
        return y.sum()

    x = jnp.ones((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    res = hlo_analysis.analyze_hlo(compiled.as_text())
    # 9 matmuls of 2*64^3, vs cost_analysis' body-once count
    expect = 9 * 2 * 64 ** 3
    assert res["dot_flops"] == pytest.approx(expect, rel=0.01), res
    ca = compiled.cost_analysis()
    if isinstance(ca, list):           # jax 0.4.x returns [dict]
        ca = ca[0] if ca else {}
    xla_flops = ca.get("flops", 0)
    assert xla_flops < res["dot_flops"]   # the very bug we correct


def test_hlo_collective_accounting():
    import subprocess, sys, os
    # collectives need >1 device: subprocess with 4 host devices
    code = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
x = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P("d", None)))
def f(x):
    return jnp.sum(x)
compiled = jax.jit(f).lower(x).compile()
res = hlo_analysis.analyze_hlo(compiled.as_text(), n_devices=4)
assert res["collective_bytes"] > 0, res
print("OK", res["collectives"])
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert p.returncode == 0 and "OK" in p.stdout, p.stderr[-2000:]


def test_hardware_peaks_keyed_by_device_kind():
    from repro.launch.mesh import V5E, hardware
    assert hardware(V5E)["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="cpu"):
        hardware("cpu")
