"""Multi-device correctness checks, run in a subprocess with 8 host devices.

Each check compares a sharded computation on a (2, 4) ("data", "model") mesh
against its single-device reference.  Invoked by tests/test_parallel.py via
``python tests/mesh_checks.py <check>`` with XLA_FLAGS set by the parent —
the main test process must keep seeing exactly 1 device.
"""
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import numpy as np


def _mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 4), ("data", "model"))


def check_train_step_sharded_matches_single():
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.optim.adamw import AdamWConfig
    from repro.training.train_state import (init_train_state,
                                            make_train_step)
    cfg = reduced_config(get_config("internlm2-20b")).replace(
        dtype="float32", d_model=64, num_heads=8, num_kv_heads=4)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(k, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (4, 32), 0, cfg.vocab_size)}
    s_ref, m_ref = jax.jit(make_train_step(cfg, AdamWConfig()))(state, batch)
    mesh = _mesh()
    with mesh:
        s_sh, m_sh = jax.jit(make_train_step(cfg, AdamWConfig(), mesh))(
            state, batch)
    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4, \
        (float(m_ref["loss"]), float(m_sh["loss"]))
    l_ref = jax.tree_util.tree_leaves(s_ref["params"])
    l_sh = jax.tree_util.tree_leaves(s_sh["params"])
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(l_ref, l_sh))
    assert err < 5e-4, err
    print("OK train_step sharded==single, err", err)


def check_moe_sharded_matches_single():
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.models.moe import moe_block
    from repro.models.model import model_param_specs
    from repro.parallel.sharding import (DEFAULT_RULES, init_params,
                                         sharding_ctx)
    from repro.models import moe as moe_lib
    from repro.parallel.sharding import ParamSpec
    cfg = reduced_config(get_config("qwen3-moe-30b-a3b")).replace(
        dtype="float32", d_model=32, num_experts=8, experts_per_token=2,
        moe_d_ff=16)
    specs = moe_lib.moe_specs(cfg)
    params = init_params(jax.random.PRNGKey(0), specs)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)

    def f_single(params, x):
        out, aux = moe_block(params, x, cfg)
        return jnp.sum(out * jnp.cos(out)) + aux

    ref_val, ref_grads = jax.value_and_grad(f_single)(params, x)

    mesh = _mesh()

    def f_sharded(params, x):
        with sharding_ctx(mesh, DEFAULT_RULES):
            out, aux = moe_block(params, x, cfg)
            return jnp.sum(out * jnp.cos(out)) + aux

    with mesh:
        sh_val, sh_grads = jax.jit(jax.value_and_grad(f_sharded))(params, x)
    assert abs(float(ref_val) - float(sh_val)) < 1e-3, \
        (float(ref_val), float(sh_val))
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(sh_grads)):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-3, err
    print("OK moe sharded==single")


def check_embed_sharded_matches_take():
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.models.layers import embed_tokens
    from repro.parallel.sharding import DEFAULT_RULES, sharding_ctx
    cfg = reduced_config(get_config("internlm2-20b")).replace(
        dtype="float32", vocab_size=64, d_model=32)
    emb = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
    params = {"embedding": emb}
    ref = jnp.take(emb, toks, axis=0)
    mesh = _mesh()

    def f(params, toks):
        with sharding_ctx(mesh, DEFAULT_RULES):
            return embed_tokens(params, toks, cfg)

    with mesh:
        out = jax.jit(f)(params, toks)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < 1e-6, err

    # gradient stays correct through the shard_map
    def g_ref(emb):
        return jnp.sum(jnp.sin(jnp.take(emb, toks, axis=0)))

    def g_sh(emb):
        with sharding_ctx(mesh, DEFAULT_RULES):
            return jnp.sum(jnp.sin(embed_tokens({"embedding": emb}, toks,
                                                cfg)))

    with mesh:
        ge = jax.jit(jax.grad(g_sh))(emb)
    gr = jax.grad(g_ref)(emb)
    err = float(jnp.max(jnp.abs(ge - gr)))
    assert err < 1e-5, err
    print("OK embed sharded==take (+grads)")


def check_decode_flash_sharded():
    import jax, jax.numpy as jnp
    from repro.models.attention import decode_attention
    from repro.parallel.sharding import INFERENCE_RULES, sharding_ctx
    B, S, Hq, Hkv, D = 4, 64, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    t = jnp.asarray([10, 20, 30, 63], jnp.int32)
    ref = decode_attention(q, kc, vc, t)          # mesh-free path
    mesh = _mesh()

    def f(q, kc, vc, t):
        with sharding_ctx(mesh, INFERENCE_RULES):
            return decode_attention(q, kc, vc, t)

    with mesh:
        out = jax.jit(f)(q, kc, vc, t)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < 1e-5, err
    print("OK sharded flash-decode == local, err", err)


def check_torrent_broadcast():
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.weight_torrent import torrent_broadcast_pieces
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("pod", "data"))
    n, Pn, L = 4, 8, 32
    rng = np.random.RandomState(0)
    views = rng.randn(n, Pn, L).astype(np.float32)
    arr = jax.device_put(jnp.asarray(views),
                         NamedSharding(mesh, P("pod", None, None)))
    out = np.asarray(torrent_broadcast_pieces(arr, mesh, axis="pod",
                                              seeder=2))
    assert all(np.allclose(out[i], views[2]) for i in range(n))
    print("OK torrent broadcast")


def check_dryrun_cell_small():
    """The dry-run machinery itself on an 8-device mesh."""
    import jax
    from repro.configs.base import get_config, reduced_config, ShapeConfig
    from repro.launch.dryrun import lower_cell
    import repro.launch.dryrun as dr
    from repro.launch import hlo_analysis
    mesh = _mesh()
    import repro.configs.base as cb
    cfg = reduced_config(get_config("granite-8b"))
    cb._REGISTRY["granite-tiny"] = cfg
    shape = ShapeConfig("t", 64, 8, "train")
    cb.SHAPES["tiny_train"] = shape
    lowered, compiled = lower_cell("granite-tiny", "tiny_train", mesh)
    hlo = hlo_analysis.analyze_hlo(compiled.as_text(), n_devices=mesh.size)
    assert hlo["flops"] > 0 and hlo["collective_bytes"] > 0
    print("OK dryrun cell small:", hlo["flops"], hlo["collective_bytes"])




def check_tp_sp_and_pad_match_baseline():
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.optim.adamw import AdamWConfig
    from repro.training.train_state import init_train_state, make_train_step
    # 12 heads % 4 != 0 when shrunk to 6 -> exercises padding on model=4
    cfg = reduced_config(get_config("qwen3-14b")).replace(
        dtype="float32", d_model=64, num_heads=6, num_kv_heads=2,
        head_dim=16, vocab_size=256)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(k, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (4, 32), 0, cfg.vocab_size)}
    mesh = _mesh()
    with mesh:
        s0, m0 = jax.jit(make_train_step(cfg, AdamWConfig(), mesh))(
            state, batch)
        cfg_opt = cfg.replace(tp_sp=True, pad_attn_heads=True)
        s1, m1 = jax.jit(make_train_step(cfg_opt, AdamWConfig(), mesh))(
            state, batch)
    assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4, \
        (float(m0["loss"]), float(m1["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(s0["params"]),
                    jax.tree_util.tree_leaves(s1["params"])):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 5e-4, err
    print("OK tp_sp + head padding match baseline")




def check_moe_int8_a2a_close_to_exact():
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.models.moe import moe_block
    from repro.models import moe as moe_lib
    from repro.parallel.sharding import (DEFAULT_RULES, init_params,
                                         sharding_ctx)
    cfg = reduced_config(get_config("qwen3-moe-30b-a3b")).replace(
        dtype="float32", d_model=32, num_experts=8, experts_per_token=2,
        moe_d_ff=16)
    specs = moe_lib.moe_specs(cfg)
    params = init_params(jax.random.PRNGKey(0), specs)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
    mesh = _mesh()

    def run(c):
        def f(params, x):
            with sharding_ctx(mesh, DEFAULT_RULES):
                out, aux = moe_block(params, x, c)
                return jnp.sum(out * jnp.cos(out)) + aux
        with mesh:
            return jax.jit(jax.value_and_grad(f))(params, x)

    v0, g0 = run(cfg)
    v1, g1 = run(cfg.replace(moe_a2a_int8=True))
    rel = abs(float(v0) - float(v1)) / max(abs(float(v0)), 1e-9)
    assert rel < 0.05, rel      # int8 dispatch noise is bounded
    # gradients flow (straight-through) and stay finite
    import numpy as np
    for g in jax.tree_util.tree_leaves(g1):
        assert np.isfinite(np.asarray(g)).all()
    print("OK moe int8 a2a, rel err", rel)




def check_pipeline_parallel_matches_sequential():
    import jax, jax.numpy as jnp
    from repro.parallel.pipeline import pipeline_apply
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("pod", "data"))
    L, M, B, D = 4, 6, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    ws = jax.random.normal(ks[0], (L, D, D), jnp.float32) * 0.3
    xs = jax.random.normal(ks[1], (M, B, D), jnp.float32)

    def stage(w, x):
        return jnp.tanh(x @ w)

    # sequential reference
    ref = []
    for m in range(M):
        h = xs[m]
        for l in range(L):
            h = stage(ws[l], h)
        ref.append(h)
    ref = jnp.stack(ref)
    with mesh:
        out = jax.jit(lambda w, x: pipeline_apply(stage, w, x, mesh,
                                                  axis="pod"))(ws, xs)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < 1e-5, err
    print("OK pipeline parallel == sequential, err", err)


def check_torrent_broadcast_tree():
    """Pytree fan-out, leaf by leaf: only the seeder pod loads the bytes,
    and every device ends up holding them as a replicated leaf."""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.parallel.weight_torrent import torrent_broadcast
    mesh = make_mesh((4, 2), ("pod", "data"))
    rng = np.random.RandomState(1)
    tree = {"w": rng.randn(7, 13).astype(np.float32),
            "b": rng.randn(5).astype(np.float32).astype(jnp.bfloat16),
            "i": np.arange(9, dtype=np.int32).reshape(3, 3)}
    out = torrent_broadcast(tree, mesh, axis="pod", seeder=1)
    for k, leaf in tree.items():
        got = out[k]
        assert got.sharding.is_fully_replicated and got.dtype == leaf.dtype
        assert len(got.addressable_shards) == 8
        for s in got.addressable_shards:
            assert np.asarray(s.data).tobytes() == leaf.tobytes(), k
    print("OK torrent broadcast tree")


def check_chip_smoke_fanout_tiny():
    """chip_smoke.py --chips 4 path on 4 of the host devices, tiny model."""
    import jax
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import tempfile
    import chip_smoke
    from repro.configs.base import get_config, reduced_config
    cfg = reduced_config(get_config("qwen2-vl-2b"))
    with tempfile.TemporaryDirectory() as d:
        out = chip_smoke.phase_fanout(cfg=cfg, devices=jax.devices()[:4],
                                      workdir=os.path.join(d, "w"),
                                      n_requests=5, prompt_len=4, max_new=3)
    assert [len(t) for t in out["tokens"]] == [3] * 5
    print("OK chip_smoke fan-out tiny")


CHECKS = {k[6:]: v for k, v in list(globals().items())
          if k.startswith("check_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CHECKS[name]()
