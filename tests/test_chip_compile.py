"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: each test lowers a jitted program at the real shapes and
hands it to the TPU compiler, which refuses what the chip would refuse
(misaligned Pallas blocks, too much VMEM, a program that does not fit
HBM).  The swarm kernels are compiled at the N=2000, P=128 flash crowd;
the serving decode step at the full published width of qwen2-vl-2b.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import swarm_kernels as sk
from repro.launch.mesh import V5E, hardware

N, P = 2048, 128          # N=2000 volunteers, padded to the row bucket
C, K_ISLANDS, H = 32, 8, 256
TOPK = 7                  # 2 * endgame_dup + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: its entries
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_program(name, s):
    """(jitted fn, args, static kwargs, expects a Pallas kernel)."""
    i32, b = jnp.int32, jnp.bool_
    if name == "rarest_keys_jnp" or name == "rarest_keys_pallas":
        impl = name.rsplit("_", 1)[1]
        return (sk._rarest_keys_jax,
                (_sds((P,), i32, s), _sds((N,), i32, s)),
                dict(n_pieces=P, impl=impl), impl == "pallas")
    if name == "island_has_jnp" or name == "island_has_pallas":
        impl = name.rsplit("_", 1)[1]
        return (sk._island_has_jax,
                (_sds((N, P), b, s), _sds((K_ISLANDS, N), b, s)),
                dict(impl=impl), impl == "pallas")
    if name == "match_requests":
        return (sk._match_requests_jax,
                (_sds((N, P), i32, s), _sds((N,), i32, s),
                 _sds((N,), i32, s), _sds((N, C), i32, s),
                 _sds((N, C), b, s), _sds((N, C), i32, s),
                 _sds((N, P), b, s), _sds((N,), b, s)), {}, False)
    if name == "choke_order":
        f32 = jnp.float32
        return (sk._choke_order_jax,
                (_sds((H, C), f32, s), _sds((H, C), f32, s),
                 _sds((H, C), b, s), _sds((H, C), i32, s)), {}, False)
    assert name == "holder_topk"
    return (sk._holder_topk_jax, (_sds((N, P), i32, s),), dict(k=TOPK),
            False)


@pytest.mark.parametrize("name", [
    "rarest_keys_jnp", "rarest_keys_pallas", "island_has_jnp",
    "island_has_pallas", "match_requests", "choke_order", "holder_topk"])
def test_swarm_kernel_compiles_for_v5e(one_chip, name):
    fn, args, static, pallas = _kernel_program(name, one_chip)
    text = fn.lower(*args, **static).compile().as_text()
    # the Pallas kernels must reach Mosaic, not the interpreter
    assert ("tpu_custom_call" in text) == pallas, name


def test_qwen2_vl_decode_step_fits_one_v5e(one_chip):
    """Full-width qwen2-vl-2b decode step (f32 master params, the serving
    smoke's 4 slots x 64-token cache) compiles for one chip and, params
    and KV cache included, fits its 16 GB."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.parallel.sharding import init_params
    from repro.training.train_state import make_decode_step

    cfg = get_config("qwen2-vl-2b")
    slots, max_len = 4, 64

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = place(jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), M.model_param_specs(cfg))))
    caches = place(jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), M.cache_specs_tree(cfg, slots, max_len))))
    batch = {"tokens": _sds((slots, 1), jnp.int32, one_chip),
             "positions": _sds((3, slots, 1), jnp.int32, one_chip)}
    compiled = jax.jit(make_decode_step(cfg)).lower(
        params, batch, caches).compile()
    mem = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert 1.7e9 < n_params < 1.9e9, n_params
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < hardware(V5E)["hbm_bytes"], peak


def test_pod_fanout_leaf_compiles_for_v5e_2x2(topo, one_chip):
    """The intra-pod fan-out of qwen2-vl-2b's largest leaf (28 x 1536 x
    8960 f32) over a 4-chip pod mesh: the ring is collective-permutes, the
    program stays small (ringing a flattened leaf made the compiler emit
    code in proportion to the leaf: 251 MB and minutes per leaf), and the
    leaf in flight fits beside the rest of the replicated params."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_mesh
    from repro.parallel.weight_torrent import _broadcast_leaf

    mesh = make_mesh((4,), ("pod",), devices=topo.devices)
    rows, rest = 28, (1536, 8960)
    views = jax.ShapeDtypeStruct(
        (4, 4, rows // 4) + rest, jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("pod", None, None,
                                                   None, None)))
    compiled = _broadcast_leaf.lower(views, mesh, "pod", 0, rows).compile()
    assert "collective-permute" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes < 16 << 20, mem
    leaf = rows * int(np.prod(rest)) * 4
    params = 7_108_122_624                     # qwen2-vl-2b, f32
    in_flight = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
    assert params - leaf + in_flight < hardware(V5E)["hbm_bytes"], mem
