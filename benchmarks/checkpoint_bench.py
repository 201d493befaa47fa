"""Checkpoint save/restore + swarm-image benchmarks.

Times the store's disk path (save, restore, async_save), the packed
step-image codec that feeds the swarm (pack -> manifest -> unpack),
and prints the analytic cold-start cost model at headline scale so the
Scenario XI simulation numbers have a closed-form anchor next to them.

Rows follow the repo convention: {name, us_per_call, derived, metrics}.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time


def _param_tree(n_layers: int = 4, d: int = 256):
    import numpy as np
    rng = np.random.default_rng(0)
    return {f"layer_{i}": {"w": rng.standard_normal((d, d), dtype=np.float32),
                           "b": rng.standard_normal((d,), dtype=np.float32)}
            for i in range(n_layers)}


def bench(verbose: bool = True, smoke: bool = False):
    import jax
    import numpy as np
    from repro.checkpoint.store import (CheckpointStore, async_save,
                                        pack_step_image, unpack_step_image)
    from repro.core.workunit import PieceManifest
    from repro.parallel.weight_torrent import cold_start_cost_model

    rows = []
    tree = _param_tree(n_layers=2 if smoke else 4)
    nbytes = sum(a.nbytes for layer in tree.values()
                 for a in layer.values())
    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        store = CheckpointStore(root, piece_bytes=1 << 20,
                                swarm_piece_bytes=256 << 10)

        t0 = time.perf_counter()
        store.save(0, tree)
        save_us = (time.perf_counter() - t0) * 1e6

        t0 = time.perf_counter()
        restored, _ = store.restore(tree, step=0)
        restore_us = (time.perf_counter() - t0) * 1e6
        flat_a = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
        flat_b = [np.asarray(x) for x in
                  jax.tree_util.tree_leaves(restored)]
        roundtrip_ok = all(np.array_equal(a, b)
                           for a, b in zip(flat_a, flat_b))
        rows.append({
            "name": "ckpt_save_restore",
            "us_per_call": save_us,
            "derived": (f"save={save_us / 1e3:.1f}ms "
                        f"restore={restore_us / 1e3:.1f}ms "
                        f"{nbytes / 1e6:.1f}MB ok={roundtrip_ok}"),
            "metrics": {"save_us": save_us, "restore_us": restore_us,
                        "tree_bytes": nbytes, "roundtrip_ok": roundtrip_ok},
        })

        t0 = time.perf_counter()
        th = async_save(store, 1, tree)
        snap_us = (time.perf_counter() - t0) * 1e6
        th.join()
        rows.append({
            "name": "ckpt_async_save",
            "us_per_call": snap_us,
            "derived": f"host_snapshot={snap_us / 1e3:.2f}ms (non-blocking)",
            "metrics": {"snapshot_us": snap_us},
        })

        # packed step image -> swarm manifest -> unpack roundtrip
        d = store.step_dir(0)
        t0 = time.perf_counter()
        image = pack_step_image(d)
        pack_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        pm = PieceManifest.from_bytes("bench", image, 256 << 10)
        hash_us = (time.perf_counter() - t0) * 1e6
        dest = os.path.join(root, "unpacked")
        t0 = time.perf_counter()
        unpack_step_image(image, dest)
        unpack_us = (time.perf_counter() - t0) * 1e6
        re_restored, _ = CheckpointStore(root).restore(tree, step=0)
        img_ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in
                     zip(flat_a, jax.tree_util.tree_leaves(re_restored)))
        mbps = len(image) / 1e6 / max(hash_us / 1e6, 1e-9)
        rows.append({
            "name": "ckpt_image_codec",
            "us_per_call": pack_us,
            "derived": (f"pack={pack_us / 1e3:.1f}ms "
                        f"hash={hash_us / 1e3:.1f}ms "
                        f"({mbps:.0f}MB/s, {pm.n_pieces} pieces) "
                        f"unpack={unpack_us / 1e3:.1f}ms ok={img_ok}"),
            "metrics": {"pack_us": pack_us, "hash_us": hash_us,
                        "unpack_us": unpack_us, "image_bytes": len(image),
                        "n_pieces": pm.n_pieces, "roundtrip_ok": img_ok},
        })
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # analytic anchor for Scenario XI: 2GB checkpoint, 50 replicas,
    # 200Mbps uplinks — the simulated swarm should approach these bounds
    cm = cold_start_cost_model(2.048e9, 50, link_Bps=25e6, n_pieces=128)
    rows.append({
        "name": "cold_start_model_2GB_50r",
        "us_per_call": 0.0,
        "derived": (f"origin={cm['origin_s']:.0f}s "
                    f"swarm>={cm['swarm_s']:.0f}s "
                    f"(x{cm['speedup']:.1f} bound) egress "
                    f"{cm['origin_egress_bytes'] / 1e9:.0f} -> "
                    f"{cm['swarm_origin_egress_bytes'] / 1e9:.0f}GB"),
        "metrics": cm,
    })
    if verbose:
        for r in rows:
            print(f"[ckpt] {r['name']}: {r['derived']}")
    return rows


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench()
