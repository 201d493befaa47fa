#!/usr/bin/env python3
"""On-chip smoke test of the system's two device paths, on one TPU.

    python chip_smoke.py                # one chip: phases 1 and 2
    python chip_smoke.py --chips 4      # four chips: the intra-pod fan-out

Phase 1 (swarm flash crowd): batched Scenario VII at N=2000 volunteers,
P=128 pieces of a 64 MB image, tick 0.5 s, through `SimRuntime.run_batched`
and `SwarmHub`, once with the ``jax`` kernel backend and once with
``pallas`` on the chip, then with ``numpy`` on the host as the reference.
The virtual outcomes of all three must be equal, every kernel output must
come from the chip, and the Pallas kernels must be Mosaic-compiled.

Phase 2 (checkpoint -> serve): a full-width qwen2-vl-2b with parameters
drawn from ``--seed`` is saved with `CheckpointStore.save`, advertised with
`checkpoint_application`, fetched piece by piece by 4 replicas through
`SimRuntime`, restored onto the chip by `ServingEngine.from_swarm`, and
asked 8 greedy requests.  The restored parameters must be byte-identical
to the saved ones, and the tokens equal to those of an engine built
directly from the same parameters.

``--chips 4`` runs only the fan-out: `from_swarm` on a 4-device ``pod``
mesh rings the restored parameters across the chips (`torrent_broadcast`);
every device must then hold the bytes of the one-chip restore, and the
same requests must give the same tokens.

Each phase prints one JSON line; the last line is the verdict
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero and prints no verdict.  JAX's compile cache goes to
``JAX_COMPILATION_CACHE_DIR`` when set, else to ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# backend compiles seen by this process (jax.monitoring listener)
COMPILES = collections.Counter()
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the serving model: full published width, parameters from --seed
MODEL = "qwen2-vl-2b"

SWARM_KEYS = ("makespan_s", "full_replication_s", "p99_completion_s",
              "origin_up_mb", "replicas", "ledger_ops")


def _on_event(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        COMPILES["backend"] += 1


def require(ok: bool, what) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def emit(phase: str, wall_s: float, compiles: int, **outcomes) -> dict:
    dev = device_info()
    row = {"phase": phase, "platform": dev["platform"],
           "device_kind": dev["kind"], "device_count": dev["count"],
           "wall_s": wall_s, "compiles": compiles, **outcomes}
    print(json.dumps(row), flush=True)
    return row


# ============================ phase 1 =================================== #
def phase_swarm(n_volunteers: int = 2000, n_pieces: int = 128,
                image_mb: float = 64.0, tick_s: float = 0.5) -> dict:
    """Scenario VII batched on the jax and pallas backends against the
    numpy reference; returns the outcomes per backend."""
    import numpy as np
    from benchmarks.paper_tables import scenario_vii
    from repro.core import swarm_kernels as sk

    platform = device_info()["platform"]
    outcomes = {}
    for backend in ("jax", "pallas", "numpy"):
        sk.DEVICE_CALLS.clear()
        c0, t0 = COMPILES["backend"], time.perf_counter()
        res = scenario_vii(verbose=False, n_volunteers=n_volunteers,
                           image_mb=image_mb, n_pieces=n_pieces,
                           batched=True, tick_s=tick_s, backend=backend)
        wall = time.perf_counter() - t0
        calls = {f"{k}@{p}": v for (k, p), v in sk.DEVICE_CALLS.items()}
        outcomes[backend] = {k: res[k] for k in SWARM_KEYS}
        emit("swarm_flash_crowd", wall, COMPILES["backend"] - c0,
             backend=backend, n_volunteers=n_volunteers, n_pieces=n_pieces,
             ticks=res["ticks"], kernel_calls=calls, **outcomes[backend])
        if backend == "numpy":
            require(not calls, calls)
        else:
            require(calls and all(p == platform
                                  for _, p in sk.DEVICE_CALLS),
                    f"{backend} kernels ran off the {platform}: {calls}")
    ref = outcomes["numpy"]
    require(ref["replicas"] == n_volunteers, ref)
    for backend in ("jax", "pallas"):
        require(outcomes[backend] == ref, (backend, outcomes[backend], ref))

    # both Pallas kernels at the run's shapes: compiled by Mosaic on the
    # chip (not interpreted) and equal to numpy; Scenario VII has no
    # topology, so island_has is driven here directly
    rng = np.random.default_rng(0)
    nb = sk._bucket(n_volunteers)
    counts = rng.integers(0, n_volunteers, n_pieces).astype(np.int32)
    offsets = rng.integers(0, 1 << 16, nb).astype(np.int32)
    have = rng.random((nb, n_pieces)) < 0.3
    member = np.zeros((8, nb), dtype=bool)
    member[rng.integers(0, 8, nb), np.arange(nb)] = True
    c0, t0 = COMPILES["backend"], time.perf_counter()
    custom = {
        "rarest_keys": sk._rarest_keys_jax.lower(
            counts, offsets % n_pieces, n_pieces=n_pieces, impl="pallas"),
        "island_has": sk._island_has_jax.lower(have, member,
                                               impl="pallas")}
    compiled = {k: "tpu_custom_call" in v.compile().as_text()
                for k, v in custom.items()}
    agree = {
        "rarest_keys": np.array_equal(
            sk.rarest_keys(counts, offsets, n_pieces, backend="pallas"),
            sk.rarest_keys(counts, offsets, n_pieces, backend="numpy")),
        "island_has": np.array_equal(
            sk.island_has(have, member, backend="pallas"),
            sk.island_has(have, member, backend="numpy"))}
    emit("pallas_kernels", time.perf_counter() - t0,
         COMPILES["backend"] - c0, tpu_custom_call=compiled,
         equal_to_numpy=agree)
    if platform == "tpu":
        require(all(compiled.values()), compiled)
    require(all(agree.values()), agree)
    return outcomes


# ============================ phase 2 =================================== #
def _prompts(cfg, n_requests: int, prompt_len: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
            for _ in range(n_requests)]


def serve(engine, prompts, max_new: int):
    """Greedy-decode every prompt through the engine's continuous batch."""
    for p in prompts:
        engine.submit(p, max_new=max_new)
    reqs = list(engine.queue)
    while engine.queue or engine.active:
        engine.step()
    return [list(r.out_tokens) for r in reqs]


def leaf_digests(tree) -> list:
    """sha256 of every leaf's bytes, pulled one leaf at a time."""
    import jax
    import numpy as np
    return [hashlib.sha256(np.ascontiguousarray(np.asarray(x)).data)
            .hexdigest() for x in jax.tree_util.tree_leaves(tree)]


def publish_and_fetch(cfg, seed: int, workdir: str, n_replicas: int):
    """Seeded params -> `CheckpointStore.save` -> swarm fetch by
    `n_replicas` through `SimRuntime`.  Returns (the params' abstract
    template, their leaf digests, the app, the ready replicas, the
    time split)."""
    import jax
    from repro.checkpoint.store import CheckpointStore
    from repro.checkpoint.swarm_restore import checkpoint_application
    from repro.core import (Agent, AgentConfig, LinkModel, SimRuntime,
                            TrackerConfig, TrackerServer)
    from repro.models import model as M
    from repro.parallel.sharding import init_params

    specs = M.model_param_specs(cfg)
    t0 = time.perf_counter()
    host = jax.device_get(init_params(jax.random.PRNGKey(seed), specs))
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), host)
    digests = leaf_digests(host)
    store = CheckpointStore(os.path.join(workdir, "store"))
    store.save(0, host, extra={"seed": seed})
    del host
    app = checkpoint_application(store, 0, host_id="origin")
    t_save = time.perf_counter() - t0

    t0 = time.perf_counter()
    rt = SimRuntime(link=LinkModel(uplink_Bps=125e6, downlink_Bps=125e6))
    rt.add_node(TrackerServer(config=TrackerConfig(ping_interval_s=1.0)))
    acfg = dict(work_timeout_s=600.0, status_interval_s=0.5,
                piece_timeout_s=30.0, replicate_completed=True)
    origin = Agent("origin", config=AgentConfig(**acfg))
    rt.add_node(origin)
    origin.host_app(app)
    replicas = [Agent(f"R{i}", config=AgentConfig(**acfg))
                for i in range(n_replicas)]
    for a in replicas:
        rt.add_node(a)
    rt.run(until=24 * 3600.0,
           stop_when=lambda: all(app.app_id in a.images for a in replicas))
    require(all(app.app_id in a.images for a in replicas),
            "fetch incomplete")
    for a in replicas:
        require(a.px.assembled_image(app.app_id) == app.image, a.node_id)
    times = {"save_s": t_save, "fetch_wall_s": time.perf_counter() - t0,
             "fetch_virtual_s": rt.now(),
             "origin_up_mb": rt.tx_bytes.get("origin", 0) / 1e6,
             "image_mb": len(app.image) / 1e6,
             "n_swarm_pieces": app.manifest.n_pieces}
    return template, digests, app, replicas, times


def phase_serve(seed: int = 0, n_replicas: int = 4, n_requests: int = 8,
                prompt_len: int = 8, max_new: int = 8, cfg=None,
                workdir: str = os.path.join(ROOT, ".smoke_work")) -> dict:
    """Checkpoint -> swarm -> `from_swarm` -> greedy decode."""
    import gc

    import jax
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.parallel.sharding import init_params
    from repro.serving.engine import ServeConfig, ServingEngine

    cfg = cfg or get_config(MODEL)
    sc = ServeConfig(slots=4, max_len=64)
    prompts = _prompts(cfg, n_requests, prompt_len, seed)
    shutil.rmtree(workdir, ignore_errors=True)
    try:
        c0, t0 = COMPILES["backend"], time.perf_counter()
        direct = ServingEngine(cfg, init_params(
            jax.random.PRNGKey(seed), M.model_param_specs(cfg)), sc)
        want = serve(direct, prompts, max_new)
        t_direct = time.perf_counter() - t0
        del direct
        gc.collect()

        template, digests, app, replicas, times = publish_and_fetch(
            cfg, seed, workdir, n_replicas)
        t0 = time.perf_counter()
        eng = ServingEngine.from_swarm(
            cfg, template, sc, agent=replicas[0], app_id=app.app_id,
            workdir=os.path.join(workdir, "restore"))
        jax.block_until_ready(eng.params)
        t_restore = time.perf_counter() - t0
        on_chip = {d.platform for x in jax.tree_util.tree_leaves(eng.params)
                   for d in x.devices()}
        identical = leaf_digests(eng.params) == digests
        t0 = time.perf_counter()
        got = serve(eng, prompts, max_new)
        t_serve = time.perf_counter() - t0
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(template))
        emit("checkpoint_serve", t_direct + times["save_s"]
             + times["fetch_wall_s"] + t_restore + t_serve,
             COMPILES["backend"] - c0, model=cfg.name, params=n_params,
             replicas=n_replicas, direct_serve_s=t_direct,
             restore_s=t_restore, swarm_serve_s=t_serve,
             params_on=sorted(on_chip), byte_identical=identical,
             tokens_equal=got == want, n_requests=len(prompts),
             tokens=sum(map(len, got)), **times)
        require(on_chip == {device_info()["platform"]}, on_chip)
        require(identical, "restored params differ from the saved ones")
        require(got == want, (got, want))
        return {"tokens": got, "digests": digests}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# ===================== --chips 4: intra-pod fan-out ===================== #
def phase_fanout(seed: int = 0, n_requests: int = 8, prompt_len: int = 8,
                 max_new: int = 8, cfg=None, devices=None,
                 workdir: str = os.path.join(ROOT, ".smoke_work")) -> dict:
    """One-chip restore vs `from_swarm` on a 4-device pod mesh."""
    import gc

    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import ServeConfig, ServingEngine

    cfg = cfg or get_config(MODEL)
    sc = ServeConfig(slots=4, max_len=64)
    prompts = _prompts(cfg, n_requests, prompt_len, seed)
    devices = devices or jax.devices()
    require(len(devices) == 4, devices)
    shutil.rmtree(workdir, ignore_errors=True)
    try:
        c0 = COMPILES["backend"]
        template, digests, app, (rep,), times = publish_and_fetch(
            cfg, seed, workdir, n_replicas=1)
        t0 = time.perf_counter()
        one = ServingEngine.from_swarm(
            cfg, template, sc, agent=rep, app_id=app.app_id,
            workdir=os.path.join(workdir, "restore1"))
        one_identical = leaf_digests(one.params) == digests
        want = serve(one, prompts, max_new)
        t_one = time.perf_counter() - t0
        del one
        gc.collect()

        mesh = make_mesh((4,), ("pod",), devices=devices)
        t0 = time.perf_counter()
        eng = ServingEngine.from_swarm(
            cfg, template, sc, agent=rep, app_id=app.app_id, mesh=mesh,
            workdir=os.path.join(workdir, "restore4"))
        jax.block_until_ready(eng.params)
        t_fanout = time.perf_counter() - t0
        per_device = collections.defaultdict(list)
        for x in jax.tree_util.tree_leaves(eng.params):
            require(x.sharding.is_fully_replicated, x.sharding)
            for s in x.addressable_shards:
                per_device[s.device.id].append(hashlib.sha256(
                    np.ascontiguousarray(np.asarray(s.data)).data)
                    .hexdigest())
        every_device = {d: v == digests for d, v in per_device.items()}
        t0 = time.perf_counter()
        got = serve(eng, prompts, max_new)
        t_serve = time.perf_counter() - t0
        emit("pod_fanout", times["save_s"] + times["fetch_wall_s"] + t_one
             + t_fanout + t_serve, COMPILES["backend"] - c0,
             model=cfg.name, mesh={"pod": 4}, one_chip_s=t_one,
             fanout_s=t_fanout, serve_s=t_serve,
             one_chip_byte_identical=one_identical,
             devices_byte_identical=every_device,
             tokens_equal=got == want, n_requests=len(prompts), **times)
        require(one_identical, "one-chip restore differs from the save")
        require(len(every_device) == 4 and all(every_device.values()),
                every_device)
        require(got == want, (got, want))
        return {"tokens": got, "digests": digests}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev['platform']}); "
              "nothing measured", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 1
    if args.chips == 4:
        phase_fanout(seed=args.seed)
    else:
        phase_swarm()
        phase_serve(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
