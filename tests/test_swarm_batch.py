"""Array-native batched swarm engine (core/swarm_arrays + swarm_kernels):
kernel differentials against the scalar PieceExchange, request-for-request
trace equivalence via SwarmHub.mirror_scalar, mixed-mode event-heap
determinism (run vs run_batched), batched flash-crowd smoke, and the chaos
overlay on the batched path."""
import random

import numpy as np
import pytest

pytestmark = pytest.mark.protocol

from repro.core import (Agent, AgentConfig, LinkModel, Msg, PieceManifest,
                        SimRuntime, SwarmHub, TrackerConfig, TrackerServer,
                        make_prime_app, rarest_first_order_np)
from repro.core import swarm_kernels as sk
from repro.core.messages import HAVE, PIECE_REQ, UNCHOKE
from tests.test_exchange_scaling import _engine


# ===================== kernel differentials ============================= #
def test_rarest_orders_matches_scalar_per_row():
    """Batched rarest-first keys reproduce `rarest_first_order_np` (itself
    differentially tied to the scalar `rarest_first_order`) row by row
    over randomized counts / missing sets / tie-break offsets."""
    rng = random.Random(11)
    for _ in range(40):
        n_pieces = rng.randrange(1, 100)
        n_rows = rng.randrange(1, 12)
        counts = np.array([rng.randrange(0, 7) for _ in range(n_pieces)],
                          dtype=np.int32)
        missing = np.zeros((n_rows, n_pieces), dtype=bool)
        offsets = np.zeros(n_rows, dtype=np.int64)
        for r in range(n_rows):
            missing[r, rng.sample(range(n_pieces),
                                  rng.randrange(0, n_pieces + 1))] = True
            offsets[r] = rng.randrange(0, 900)
        orders = sk.rarest_orders(missing, counts, offsets, n_pieces)
        assert orders.shape == (n_rows, n_pieces)
        for r in range(n_rows):
            k = int(missing[r].sum())
            want = rarest_first_order_np(
                sorted(np.nonzero(missing[r])[0].tolist()), counts,
                offset=int(offsets[r]), n_pieces=n_pieces)
            assert orders[r, :k].tolist() == want, f"row {r}"


def test_choke_order_matches_scalar_ranking():
    """Batched choke ranking reproduces `_rechoke_app`'s
    sorted(key=(-rate_from, -rate_to, name)) for every holder at once,
    including rate ties broken by the lexicographic name."""
    rng = random.Random(5)
    rates = [0.0, 0.0, 1.5, 7.25, 7.25, 100.0]
    for _ in range(40):
        n_cols = rng.randrange(1, 20)
        n_holders = rng.randrange(1, 10)
        names = sorted(f"N{rng.randrange(1000):03d}-{i}"
                       for i in range(n_cols))
        ranks = np.arange(n_cols, dtype=np.int64)
        recv = np.array([[rng.choice(rates) for _ in range(n_cols)]
                         for _ in range(n_holders)], dtype=np.float32)
        sent = np.array([[rng.choice(rates) for _ in range(n_cols)]
                         for _ in range(n_holders)], dtype=np.float32)
        cand = np.array([[rng.random() < 0.6 for _ in range(n_cols)]
                         for _ in range(n_holders)], dtype=bool)
        order = sk.choke_order_np(recv, sent, cand, ranks)
        for h in range(n_holders):
            cs = [j for j in range(n_cols) if cand[h, j]]
            want = sorted(cs, key=lambda j: (-recv[h, j], -sent[h, j],
                                             names[j]))
            got = order[h, :len(cs)].tolist()
            assert got == want, f"holder {h}"


@pytest.mark.jax_slow
def test_kernel_backends_agree_with_numpy():
    """jax (and pallas, when present) backends produce bit-identical
    rarest orders and choke rankings to the numpy reference."""
    backends = [b for b in sk.available_backends() if b != "numpy"]
    rng = random.Random(31)
    for _ in range(10):
        n_pieces = rng.randrange(1, 300)
        n_rows = rng.randrange(1, 20)
        counts = np.array([rng.randrange(0, 9) for _ in range(n_pieces)],
                          dtype=np.int32)
        missing = np.array([[rng.random() < 0.5 for _ in range(n_pieces)]
                            for _ in range(n_rows)], dtype=bool)
        offsets = np.array([rng.randrange(0, 2000)
                            for _ in range(n_rows)], dtype=np.int64)
        ref = sk.rarest_orders(missing, counts, offsets, n_pieces,
                               backend="numpy")
        for b in backends:
            got = sk.rarest_orders(missing, counts, offsets, n_pieces,
                                   backend=b)
            assert got.tolist() == ref.tolist(), b
        recv = np.array([[rng.choice([0.0, 3.5, 9.0])
                          for _ in range(n_rows)]
                         for _ in range(n_rows)], dtype=np.float32)
        sent = recv.T.copy()
        cand = np.array([[rng.random() < 0.5 for _ in range(n_rows)]
                         for _ in range(n_rows)], dtype=bool)
        ranks = np.arange(n_rows, dtype=np.int64)
        cref = sk.choke_order_np(recv, sent, cand, ranks)
        for b in backends:
            got = sk.choke_order(recv, sent, cand, ranks, backend=b)
            assert got.tolist() == cref.tolist(), b


# ============== trace differential: hub vs scalar pump ================== #
def test_batched_requests_match_scalar_over_seeded_trace():
    """320-event seeded trace: after every event, a hub mirroring the
    scalar engine's exact information set must predict the scalar pump's
    PIECE_REQ decisions request-for-request (piece, holder, order), and
    its endgame bridge must predict the scalar endgame duplicates."""
    n_pieces = 64
    manifest = PieceManifest.synthetic("a", n_pieces * 1000, 1000)
    px, log = _engine(piece_pipeline=6)
    rng = random.Random(97)
    peers = [f"P{i}" for i in range(16)]
    px.join("a", manifest)
    px.note_full_seeders("a", set(peers[:2]))
    compared = 0
    for step in range(320):
        # apply the event with pump disabled so the mirror sees the
        # pre-decision state the scalar engine is about to act on
        orig_pump, px.pump = px.pump, lambda app_id: None
        roll = rng.random()
        if roll < 0.5:
            px.on_have(Msg(HAVE, rng.choice(peers),
                           {"app_id": "a",
                            "mask": rng.getrandbits(n_pieces)}))
        elif roll < 0.8:
            px.on_unchoke(Msg(UNCHOKE, rng.choice(peers), {"app_id": "a"}))
        else:
            px.on_peer_gone(rng.choice(peers))
        px.pump = orig_pump
        hub = SwarmHub.mirror_scalar(px, "a")
        want = hub.decide_requests("a", "L", now=0.0)
        want_eg = hub.decide_endgame("a", "L", now=0.0)
        n0 = len(log)
        px.pump("a")
        got = [(m.payload["piece_id"], d) for d, m in log[n0:]
               if m.kind == PIECE_REQ and not m.payload.get("endgame")]
        got_eg = [(m.payload["piece_id"], d) for d, m in log[n0:]
                  if m.kind == PIECE_REQ and m.payload.get("endgame")]
        assert got == want, f"step {step}"
        assert got_eg == want_eg, f"step {step} (endgame)"
        compared += len(got)
    assert compared > 10          # the trace actually exercised matching


# ================= mixed-mode event-heap determinism ==================== #
def _mini_flash(n_leechers=4):
    rt = SimRuntime(link=LinkModel(uplink_Bps=12.5e6,
                                   downlink_Bps=12.5e6))
    rt.add_node(TrackerServer(config=TrackerConfig(ping_interval_s=2.0)))
    host = Agent("host", config=AgentConfig(work_timeout_s=600.0))
    rt.add_node(host)
    app = make_prime_app("mm-app", "host", 3, 6_000, n_parts=6,
                         sim_time_per_number=1e-4, swarm=True,
                         app_bytes=262_144, piece_bytes=32_768)
    host.host_app(app)
    leech = [Agent(f"L{i}", config=AgentConfig(work_timeout_s=600.0))
             for i in range(n_leechers)]
    for a in leech:
        rt.add_node(a)
    done = lambda: all("mm-app" in a.images for a in leech)
    return rt, host, leech, done


def test_run_batched_without_ticks_is_event_identical_to_run():
    """`run_batched` shares the heap, the monotonic `_seq` counter and
    `events_processed` with `run`; with no tick callback it must drain
    the same scenario pop-for-pop: same event count, same sequence
    watermark, same virtual clock, same per-node traffic."""
    a_rt, a_host, a_leech, a_done = _mini_flash()
    b_rt, b_host, b_leech, b_done = _mini_flash()
    a_rt.run(until=3_600, stop_when=a_done)
    b_rt.run_batched(until=3_600, stop_when=b_done, tick_s=0.25)
    assert a_done() and b_done()
    assert a_rt.events_processed == b_rt.events_processed
    assert repr(a_rt._seq) == repr(b_rt._seq)   # same push watermark
    assert a_rt.now() == b_rt.now()
    assert a_rt.tx_bytes == b_rt.tx_bytes
    assert a_host.completed_at == b_host.completed_at


def test_run_batched_resumes_mixed_with_run():
    """Mixed-mode regression: a scenario driven part-way by `run`, then
    finished by `run_batched` (and vice versa) lands in the same final
    state — the shared seq counter keeps FIFO order across the seam."""
    final = []
    for order in ((0, 1), (1, 0)):
        rt, host, leech, done = _mini_flash()
        runners = [lambda u: rt.run(until=u, stop_when=done),
                   lambda u: rt.run_batched(until=u, stop_when=done,
                                            tick_s=0.5)]
        runners[order[0]](1.5)
        assert not done()
        runners[order[1]](3_600)
        assert done()
        final.append((rt.events_processed, repr(rt._seq), rt.now(),
                      dict(rt.tx_bytes)))
    assert final[0] == final[1]


# ==================== batched end-to-end scenarios ====================== #
def test_scenario_vii_batched_smoke():
    """Small batched flash crowd completes and fully replicates; the hub
    actually carried the decisions (batch_ops) and coalesced the control
    plane (logical > heap events)."""
    from benchmarks.paper_tables import scenario_vii
    res = scenario_vii(verbose=False, n_volunteers=8, image_mb=4.0,
                       n_pieces=8, batched=True)
    assert res["done"] and res["replicated"] and res["replicas"] == 8
    assert res["batch_ops"] > 0
    assert res["logical_events"] > res["events"] > 0
    assert res["full_replication_s"] >= res["makespan_s"] > 0
    assert res["backend"] in sk.available_backends()


def test_chaos_overlay_on_batched_path():
    """Seeded FaultPlan over the batched engine: loss / dup / jitter /
    churn / a partition, with the PR-4 convergence, quorum and
    hub-consistency invariants asserted by check_invariants()."""
    from repro.core.chaos import ChaosScenario
    sc = ChaosScenario(seed=3, n_volunteers=8, n_pieces=12, n_parts=16,
                       image_bytes=96_000, real_image=False,
                       batched=True).run()
    sc.check_invariants()
    rep = sc.report()
    assert rep["replicated"] and rep["done"]
    assert rep["batch_ops"] > 0


@pytest.mark.jax_slow
def test_scenario_vii_batched_large_n_converges():
    """N=500 batched flash crowd (the CI sweep ceiling) fully replicates
    and clearly outruns the per-message path's historical event rate."""
    from benchmarks.paper_tables import scenario_vii
    res = scenario_vii(verbose=False, n_volunteers=500, batched=True)
    assert res["done"] and res["replicated"] and res["replicas"] == 500
    assert res["wall_s"] < 120
    assert res["events_per_sec"] > 500_000


# ================ backend selection and compile budget ================== #
def test_unknown_backend_raises(monkeypatch):
    """No silent numpy fallback: an unknown name raises wherever it
    enters (explicit argument, global default, env-selected default)."""
    with pytest.raises(ValueError, match="nope"):
        sk.set_backend("nope")
    with pytest.raises(ValueError, match="nope"):
        sk.get_backend("nope")
    with pytest.raises(ValueError, match="nope"):
        sk.rarest_keys(np.zeros(4, np.int32), np.zeros(2, np.int64), 4,
                       backend="nope")
    from repro.core.swarm_arrays import SwarmHub
    with pytest.raises(ValueError, match="nope"):
        SwarmHub(backend="nope")
    monkeypatch.setattr(sk, "_backend", "nope")
    with pytest.raises(ValueError, match="nope"):
        sk.get_backend()


def test_unavailable_backend_raises(monkeypatch):
    monkeypatch.setattr(sk, "available_backends", lambda: ["numpy"])
    with pytest.raises(ValueError, match="unavailable"):
        sk.set_backend("jax")
    with pytest.raises(ValueError, match="unavailable"):
        sk.get_backend("pallas")
    assert sk.get_backend("numpy") == "numpy"


@pytest.mark.parametrize("kernel", ["rarest_keys", "island_has"])
def test_pallas_kernels_interpreted_only_on_cpu(kernel):
    """Lowered for the CPU, the Pallas backend carries the interpreter
    and no Mosaic call; tests/test_chip_compile.py shows the TPU lowering
    carries the compiled kernel (`tpu_custom_call`)."""
    if kernel == "rarest_keys":
        lowered = sk._rarest_keys_jax.lower(
            np.zeros(16, np.int32), np.zeros(8, np.int32), n_pieces=16,
            impl="pallas")
    else:
        lowered = sk._island_has_jax.lower(
            np.zeros((8, 16), bool), np.zeros((8, 8), bool), impl="pallas")
    assert "tpu_custom_call" not in lowered.as_text()


def test_jax_backend_compiles_per_bucket_not_per_tick():
    """Batched Scenario VII at N=200 on the jax backend: the wrappers pad
    every varying dimension to a power-of-two bucket, so the run compiles
    a few dozen programs over its ~500 ticks (it compiled about once per
    tick before), with outcomes identical to numpy."""
    import jax
    from benchmarks.paper_tables import scenario_vii
    keys = ("makespan_s", "full_replication_s", "p99_completion_s",
            "origin_up_mb", "replicas", "ledger_ops")
    kw = dict(verbose=False, n_volunteers=200, n_pieces=128, batched=True)
    ref = scenario_vii(backend="numpy", **kw)
    seen = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        got = scenario_vii(backend="jax", **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    compiles = len(seen)
    assert got["ticks"] > 400
    assert compiles <= 40, compiles
    assert {k: got[k] for k in keys} == {k: ref[k] for k in keys}


# ====== ISSUE 10: fused request matching / endgame top-k kernels ======== #
def _match_requests_scalar(orders, n_walk, budgets, cand, cand_ok,
                           cand_key, have, full):
    """Pure-Python greedy walk — the semantics `match_requests_np`
    vectorizes: per row, for each order position in turn, pick the
    lowest-keyed usable candidate that holds the piece, mark it busy,
    burn one budget unit."""
    R, P = orders.shape
    C = cand.shape[1]
    picks = np.full((R, P), -1, dtype=np.int32)
    for r in range(R):
        taken = {c for c in range(C) if not cand_ok[r, c]}
        budget = int(budgets[r])
        for k in range(min(int(n_walk[r]), P)):
            if budget <= 0 or len(taken) == C:
                break
            p = int(orders[r, k])
            best = None
            for c in range(C):
                if c in taken:
                    continue
                j = int(cand[r, c])
                if not (full[j] or have[j, p]):
                    continue
                if best is None or cand_key[r, c] < cand_key[r, best]:
                    best = c
            if best is not None:
                picks[r, k] = int(cand[r, best])
                taken.add(best)
                budget -= 1
    return picks


def _holder_topk_scalar(keys, k):
    """Per-column sorted selection of the K cheapest valid holders."""
    n, p = keys.shape
    out = np.full((k, p), -1, dtype=np.int32)
    for col in range(p):
        valid = sorted((int(keys[r, col]), r) for r in range(n)
                       if keys[r, col] < sk.KEY_INF32)
        for s, (_, r) in enumerate(valid[:k]):
            out[s, col] = r
    return out


def _random_match_case(rng):
    R = rng.randrange(1, 10)
    P = rng.randrange(1, 24)
    N = rng.randrange(1, 16)
    C = rng.randrange(1, min(N, 8) + 1)
    orders = np.array([rng.sample(range(P), P) for _ in range(R)],
                      dtype=np.int32)
    n_walk = np.array([rng.randrange(0, P + 1) for _ in range(R)],
                      dtype=np.int32)
    budgets = np.array([rng.randrange(0, 7) for _ in range(R)],
                       dtype=np.int32)
    cand = np.full((R, C), -1, dtype=np.int32)
    cand_ok = np.zeros((R, C), dtype=bool)
    cand_key = np.full((R, C), sk.KEY_INF32, dtype=np.int32)
    for r in range(R):
        rows = rng.sample(range(N), rng.randrange(0, C + 1))
        keys = rng.sample(range(1 << 20), len(rows))   # unique per row
        for c, (j, key) in enumerate(zip(rows, keys)):
            cand[r, c] = j
            cand_ok[r, c] = rng.random() < 0.85
            cand_key[r, c] = key
    have = np.array([[rng.random() < 0.45 for _ in range(P)]
                     for _ in range(N)], dtype=bool)
    full = np.array([rng.random() < 0.15 for _ in range(N)], dtype=bool)
    return orders, n_walk, budgets, cand, cand_ok, cand_key, have, full


def test_match_requests_matches_scalar_reference():
    """The fused holder-match kernel reproduces the pure-Python greedy
    walk over randomized rows/candidates/budgets (numpy path: this is
    the reference the jax/pallas backends are then held to)."""
    rng = random.Random(23)
    picked = 0
    for _ in range(60):
        case = _random_match_case(rng)
        got = sk.match_requests_np(*case)
        want = _match_requests_scalar(*case)
        assert got.tolist() == want.tolist()
        picked += int((got >= 0).sum())
    assert picked > 100            # the cases actually exercised matching


def test_holder_topk_matches_scalar_reference():
    """The endgame shortlist kernel returns exactly the K cheapest valid
    holders per piece, ascending, -1 padded (keys unique per column, as
    the hub guarantees by embedding the name rank)."""
    rng = random.Random(29)
    filled = 0
    for _ in range(60):
        n = rng.randrange(1, 14)
        p = rng.randrange(1, 20)
        k = rng.randrange(1, 8)
        keys = np.full((n, p), sk.KEY_INF32, dtype=np.int32)
        for col in range(p):
            rows = rng.sample(range(n), rng.randrange(0, n + 1))
            vals = rng.sample(range(1 << 27), len(rows))
            for r, v in zip(rows, vals):
                keys[r, col] = v
        got = sk.holder_topk_np(keys, k)
        want = _holder_topk_scalar(keys, k)
        assert got.shape == (k, p)
        assert got.tolist() == want.tolist()
        filled += int((got >= 0).sum())
    assert filled > 100


@pytest.mark.jax_slow
def test_fused_kernel_backends_agree_with_numpy():
    """jax (and pallas, when present) produce bit-identical request
    matches and endgame shortlists to the numpy reference."""
    backends = [b for b in sk.available_backends() if b != "numpy"]
    rng = random.Random(41)
    for _ in range(12):
        case = _random_match_case(rng)
        ref = sk.match_requests(*case, backend="numpy")
        for b in backends:
            got = sk.match_requests(*case, backend=b)
            assert got.tolist() == ref.tolist(), b
        n = rng.randrange(1, 20)
        p = rng.randrange(1, 24)
        k = rng.randrange(1, 9)
        keys = np.full((n, p), sk.KEY_INF32, dtype=np.int32)
        for col in range(p):
            rows = rng.sample(range(n), rng.randrange(0, n + 1))
            vals = rng.sample(range(1 << 27), len(rows))
            for r, v in zip(rows, vals):
                keys[r, col] = v
        tref = sk.holder_topk(keys, k, backend="numpy")
        for b in backends:
            got = sk.holder_topk(keys, k, backend=b)
            assert got.tolist() == tref.tolist(), b


def _shaped_match_case(seed, R, C, P=256, N=64, dens=0.05, walk="mixed",
                       budget="mixed", ok=0.85, cost=False):
    """One `match_requests` case at a cell's widths: permutation orders
    over P pieces; ``walk`` and ``budget`` give each row "mixed" (drawn
    from 0..hi), "none", "part" (1..hi-1) or "full" (hi), where hi is P
    for the walk and C + 3, more than the width, for the budget;
    ``dens`` is the share of pieces a holder holds (0: no candidate
    holds anything); ``cost`` gives keys ``cost * 2^20 + rank`` with
    shared costs, else name ranks."""
    rng = np.random.default_rng(seed)
    orders = np.stack([rng.permutation(P) for _ in range(R)]) \
        .astype(np.int32)
    draw = {"mixed": lambda hi: rng.integers(0, hi + 1, R),
            "none": lambda hi: np.zeros(R, np.int64),
            "part": lambda hi: rng.integers(1, hi, R),
            "full": lambda hi: np.full(R, hi)}
    n_walk = draw[walk](P).astype(np.int32)
    budgets = draw[budget](C + 3).astype(np.int32)
    cand = np.stack([rng.choice(N, C, replace=False) for _ in range(R)]) \
        .astype(np.int32)
    cand[rng.random((R, C)) < 0.1] = -1
    cand_ok = (cand >= 0) & (rng.random((R, C)) < ok)
    rank = np.stack([rng.choice(1 << 20, C, replace=False)
                     for _ in range(R)])
    key = rank + (rng.integers(0, 4, (R, C)) << 20 if cost else 0)
    cand_key = np.where(cand >= 0, key, sk.KEY_INF32).astype(np.int32)
    have = rng.random((N, P)) < dens
    full = (rng.random(N) < 0.03) & (dens > 0)
    return orders, n_walk, budgets, cand, cand_ok, cand_key, have, full


# (case kwargs, whether the reference makes picks there)
_MATCH_SHAPES = {
    "p256_w8": (dict(R=40, C=8), True),
    "p256_w32": (dict(R=8, C=32), True),
    "p256_w1": (dict(R=40, C=1), True),
    "p256_w8_sparse": (dict(R=64, C=8, dens=0.004), True),
    "cost_keys_w8": (dict(R=40, C=8, cost=True), True),
    "cost_keys_w32": (dict(R=8, C=32, cost=True), True),
    "starving": (dict(R=24, C=8, dens=0.0, walk="full"), False),
    "walk_none": (dict(R=16, C=8, walk="none"), False),
    "walk_part": (dict(R=16, C=8, walk="part"), True),
    "walk_full": (dict(R=16, C=8, walk="full"), True),
    "budget_none": (dict(R=16, C=8, budget="none"), False),
    "budget_over_width": (dict(R=16, C=8, budget="full", dens=0.5), True),
    "none_usable": (dict(R=16, C=8, ok=0.0), False),
}


@pytest.mark.jax_slow
@pytest.mark.parametrize("name", sorted(_MATCH_SHAPES))
def test_match_requests_jax_matches_numpy_at_cell_widths(name):
    """The device walk (by picks) makes the reference walk's (by order
    positions) decisions bit for bit at P = 256, candidate widths 1, 8
    and 32 and both key encodings, with starving rows, walks of none,
    part or all of P, spent and oversized budgets and no usable
    candidate."""
    kw, picks_some = _MATCH_SHAPES[name]
    case = _shaped_match_case(sum(map(ord, name)), **kw)
    ref = sk.match_requests_np(*case)
    assert ref.tolist() == _match_requests_scalar(*case).tolist()
    got = sk.match_requests(*case, backend="jax")
    assert got.dtype == np.int32 and got.shape == ref.shape
    assert got.tolist() == ref.tolist()
    made = (ref >= 0).sum(axis=1)
    assert bool(made.any()) == picks_some
    if name == "budget_over_width":
        usable = case[4].sum(axis=1)
        assert (made == usable).any()       # a row took every candidate
    assert (made <= np.minimum(case[2].clip(0), kw["C"])).all()


@pytest.mark.jax_slow
def test_match_walk_passes_bounded_by_picks_not_pieces(monkeypatch):
    """A short batched Scenario VII on the jax backend: the hub's
    ``swarm.match.steps`` counter holds each `match_requests` call to at
    most ``piece_pipeline + 1`` passes of the device walk (128 order
    positions here), and ``swarm.match.picks`` counts the picks the
    calls returned, at most ``piece_pipeline`` a row."""
    from benchmarks.paper_tables import scenario_vii
    from repro.core import AgentConfig, swarm_arrays, trace
    pipeline = AgentConfig().piece_pipeline
    calls = []
    inner = swarm_arrays.match_requests

    def spy(orders, *args, **kw):
        picks = inner(orders, *args, **kw)
        calls.append((picks >= 0).sum(axis=1))
        return picks
    spy.__name__ = inner.__name__
    monkeypatch.setattr(swarm_arrays, "match_requests", spy)
    before = trace.snapshot()
    got = scenario_vii(verbose=False, n_volunteers=24, n_pieces=128,
                       image_mb=4, batched=True, backend="jax")
    d = trace.delta(before, trace.snapshot())
    assert got["ticks"] > 20 and calls
    assert d["calls"]["swarm.kernel.match_requests"] == len(calls)
    steps = [int(made.max()) + 1 for made in calls]
    assert max(steps) <= pipeline + 1
    assert d["counts"]["swarm.match.steps"] == sum(steps)
    rows = sum(made.size for made in calls)
    picks = d["counts"]["swarm.match.picks"]
    assert picks == sum(int(made.sum()) for made in calls)
    assert 0 < picks <= pipeline * rows


# ========= ISSUE 10: array ledger vs scalar pending differential ======== #
def _assert_ledger_matches_dicts(hub):
    """Every hub state's in-flight ledger must be entry-for-entry
    identical to its engines' scalar `px.pending` dicts: same pieces,
    same holders, same request timestamps, same budget counters."""
    entries = 0
    max_dup = 0
    for st in hub.states.values():
        for name, i in st.row.items():
            px = st.clients[i]
            if px is None or not st.alive[i]:
                continue
            pending = px.pending.get(st.app_id, {})
            assert int(st.pend_n[i]) == len(pending), name
            assert int(st.pipeline[i]) == int(px.cfg.piece_pipeline)
            total = 0
            for p, asked in pending.items():
                cnt = int(st.pend_cnt[i, p])
                assert cnt == len(asked), (name, p)
                max_dup = max(max_dup, cnt)
                named = {}
                rowless = []
                for s in range(cnt):
                    j = int(st.pend_holder[i, p, s])
                    t = float(st.pend_t[i, p, s])
                    if j >= 0:
                        named[st.names[j]] = t
                    else:
                        assert j == -2, (name, p, s)
                        rowless.append(t)
                assert named == {h: float(t) for h, t in asked.items()
                                 if h in st.row}, (name, p)
                assert sorted(rowless) == sorted(
                    float(t) for h, t in asked.items()
                    if h not in st.row), (name, p)
                total += cnt
                entries += cnt
            # no ledger entries exist outside the dict's pieces
            assert int(st.pend_cnt[i].astype(np.int64).sum()) == total, name
    return entries, max_dup


def test_array_ledger_matches_scalar_pending_over_trace():
    """Seeded >=500-event batched flash crowd: after EVERY hub tick, the
    array ledger (pend_holder/pend_t/pend_cnt/pend_n) is entry-for-entry
    identical to the scalar `px.pending` dicts — requests, endgame
    duplicates, cancels and budget counters all flow through the same
    funnel and may never drift."""
    rt = SimRuntime(link=LinkModel(uplink_Bps=12.5e6,
                                   downlink_Bps=12.5e6))
    rt.add_node(TrackerServer(config=TrackerConfig(ping_interval_s=2.0)))
    hub = SwarmHub()
    host = Agent("host", config=AgentConfig(work_timeout_s=600.0),
                 hub=hub)
    rt.add_node(host)
    app = make_prime_app("lg-app", "host", 3, 6_000, n_parts=8,
                         sim_time_per_number=1e-4, swarm=True,
                         app_bytes=16 * 32_768, piece_bytes=32_768)
    host.host_app(app)
    leech = [Agent(f"L{i}", config=AgentConfig(work_timeout_s=600.0),
                   hub=hub) for i in range(6)]
    for a in leech:
        rt.add_node(a)
    rt.crash_hooks.append(hub.node_gone)
    done = lambda: all("lg-app" in a.images for a in leech)
    stats = {"checks": 0, "entries": 0, "max_dup": 0}

    def on_tick(now):
        hub.tick(now)
        entries, max_dup = _assert_ledger_matches_dicts(hub)
        stats["checks"] += 1
        stats["entries"] += entries
        stats["max_dup"] = max(stats["max_dup"], max_dup)

    rt.run_batched(until=3_600, stop_when=done, tick_s=0.5,
                   on_tick=on_tick)
    assert done()
    _assert_ledger_matches_dicts(hub)
    assert rt.events_processed >= 500     # the trace is big enough to count
    assert stats["checks"] > 0 and stats["entries"] > 0
    assert hub.ledger_ops > 0             # the ledger was kept incrementally
    # cancels were exercised: endgame duplicates appeared in the ledger
    # and their losers were cancelled on the winning PIECE_DATA
    cancels = sum(px.cancels_sent for a in leech + [host]
                  for px in [a.px])
    assert stats["max_dup"] >= 2 or cancels > 0


# =========== ISSUE 10: single-pass SwarmState row growth ================ #
def test_swarm_state_growth_single_pass_covers_every_row_array():
    """Capacity growth reallocates every per-row buffer in ONE registry
    walk: any (cap, ...) ndarray on SwarmState must be listed in
    _ROW_ARRAYS (else _grow would silently orphan it), fills must follow
    _ROW_FILL, and existing data must survive a doubling."""
    from repro.core.swarm_arrays import SwarmState
    m = PieceManifest.synthetic("g", 8_000, 1_000)     # P=8 != cap=4
    st = SwarmState("g", m, capacity=4)
    cap = st.have.shape[0]
    assert cap == 4 and st.P == 8
    per_row = {name for name, a in vars(st).items()
               if isinstance(a, np.ndarray) and a.ndim >= 1
               and a.shape[0] == cap}
    assert per_row == set(SwarmState._ROW_ARRAYS)
    assert set(SwarmState._ROW_FILL) <= set(SwarmState._ROW_ARRAYS)
    # populate all four rows, then grow past capacity
    for i in range(4):
        st.ensure_row(f"N{i}")
    st.have[2, 5] = True
    st.have_n[2] = 1
    st.pend_holder[1, 3, 0] = 2
    st.pend_t[1, 3, 0] = 7.25
    st.pend_cnt[1, 3] = 1
    st.pend_n[1] = 1
    st.pipeline[:4] = 6
    st.opt_peer[3] = 1
    st.uc_rows[0, 0] = 3
    st.uc_n[0] = 1
    st.busy_rows[1, 0] = 2
    st.busy_n[1] = 1
    i4 = st.ensure_row("N4")
    assert i4 == 4 and st.have.shape[0] == 8
    for name in SwarmState._ROW_ARRAYS:
        assert getattr(st, name).shape[0] == 8, name
    # old data intact
    assert st.have[2, 5] and int(st.have_n[2]) == 1
    assert int(st.pend_holder[1, 3, 0]) == 2
    assert float(st.pend_t[1, 3, 0]) == 7.25
    assert int(st.pend_cnt[1, 3]) == 1 and int(st.pend_n[1]) == 1
    assert st.pipeline[:4].tolist() == [6] * 4
    assert int(st.opt_peer[3]) == 1
    assert int(st.uc_rows[0, 0]) == 3 and int(st.busy_rows[1, 0]) == 2
    # new rows carry the registered fills
    assert not st.have[5:].any() and not st.alive[5:].any()
    assert (st.opt_peer[5:] == -1).all()
    assert (st.pend_holder[5:] == -1).all()
    assert (st.uc_rows[5:] == -1).all()
    assert (st.ub_rows[5:] == -1).all()
    assert (st.busy_rows[5:] == -1).all()
    assert int(st.pend_cnt[5:].sum()) == 0


# ---------- versioned manifests: (app_id, version) state keying --------- #
def _hub_engine(node_id, hub, **over):
    from repro.core import PieceExchange
    cfg = AgentConfig(**over)
    px = PieceExchange(node_id, cfg, send=lambda dst, msg: None,
                       now=lambda: 0.0, tracker_id="server", hub=hub)
    return px


def test_hub_states_keyed_by_version_never_cross_masks():
    hub = SwarmHub()
    m1 = PieceManifest.synthetic("a", 8_000, 1_000, version=1)
    m2 = PieceManifest.synthetic("a", 8_000, 1_000, version=2, prev=m1,
                                 changed={0})
    seeder = _hub_engine("S", hub)
    seeder.add_local_app("a", m1)
    leech = _hub_engine("L", hub)
    leech.join("a", m2)
    # one state per (app_id, version): the v1 seeder's full mask lives in
    # a different state than the v2 leecher's row — mixed-version swarms
    # can never merge availability
    assert set(hub.states) == {("a", 1), ("a", 2)}
    st2 = hub.states[("a", 2)]
    assert "S" not in st2.row and int(st2.counts.sum()) == 0
    assert hub.has_row("a", "S") and hub.has_row("a", "L")
    # decide_requests for the v2 leecher sees zero holders — it cannot be
    # steered at the v1 seeder
    st1 = hub.states[("a", 1)]
    assert st1.full[st1.row["S"]]


def test_hub_retire_detaches_row_and_prunes_empty_state():
    hub = SwarmHub()
    m1 = PieceManifest.synthetic("a", 8_000, 1_000, version=1)
    m2 = PieceManifest.synthetic("a", 8_000, 1_000, version=2, prev=m1,
                                 changed={0})
    a = _hub_engine("A", hub)
    b = _hub_engine("B", hub)
    a.add_local_app("a", m1)
    b.add_local_app("a", m1)
    assert hub.states[("a", 1)].n_alive == 2
    # A upgrades: its engine retires the v1 row and re-registers under v2
    # (the synthetic publisher path carries no image bytes)
    assert a.upgrade("a", m2, full=True)
    st1 = hub.states[("a", 1)]
    assert st1.n_alive == 1 and not st1.alive[st1.row["A"]]
    assert st1.full[st1.row["B"]]                   # only B's claim remains
    assert set(hub.states) == {("a", 1), ("a", 2)}
    # the last v1 holder upgrading prunes the superseded state entirely
    assert b.upgrade("a", m2, full=True)
    assert set(hub.states) == {("a", 2)}
    assert hub.states[("a", 2)].n_alive == 2
