"""Torrent swarm vs naive fan-out: rounds, seeder load, makespan.

Two layers: the offline `plan_broadcast` planner (analytic round bound)
and the *live* agent/tracker protocol running Scenario V — piece-wise
multi-seeder image distribution with per-node uplink contention and
origin-death failover (paper §V extension).
"""
from __future__ import annotations

import time

from repro.core.swarm import naive_rounds, plan_broadcast, rounds_of, simulate
from repro.parallel.weight_torrent import broadcast_cost_model


def bench_scenario_vii(verbose: bool = True, n_volunteers: int = 200,
                       image_mb: float = 64.0):
    """Scenario VII (flash crowd at scale) as a perf-trajectory row:
    protocol metrics plus simulator throughput."""
    from benchmarks.paper_tables import scenario_vii
    res = scenario_vii(verbose=False, n_volunteers=n_volunteers,
                       image_mb=image_mb)
    row = {
        "name": f"swarm_flashcrowd_n{n_volunteers}_img{int(image_mb)}MB",
        "us_per_call": 0.0,
        "derived": (f"makespan {res['makespan_s']:.0f}s replication "
                    f"{res['full_replication_s']:.0f}s origin_up "
                    f"{res['origin_up_mb']:.0f}MB replicas "
                    f"{res['replicas']}/{n_volunteers} | "
                    f"{res['events_per_sec']:.0f} events/s "
                    f"rss {res['peak_rss_mb']:.0f}MB"),
        "metrics": {k: res[k] for k in
                    ("makespan_s", "full_replication_s", "origin_up_mb",
                     "replicas", "done", "replicated", "events",
                     "events_per_sec", "wall_s", "peak_rss_mb")},
    }
    if verbose:
        print(f"[swarm] {row['name']}: {row['derived']}")
    return [row]


def bench_scenario_viii(verbose: bool = True, n_volunteers: int = 48,
                        image_mb: float = 32.0, seed: int = 8):
    """Scenario VIII (chaos) as a perf-trajectory row: the same N=48
    flash crowd fault-free vs under 10% loss / 200ms jitter / 30% churn,
    reporting the makespan and origin-egress overhead of surviving it.
    The chaos invariants are asserted inside scenario_viii itself."""
    from benchmarks.paper_tables import scenario_viii
    res = scenario_viii(verbose=False, n_volunteers=n_volunteers,
                        image_mb=image_mb, seed=seed)
    b, c = res["baseline"], res["chaos"]
    row = {
        "name": f"swarm_chaos_n{n_volunteers}_img{int(image_mb)}MB"
                f"_seed{seed}",
        "us_per_call": 0.0,
        "derived": (f"makespan {b['makespan_s']:.0f}s->"
                    f"{c['makespan_s']:.0f}s "
                    f"(x{res['makespan_overhead']:.2f}) origin_up "
                    f"{b['origin_up_mb']:.0f}->{c['origin_up_mb']:.0f}MB "
                    f"dropped {c['dropped_msgs']} dup {c['dup_msgs']} "
                    f"restarts {c['restarts']} "
                    f"replicated={c['replicated']}"),
        "metrics": {
            "seed": seed,
            "makespan_overhead": res["makespan_overhead"],
            "egress_overhead": res["egress_overhead"],
            "baseline_makespan_s": b["makespan_s"],
            "chaos_makespan_s": c["makespan_s"],
            "dropped_msgs": c["dropped_msgs"],
            "dup_msgs": c["dup_msgs"],
            "crashes": c["crashes"],
            "restarts": c["restarts"],
            "replicated": c["replicated"],
            "invariants_ok": res["invariants_ok"],
        },
    }
    if verbose:
        print(f"[swarm] {row['name']}: {row['derived']}")
    return [row]


def bench_live(verbose: bool = True, n_volunteers: int = 8,
               image_mb: float = 32.0):
    """Scenarios V + VI through the real protocol (smaller than
    paper_tables' defaults)."""
    from benchmarks.paper_tables import scenario_v, scenario_vi
    res = scenario_v(verbose=False, n_volunteers=n_volunteers,
                     image_mb=image_mb, n_pieces=16, n_parts=24)
    rows = [{
        "name": f"swarm_live_n{n_volunteers}_img{int(image_mb)}MB",
        "us_per_call": 0.0,
        "derived": (f"origin_up {res['single']['origin_up_mb']:.0f}MB->"
                    f"{res['swarm']['origin_up_mb']:.0f}MB "
                    f"makespan {res['single']['makespan_s']:.0f}s->"
                    f"{res['swarm']['makespan_s']:.0f}s "
                    f"failover_done={res['failover']['done']}"),
        "metrics": {"origin_up_mb": res["swarm"]["origin_up_mb"],
                    "makespan_s": res["swarm"]["makespan_s"],
                    "failover_done": res["failover"]["done"]},
    }]
    # choke/endgame effects need a few seeders' worth of swarm: below ~8
    # volunteers the duplicate-execution counts are dominated by noise
    n_vi = max(n_volunteers, 8)
    vi = scenario_vi(verbose=False, n_volunteers=n_vi,
                     image_mb=image_mb, n_pieces=16, n_parts=4 * n_vi)
    rows.append({
        "name": f"swarm_choke_n{n_vi}_img{int(image_mb)}MB",
        "us_per_call": 0.0,
        "derived": (f"dup_execs {vi['baseline']['dup_execs']}->"
                    f"{vi['choked']['dup_execs']} origin_up "
                    f"{vi['baseline']['origin_up_mb']:.0f}MB->"
                    f"{vi['choked']['origin_up_mb']:.0f}MB "
                    f"makespan {vi['baseline']['makespan_s']:.0f}s->"
                    f"{vi['choked']['makespan_s']:.0f}s"),
        "metrics": {k: {"makespan_s": vi[k]["makespan_s"],
                        "origin_up_mb": vi[k]["origin_up_mb"],
                        "dup_execs": vi[k]["dup_execs"],
                        "done": vi[k]["done"]}
                    for k in ("baseline", "unchoked", "choked")},
    })
    if verbose:
        for r in rows:
            print(f"[swarm] {r['name']}: {r['derived']}")
    return rows


def bench_scenario_ix(verbose: bool = True, n_volunteers: int = 500,
                      n_islands: int = 8, image_mb: float = 32.0,
                      backend=None):
    """Scenario IX (topology-aware P4P selection) as perf-trajectory
    rows: the same WAN flash crowd with rarity-only vs cost-aware peer
    selection, one row per mode so bench_guard tracks the cross-ISP
    bytes and p99 completion of each independently."""
    from benchmarks.paper_tables import scenario_ix
    res = scenario_ix(verbose=False, n_volunteers=n_volunteers,
                      n_islands=n_islands, image_mb=image_mb,
                      backend=backend)
    rows = []
    for mode in ("naive", "p4p"):
        m = res[mode]
        rows.append({
            "name": f"swarm_scenario_ix_{mode}_n{n_volunteers}"
                    f"_i{n_islands}",
            "us_per_call": 0.0,
            "derived": (f"cross_isp {m['cross_isp_bytes'] / 1e6:.0f}MB "
                        f"p99 {m['p99_completion_s']:.0f}s makespan "
                        f"{m['makespan_s']:.0f}s replicas "
                        f"{m['replicas']}/{n_volunteers} "
                        f"[{m['backend']}]"),
            "metrics": {"n_volunteers": n_volunteers,
                        "n_islands": n_islands,
                        **{k: m[k] for k in
                           ("cross_isp_bytes", "p99_completion_s",
                            "makespan_s", "full_replication_s",
                            "origin_up_mb", "replicas", "done",
                            "replicated", "events", "events_per_sec",
                            "wall_s", "backend")}},
        })
    rows.append({
        "name": f"swarm_scenario_ix_summary_n{n_volunteers}"
                f"_i{n_islands}",
        "us_per_call": 0.0,
        "derived": (f"cross_isp cut {res['cross_isp_reduction']:.1f}x "
                    f"makespan x{res['makespan_ratio']:.3f} "
                    f"p99 x{res['p99_ratio']:.3f} "
                    f"replicated={res['replicated']}"),
        "metrics": {"cross_isp_reduction": res["cross_isp_reduction"],
                    "makespan_ratio": res["makespan_ratio"],
                    "p99_ratio": res["p99_ratio"],
                    "done": res["done"],
                    "replicated": res["replicated"]},
    })
    if verbose:
        for r in rows:
            print(f"[swarm] {r['name']}: {r['derived']}")
    return rows


def bench_scenario_x(verbose: bool = True, n_volunteers: int = 200,
                     image_mb: float = 64.0, n_pieces: int = 128,
                     delta_frac: float = 0.05, backend=None,
                     include_chaos: bool = True):
    """Scenario X (versioned-manifest delta upgrade) as perf-trajectory
    rows: one row per arm (delta upgrade vs scratch redistribution) so
    bench_guard tracks `upgrade_traffic_bytes` and `upgrade_makespan_s`
    independently, plus a summary row with the >=10x reduction ratios
    and the churn-overlay verdict (`no_stale` / `chaos_ready`)."""
    from benchmarks.paper_tables import scenario_x
    res = scenario_x(verbose=False, n_volunteers=n_volunteers,
                     image_mb=image_mb, n_pieces=n_pieces,
                     delta_frac=delta_frac, backend=backend,
                     include_chaos=include_chaos)
    rows = [{
        "name": f"swarm_scenario_x_upgrade_n{n_volunteers}",
        "us_per_call": 0.0,
        "derived": (f"delta {res['n_changed']}/{n_pieces} pieces: "
                    f"{res['upgrade_traffic_bytes'] / 1e6:.0f}MB "
                    f"{res['upgrade_makespan_s']:.0f}s reused "
                    f"{res['reused_pieces']} "
                    f"upgraded={res['upgraded']}"),
        "metrics": {"n_volunteers": n_volunteers, "n_pieces": n_pieces,
                    **{k: res[k] for k in
                       ("image_mb", "n_changed", "delta_frac",
                        "upgrade_traffic_bytes", "upgrade_makespan_s",
                        "reused_pieces", "upgraded", "stale_accepts",
                        "no_stale", "wall_s")}},
    }, {
        "name": f"swarm_scenario_x_scratch_n{n_volunteers}",
        "us_per_call": 0.0,
        "derived": (f"full {image_mb:.0f}MB redistribution: "
                    f"{res['scratch_traffic_bytes'] / 1e6:.0f}MB "
                    f"{res['scratch_makespan_s']:.0f}s "
                    f"replicated={res['replicated']}"),
        "metrics": {"n_volunteers": n_volunteers, "n_pieces": n_pieces,
                    **{k: res[k] for k in
                       ("image_mb", "scratch_traffic_bytes",
                        "scratch_makespan_s", "v1_makespan_s",
                        "v1_traffic_bytes", "replicated")}},
    }]
    summary = {"n_volunteers": n_volunteers,
               "traffic_reduction": res["traffic_reduction"],
               "makespan_speedup": res["makespan_speedup"],
               "no_stale": res["no_stale"],
               "upgraded": res["upgraded"],
               "replicated": res["replicated"]}
    if include_chaos:
        c = res["chaos"]
        summary["chaos_ready"] = res["chaos_ready"]
        summary["chaos_reused_pieces"] = c["reused_pieces"]
        summary["chaos_stale_have_demoted"] = c["stale_have_demoted"]
        summary["chaos_stale_accepts"] = c["stale_accepts"]
    rows.append({
        "name": f"swarm_scenario_x_summary_n{n_volunteers}",
        "us_per_call": 0.0,
        "derived": (f"traffic /{res['traffic_reduction']:.1f} makespan "
                    f"x{res['makespan_speedup']:.1f} "
                    f"no_stale={res['no_stale']} "
                    f"chaos_ready={summary.get('chaos_ready')}"),
        "metrics": summary,
    })
    if verbose:
        for r in rows:
            print(f"[swarm] {r['name']}: {r['derived']}")
    return rows


def bench_scenario_xi(verbose: bool = True, n_replicas: int = 50,
                      ckpt_mb: float = 2048.0, n_islands: int = 8,
                      n_pieces: int = 128):
    """Scenario XI (swarm-served checkpoints) as perf-trajectory rows:
    replica cold-start flash crowd, origin-only vs swarm on flat and
    island topologies, one row per (mode, topology) so bench_guard
    tracks `ttr_p99_s` and `origin_egress_bytes` independently, plus a
    summary row with the reduction ratios and the origin-death chaos
    verdict."""
    from benchmarks.paper_tables import scenario_xi
    res = scenario_xi(verbose=False, n_replicas=n_replicas,
                      ckpt_mb=ckpt_mb, n_islands=n_islands,
                      n_pieces=n_pieces)
    rows = []
    topos = [("flat", res["flat"])]
    if "islands" in res:
        topos.append((f"isl{n_islands}", res["islands"]))
    for tag, pair in topos:
        for mode in ("origin", "swarm"):
            m = pair[mode]
            rows.append({
                "name": f"ckpt_flashcrowd_{mode}_r{n_replicas}_{tag}",
                "us_per_call": 0.0,
                "derived": (f"ttr_p99 {m['ttr_p99_s']:.0f}s max "
                            f"{m['ttr_max_s']:.0f}s origin_egress "
                            f"{m['origin_egress_bytes'] / 1e9:.2f}GB "
                            f"ready {m['replicas_ready']}/{n_replicas}"),
                "metrics": {"n_replicas": n_replicas, "ckpt_mb": ckpt_mb,
                            **{k: m[k] for k in
                               ("ttr_p99_s", "ttr_max_s", "ttr_median_s",
                                "origin_egress_bytes", "cross_isp_bytes",
                                "ready", "replicas_ready", "events")}},
            })
    summary = {"ckpt_mb": ckpt_mb,
               "egress_reduction_flat": res["egress_reduction_flat"],
               "ttr_p99_speedup_flat": res["ttr_p99_speedup_flat"],
               "all_ready": res["all_ready"]}
    if "islands" in res:
        summary["egress_reduction_islands"] = \
            res["egress_reduction_islands"]
        summary["ttr_p99_speedup_islands"] = \
            res["ttr_p99_speedup_islands"]
    if "chaos" in res:
        summary["chaos_ready"] = res["chaos"]["ready"]
        summary["chaos_origin_died_at_s"] = \
            res["chaos"]["origin_died_at_s"]
    rows.append({
        "name": f"ckpt_flashcrowd_summary_r{n_replicas}",
        "us_per_call": 0.0,
        "derived": (f"flat: egress /{res['egress_reduction_flat']:.1f} "
                    f"ttr_p99 x{res['ttr_p99_speedup_flat']:.1f} | "
                    f"chaos_ready={summary.get('chaos_ready')} "
                    f"all_ready={res['all_ready']}"),
        "metrics": summary,
    })
    if verbose:
        for r in rows:
            print(f"[swarm] {r['name']}: {r['derived']}")
    return rows


def bench_sweep(ns, verbose: bool = True, backend=None,
                tick_s: float = 0.5, profile: bool = False):
    """N-sweep of the *batched* array-native Scenario VII: one row per N
    with events/s (logical and heap), wall-clock and peak RSS.  This is
    the scaling curve the batched engine exists for — the per-message
    path tops out around N≈500 while the hub path (with the ISSUE-10
    array ledger + fused tick) reaches N=10000.  With `profile`, each
    row also carries the per-tick wall breakdown: host Python vs kernel
    milliseconds, drain (message-burst) seconds and the incremental
    ledger-update count — the numbers that show host time staying
    sublinear in N."""
    from benchmarks.paper_tables import scenario_vii
    rows = []
    for n in ns:
        res = scenario_vii(verbose=False, n_volunteers=n, batched=True,
                           backend=backend, tick_s=tick_s)
        row = {
            "name": f"swarm_sweep_batched_n{n}",
            "us_per_call": 0.0,
            "derived": (f"makespan {res['makespan_s']:.0f}s replication "
                        f"{res['full_replication_s']:.0f}s replicas "
                        f"{res['replicas']}/{n} | "
                        f"{res['events_per_sec']:.0f} logical ev/s "
                        f"({res['heap_events_per_sec']:.0f} heap) "
                        f"wall {res['wall_s']:.1f}s "
                        f"rss {res['peak_rss_mb']:.0f}MB "
                        f"[{res['backend']}]"),
            "metrics": {k: res[k] for k in
                        ("n_volunteers", "makespan_s",
                         "full_replication_s", "p99_completion_s",
                         "cross_isp_bytes", "origin_up_mb", "replicas",
                         "done", "replicated", "events", "logical_events",
                         "events_per_sec", "heap_events_per_sec",
                         "batch_ops", "coalesced_events", "ticks",
                         "wall_s", "peak_rss_mb", "backend")},
        }
        if profile:
            ticks = max(int(res.get("ticks", 0)), 1)
            tick_w = float(res.get("tick_wall_s", 0.0))
            kern_w = float(res.get("kernel_wall_s", 0.0))
            host_ms = (tick_w - kern_w) / ticks * 1e3
            row["metrics"].update({
                "tick_wall_s": res.get("tick_wall_s"),
                "kernel_wall_s": res.get("kernel_wall_s"),
                "drain_wall_s": res.get("drain_wall_s"),
                "ledger_ops": res.get("ledger_ops"),
                "host_ms_per_tick": host_ms,
                "kernel_ms_per_tick": kern_w / ticks * 1e3,
            })
            row["derived"] += (
                f" | tick {tick_w:.1f}s (host {host_ms:.1f}ms/tick, "
                f"kernel {kern_w / ticks * 1e3:.1f}ms/tick) drain "
                f"{res.get('drain_wall_s', 0.0):.1f}s "
                f"ledger_ops {res.get('ledger_ops')}")
        rows.append(row)
        if verbose:
            print(f"[swarm] {row['name']}: {row['derived']}")
    return rows


def merge_rows(path, rows):
    """Merge bench rows into an existing BENCH json by row name (new rows
    replace same-named rows, others are preserved) so `--sweep` runs can
    update the scaling curve without clobbering the rest of the file."""
    import json
    import os
    doc = {"bench": "swarm", "rows": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    by_name = {r["name"]: i for i, r in enumerate(doc.get("rows", []))}
    for r in rows:
        if r["name"] in by_name:
            doc["rows"][by_name[r["name"]]] = r
        else:
            doc["rows"].append(r)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=str)
    return doc


def bench(verbose: bool = True, smoke: bool = False):
    rows = []
    plan_cases = [(8, 8), (16, 16), (64, 64)] if smoke else \
        [(8, 8), (16, 16), (64, 64), (256, 64), (1024, 128)]
    for n_nodes, n_pieces in plan_cases:
        t0 = time.perf_counter()
        plan = plan_broadcast(n_nodes, n_pieces, fanout=1)
        dt = (time.perf_counter() - t0) * 1e6
        r = rounds_of(plan)
        nr = naive_rounds(n_nodes, n_pieces)
        stats = simulate(plan, piece_bytes=64e6, link_Bps=25e9,
                         n_nodes=n_nodes)
        rows.append({
            "name": f"swarm_plan_n{n_nodes}_p{n_pieces}",
            "us_per_call": dt,
            "derived": (f"rounds={r} naive={nr} speedup={nr / r:.1f}x "
                        f"seeder_up={stats.seeder_uploads}"),
        })
    # analytic ppermute-ring model at checkpoint scale (20B params bf16)
    cm = broadcast_cost_model(40e9, n_pods=8)
    rows.append({"name": "weight_torrent_40GB_8pods", "us_per_call": 0.0,
                 "derived": (f"torrent={cm['torrent_s']:.1f}s "
                             f"naive={cm['naive_s']:.1f}s "
                             f"speedup={cm['speedup']:.2f}x")})
    if verbose:
        for r in rows:
            print(f"[swarm] {r['name']}: {r['derived']}")
    rows += bench_live(verbose=verbose,
                       n_volunteers=6 if smoke else 8,
                       image_mb=16.0 if smoke else 32.0)
    # Scenario VII — the flash crowd runs at full N=200 even in smoke (the
    # incremental engine made it cheap enough for CI); a quick N=64 run
    # rides along for the scaling curve
    from benchmarks import exchange_bench
    rows += bench_scenario_vii(verbose=verbose, n_volunteers=64)
    rows += bench_scenario_vii(verbose=verbose, n_volunteers=200)
    # Scenario VIII chaos rows ride along at full N=48 even in smoke: the
    # fault-tolerance overhead is a tracked trajectory metric like the
    # flash-crowd numbers above
    rows += bench_scenario_viii(verbose=verbose)
    # Scenario IX (P4P): smoke runs the CI-sized N=64/4-island WAN, the
    # full bench the headline N=500/8-island configuration
    if smoke:
        rows += bench_scenario_ix(verbose=verbose, n_volunteers=64,
                                  n_islands=4, image_mb=8.0)
    else:
        rows += bench_scenario_ix(verbose=verbose)
    # Scenario XI (swarm-served checkpoints): smoke runs the CI-sized
    # R=8/256MB flash crowd, the full bench the headline R=50/2GB one
    if smoke:
        rows += bench_scenario_xi(verbose=verbose, n_replicas=8,
                                  ckpt_mb=256.0, n_islands=4,
                                  n_pieces=64)
    else:
        rows += bench_scenario_xi(verbose=verbose)
    # pump micro-benchmark: the ≥10x incremental-vs-reference ratio is the
    # acceptance gate for the bookkeeping rewrite
    rows += exchange_bench.bench(verbose=verbose, smoke=smoke)
    return rows


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scale for CI")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows as JSON (perf trajectory artifact)")
    ap.add_argument("--sweep", metavar="N1,N2,...",
                    help="run ONLY the batched Scenario VII N-sweep at "
                         "these sizes (e.g. 50,200,500,1000,2000); with "
                         "--json, rows are merged into the file by name "
                         "instead of overwriting it")
    ap.add_argument("--backend", choices=("numpy", "jax", "pallas"),
                    help="kernel backend for --sweep (default: best "
                         "available)")
    ap.add_argument("--profile", action="store_true",
                    help="with --sweep: add the per-tick wall breakdown "
                         "(host vs kernel ms, drain seconds, ledger "
                         "update counts) to each row")
    ap.add_argument("--scenario-ix", metavar="N,K",
                    help="run ONLY Scenario IX (P4P vs naive) at N "
                         "volunteers over K islands (e.g. 500,8 or the "
                         "CI smoke 64,4); with --json, rows are merged "
                         "into the file by name")
    ap.add_argument("--scenario-x", metavar="N",
                    help="run ONLY Scenario X (versioned-manifest delta "
                         "upgrade) with N volunteers (e.g. 200 or the CI "
                         "smoke 32); with --json, rows are merged into "
                         "the file by name")
    ap.add_argument("--scenario-xi", metavar="R,MB",
                    help="run ONLY Scenario XI (checkpoint flash crowd) "
                         "at R replicas pulling an MB-sized checkpoint "
                         "(e.g. 50,2048 or the CI smoke 8,256); with "
                         "--json, rows are merged into the file by name")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.scenario_x:
        n = int(args.scenario_x)
        rows = bench_scenario_x(
            n_volunteers=n, image_mb=8.0 if n <= 64 else 64.0,
            n_pieces=64 if n <= 64 else 128, backend=args.backend)
        if args.json:
            merge_rows(args.json, rows)
            print(f"[swarm] merged {len(rows)} scenario-x rows "
                  f"into {args.json}")
        return
    if args.scenario_xi:
        r, mb = (int(x) for x in args.scenario_xi.split(","))
        rows = bench_scenario_xi(n_replicas=r, ckpt_mb=float(mb),
                                 n_islands=4 if r <= 16 else 8,
                                 n_pieces=64 if r <= 16 else 128)
        if args.json:
            merge_rows(args.json, rows)
            print(f"[swarm] merged {len(rows)} scenario-xi rows "
                  f"into {args.json}")
        return
    if args.scenario_ix:
        n, k = (int(x) for x in args.scenario_ix.split(","))
        rows = bench_scenario_ix(n_volunteers=n, n_islands=k,
                                 image_mb=8.0 if n <= 100 else 32.0,
                                 backend=args.backend)
        if args.json:
            merge_rows(args.json, rows)
            print(f"[swarm] merged {len(rows)} scenario-ix rows "
                  f"into {args.json}")
        return
    if args.sweep:
        ns = [int(x) for x in args.sweep.split(",") if x.strip()]
        rows = bench_sweep(ns, backend=args.backend,
                           profile=args.profile)
        if args.json:
            merge_rows(args.json, rows)
            print(f"[swarm] merged {len(rows)} sweep rows "
                  f"into {args.json}")
        return
    rows = bench(smoke=args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "swarm", "smoke": args.smoke,
                       "rows": rows}, f, indent=2, default=str)
        print(f"[swarm] wrote {args.json}")


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
