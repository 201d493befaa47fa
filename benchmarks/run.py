"""Benchmark harness: one function per paper table + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (stdout also carries the
human-readable lines each bench emits).
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []

    # ---- paper tables I-IV (the reproduction) -------------------------- #
    from benchmarks import paper_tables as pt
    for name, fn in pt.ALL_TABLES.items():
        t0 = time.perf_counter()
        res = fn(verbose=True)
        dt = (time.perf_counter() - t0) * 1e6
        if name == "table1":
            derived = (f"speedup_host={res['speedup_vs_host']:.2f}"
                       f"(paper1.56) vm={res['speedup_vs_vm']:.2f}(1.73)")
        elif name == "table2":
            derived = (f"faster={res['faster_than_seq_pct']:.0f}%"
                       f"(paper~33%) makespan={res['makespan_h']:.2f}h(4.48)")
        elif name == "table3":
            derived = (f"app1={res['app1_h']:.2f}h(2.88) "
                       f"app2={res['app2_h']:.2f}h(3.50)")
        elif name == "scenario_v":
            derived = (f"origin_bytes/{res['origin_bytes_reduction']:.0f} "
                       f"makespan_x{res['makespan_speedup']:.0f} "
                       f"failover_done={res['failover']['done']}")
        elif name == "scenario_vi":
            derived = (f"dup_execs {res['baseline']['dup_execs']}->"
                       f"{res['choked']['dup_execs']} origin_up "
                       f"{res['baseline']['origin_up_mb']:.0f}MB->"
                       f"{res['choked']['origin_up_mb']:.0f}MB "
                       f"makespan {res['baseline']['makespan_s']:.0f}s->"
                       f"{res['choked']['makespan_s']:.0f}s")
        elif name == "scenario_vii":
            derived = (f"N={res['n_volunteers']} makespan="
                       f"{res['makespan_s']:.0f}s replication="
                       f"{res['full_replication_s']:.0f}s origin_up="
                       f"{res['origin_up_mb']:.0f}MB "
                       f"{res['events_per_sec']:.0f}ev/s "
                       f"rss={res['peak_rss_mb']:.0f}MB")
        elif name == "scenario_viii":
            derived = (f"chaos makespan x{res['makespan_overhead']:.2f} "
                       f"egress x{res['egress_overhead']:.2f} "
                       f"dropped={res['chaos']['dropped_msgs']} "
                       f"restarts={res['chaos']['restarts']} "
                       f"replicated={res['replicated']}")
        elif name == "scenario_ix":
            derived = (f"cross_isp/{res['cross_isp_reduction']:.1f} "
                       f"p99_x{res['p99_ratio']:.2f} "
                       f"replicated={res['replicated']}")
        elif name == "scenario_xi":
            derived = (f"R={res['n_replicas']} "
                       f"egress/{res['egress_reduction_flat']:.1f} "
                       f"ttr_p99_x{res['ttr_p99_speedup_flat']:.1f} "
                       f"all_ready={res['all_ready']}")
        else:
            derived = (f"speedup1={res['speedup_app1']:.2f}(3.5) "
                       f"speedup2={res['speedup_app2']:.2f}(3.3)")
        rows.append({"name": f"paper_{name}", "us_per_call": dt,
                     "derived": derived})

    # ---- framework benches --------------------------------------------- #
    from benchmarks import (checkpoint_bench, kernel_bench,
                            scheduler_bench, swarm_bench)
    rows += swarm_bench.bench()
    rows += checkpoint_bench.bench()
    rows += scheduler_bench.bench()
    rows += kernel_bench.bench()

    # ---- roofline summary (if dry-run artifacts exist) ------------------ #
    try:
        from repro.launch.roofline import load_cells
        cells = load_cells("artifacts/dryrun", "16x16")
        if cells:
            worst = min(cells, key=lambda c: c.roofline_fraction)
            med = sorted(c.roofline_fraction for c in cells)[len(cells) // 2]
            rows.append({
                "name": "roofline_summary", "us_per_call": 0.0,
                "derived": (f"{len(cells)} cells; median_frac={med:.3f}; "
                            f"worst={worst.arch}/{worst.shape}="
                            f"{worst.roofline_fraction:.3f}")})
    except Exception as e:  # noqa: BLE001
        print(f"(roofline summary skipped: {e})", file=sys.stderr)

    print("\nname,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"")


if __name__ == "__main__":
    main()
