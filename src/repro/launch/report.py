"""Generate the data-driven sections of EXPERIMENTS.md from artifacts."""
from __future__ import annotations

import glob
import json
import os

from repro.launch.hlo_analysis import collective_link_bytes
from repro.launch.mesh import V5E, hardware
from repro.launch.roofline import analyze_cell, load_cells, markdown_table


def load(art, arch, shape, mesh="16x16", variant=None):
    suffix = f"__{variant}" if variant else ""
    fn = os.path.join(art, f"{arch}__{shape}__{mesh}{suffix}.json")
    if not os.path.exists(fn):
        return None
    with open(fn) as f:
        return json.load(f)


def terms(rec):
    h = rec["hlo"]
    hw = hardware(V5E)
    link = collective_link_bytes(h.get("coll_ops", []))
    return {
        "flops": h["flops"],
        "bytes": h["bytes_accessed"],
        "coll_raw": h["collective_bytes"],
        "coll_link": link,
        "compute_s": h["flops"] / hw["peak_flops_bf16"],
        "memory_s": h["bytes_accessed"] / hw["hbm_bandwidth"],
        "coll_s": link / hw["ici_link_bandwidth"],
        "temp_gib": rec["memory"]["temp_bytes"] / 2**30,
        "kinds": h.get("collectives", {}),
    }


def dryrun_section(art="artifacts/dryrun") -> str:
    rows = ["| arch | shape | mesh | status | HLO flops/dev | coll B/dev | "
            "args GiB | temp GiB |", "|---|---|---|---|---|---|---|---|"]
    for fn in sorted(glob.glob(os.path.join(art, "*.json"))):
        if "__tp_sp" in fn or "__pad" in fn or "__moe_int8" in fn \
                or "__flash_full" in fn:
            continue
        rec = json.load(open(fn))
        if rec["status"] == "ok":
            h = rec["hlo"]
            rows.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | ok | "
                f"{h['flops']:.2e} | {h['collective_bytes']:.2e} | "
                f"{rec['memory']['argument_bytes'] / 2**30:.2f} | "
                f"{rec['memory']['temp_bytes'] / 2**30:.2f} |")
        elif rec["status"] == "skipped":
            rows.append(f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} "
                        f"| skipped | - | - | - | - |")
        else:
            rows.append(f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} "
                        f"| ERROR | - | - | - | - |")
    return "\n".join(rows)


def perf_row(label, rec):
    t = terms(rec)
    return (f"| {label} | {t['flops']:.3e} | {t['bytes']:.3e} | "
            f"{t['coll_link']:.3e} | {t['compute_s']:.2f} | "
            f"{t['memory_s']:.2f} | {t['coll_s']:.2f} | "
            f"{t['temp_gib']:.1f} |")


PERF_HDR = ("| variant | HLO flops/dev | HLO bytes/dev | coll link-B/dev | "
            "compute s | memory s | coll s | temp GiB |\n"
            "|---|---|---|---|---|---|---|---|")


def main():
    art = "artifacts/dryrun"
    print("## §Dry-run\n")
    print(dryrun_section(art))
    print("\n\n## §Roofline (single-pod 16x16)\n")
    cells = load_cells(art, "16x16")
    print(markdown_table(cells))
    print("\n\n## §Perf cells\n")
    for arch, shape, variants in [
        ("internlm2-20b", "train_4k",
         ["flash_full", None, "tp_sp", "tp_sp+remat_dots"]),
        ("qwen3-14b", "prefill_32k", [None, "pad_heads", "tp_sp+pad"]),
        ("qwen3-moe-30b-a3b", "train_4k",
         [None, "moe_int8", "tp_sp+moe_int8"]),
    ]:
        print(f"### {arch} / {shape}\n")
        print(PERF_HDR)
        for v in variants:
            rec = load(art, arch, shape, variant=v)
            if rec and rec.get("status") == "ok":
                print(perf_row(v or "baseline(flash)", rec))
        print()


if __name__ == "__main__":
    main()
